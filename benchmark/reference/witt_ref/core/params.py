"""Typed parameter objects + protocol registry.

The reference uses `WParameters` value-objects (JSON-polymorphic) and a
reflection-scanned protocol registry for its REST server
(reference: core WParameters.java:11, wserver Server.java:37-103).  Here the
same contract is explicit: protocols register themselves under a name, their
parameter dataclass must be default-constructible (that is what lets the API
layer discover default parameters), and parameters round-trip through JSON
with a `type` tag.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, Type


@dataclasses.dataclass
class WParameters:
    """Base class for protocol parameters.  Subclasses are dataclasses with
    defaults for every field (default-constructible contract)."""

    def to_json(self) -> str:
        d = {"type": type(self).__name__}
        d.update(dataclasses.asdict(self))
        return json.dumps(d)

    @classmethod
    def from_json(cls, s: str) -> "WParameters":
        d = json.loads(s)
        return cls.from_dict(d)

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "WParameters":
        d = dict(d)
        typ = d.pop("type", None)
        klass = cls
        if typ is not None and typ != cls.__name__:
            klass = _params_types.get(typ)
            if klass is None:
                raise KeyError(f"unknown parameters type {typ!r}")
        fields = {f.name for f in dataclasses.fields(klass) if f.init}
        return klass(**{k: v for k, v in d.items() if k in fields})

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        _params_types[cls.__name__] = cls

    def __str__(self) -> str:  # reflective toString parity (Strings.java:7-23)
        inner = ", ".join(
            f"{f.name}={getattr(self, f.name)}" for f in dataclasses.fields(self)
        )
        return f"{type(self).__name__}{{{inner}}}"


_params_types: Dict[str, Type[WParameters]] = {}

# ---------------------------------------------------------------------------
# Protocol registry: name -> (protocol factory, parameters class).
# The factory takes a single parameters instance, mirroring the reference
# contract "public constructor taking WParameters" (Protocol.java:9-22).
# ---------------------------------------------------------------------------

protocol_registry: Dict[str, "RegisteredProtocol"] = {}


@dataclasses.dataclass(frozen=True)
class RegisteredProtocol:
    name: str
    factory: Callable[[WParameters], Any]
    params_cls: Type[WParameters]

    def default_params(self) -> WParameters:
        return self.params_cls()


def register_protocol(name: str, params_cls: Type[WParameters]):
    """Class decorator: @register_protocol("Handel", HandelParameters)."""

    def deco(klass):
        protocol_registry[name] = RegisteredProtocol(name, klass, params_cls)
        klass.protocol_name = name
        return klass

    return deco
