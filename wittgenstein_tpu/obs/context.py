"""Trace context: the correlated identity spine of a run.

Every layer of the stack already emits records — SpanTracer Chrome
traces (telemetry/trace.py), JSONL run records (telemetry/export.py),
supervisor provenance + checkpoint manifests (runtime/supervisor.py,
engine/checkpoint.py), serve metrics (serve/metrics.py) — but until
this module they were uncorrelated: a failed job could not be
reconstructed end-to-end without hand-joining logs.  A TraceContext is minted ONCE, at serve admission or
bench entry, and threaded through everything; every record that
carries ``run_id`` can be joined.

Identity semantics:

- ``run_id``    — one durable *run* of work.  Survives SIGKILL + resume:
                  the supervisor writes it into the checkpoint manifest
                  and ADOPTS the stored id when resuming, so the victim
                  process and the resume process share one run_id.
- ``job_id``    — the serve-layer job (``job-NNNNNN``) when the run came
                  through /w/jobs; None for bench / campaign runs.
- ``tenant_id`` — the submitting tenant (serve multi-tenancy).
- ``chunk_seq`` — the chunk index inside a supervised run; stamped by
                  the supervisor per chunk event, not at mint time.

The context is frozen: derive narrowed copies with ``child()``.  It is
pure host-side metadata — nothing here ever touches sim state, so the
telemetry-neutrality standard (bit-identical sim state with tracing
armed) holds by construction.
"""

from __future__ import annotations

import binascii
import dataclasses
import os
import time
from typing import Optional


@dataclasses.dataclass(frozen=True)
class TraceContext:
    """Immutable bundle of correlation ids carried by every obs record."""

    run_id: str
    job_id: Optional[str] = None
    tenant_id: Optional[str] = None
    chunk_seq: Optional[int] = None

    def child(self, **overrides) -> "TraceContext":
        """A copy with some ids narrowed (e.g. ``ctx.child(chunk_seq=3)``)."""
        return dataclasses.replace(self, **overrides)

    def ids(self) -> dict:
        """The non-None ids as a flat dict — the join key set for any
        record (flight-recorder event, span args, run-record field)."""
        out = {"run_id": self.run_id}
        if self.job_id is not None:
            out["job_id"] = self.job_id
        if self.tenant_id is not None:
            out["tenant_id"] = self.tenant_id
        if self.chunk_seq is not None:
            out["chunk_seq"] = self.chunk_seq
        return out


def new_run_id(prefix: str = "run") -> str:
    """A fresh globally-unique-enough run id: ``prefix-SSSSSSSS-RRRRRRRR``
    (unix seconds + 4 random bytes).  Readable in a timeline, sortable
    by mint time, collision-safe across hosts without coordination."""
    stamp = format(int(time.time()) & 0xFFFFFFFF, "08x")
    rand = binascii.hexlify(os.urandom(4)).decode("ascii")
    return f"{prefix}-{stamp}-{rand}"


def mint_context(
    prefix: str = "run",
    job_id: Optional[str] = None,
    tenant_id: Optional[str] = None,
) -> TraceContext:
    """Mint a new root context.  Call this exactly once per unit of
    admitted work — serve admission or bench entry — and thread the
    result; never mint twice for the same run (resume paths must adopt
    the checkpointed id instead, see Supervisor._resume)."""
    return TraceContext(run_id=new_run_id(prefix), job_id=job_id, tenant_id=tenant_id)
