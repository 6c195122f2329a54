"""IServer implementation (reference: wserver Server.java:20-173).

The reference scans the classpath for Protocol subclasses and Message
subtypes with Spring and instantiates them reflectively from WParameters
(Server.java:37-103, :115-126).  Here the protocol registry is explicit
(core.params.protocol_registry — populated by importing
wittgenstein_tpu.protocols) and the message-subtype scan walks the
oracle Message class hierarchy; injection rebuilds messages field-wise,
mirroring Jackson's field-visibility-ANY mapping (ObjectMapperFactory)."""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Type

from ..core.params import WParameters, protocol_registry
from ..oracle.messages import Message, SendMessage


@functools.lru_cache(maxsize=1)
def _message_types() -> Dict[str, Type[Message]]:
    """All concrete Message subtypes (Server.java:115-126's classpath scan,
    done on the live class hierarchy).  Keys: the qualified
    '<module>.<Class>' name always, plus the simple class name when it is
    unambiguous — several protocols define e.g. their own SendSigs, and a
    silent simple-name collision would inject the wrong class.  Cached:
    the hierarchy is fixed once wittgenstein_tpu.protocols is imported."""
    import wittgenstein_tpu.protocols  # noqa: F401  (registers everything)

    out: Dict[str, Type[Message]] = {}
    ambiguous = set()
    stack = list(Message.__subclasses__())
    while stack:
        c = stack.pop()
        stack.extend(c.__subclasses__())
        out[f"{c.__module__.rsplit('.', 1)[-1]}.{c.__name__}"] = c
        if c.__name__ in out:
            ambiguous.add(c.__name__)
        else:
            out[c.__name__] = c
    for name in ambiguous:
        out.pop(name, None)
    return out


def node_to_dict(n) -> dict:
    """JSON view of a node: the reference serializes all public Node fields
    (Node.java:22-88) plus protocol counters via Jackson."""
    d = {
        "nodeId": n.node_id,
        "x": n.x,
        "y": n.y,
        "cityName": n.city_name,
        "byzantine": n.byzantine,
        "down": n.is_down(),
        "doneAt": n.done_at,
        "msgReceived": n.msg_received,
        "msgSent": n.msg_sent,
        "bytesReceived": n.bytes_received,
        "bytesSent": n.bytes_sent,
        "speedRatio": n.speed_ratio,
        "extraLatency": n.extra_latency,
        "external": str(n.external) if n.external is not None else None,
    }
    return d


def message_from_dict(d: dict) -> Message:
    """Rebuild a message field-wise without calling its constructor —
    the analog of Jackson's field mapping (WServer.java:99-110)."""
    d = dict(d)
    typ = d.pop("type")
    cls = _message_types().get(typ)
    if cls is None:
        hint = [k for k in _message_types() if k.endswith("." + typ)]
        raise KeyError(
            f"unknown or ambiguous message type {typ!r}"
            + (f" — use one of {hint}" if hint else "")
        )
    m = cls.__new__(cls)
    for k, v in d.items():
        setattr(m, k, v)
    return m


class Server:
    """The in-process server core: one live protocol at a time."""

    def __init__(self):
        self._protocol = None

    # -- discovery (Server.java:73-113) --------------------------------------
    def get_protocols(self) -> List[str]:
        import wittgenstein_tpu.protocols  # noqa: F401

        return sorted(protocol_registry.keys())

    def get_protocol_parameters(self, name: str) -> WParameters:
        import wittgenstein_tpu.protocols  # noqa: F401

        return protocol_registry[name].default_params()

    def get_parameters_name(self) -> List[str]:
        import wittgenstein_tpu.protocols  # noqa: F401

        return [r.params_cls.__name__ for r in protocol_registry.values()]

    # -- lifecycle (Server.java:32-70) ---------------------------------------
    def init(self, name: str, parameters: Optional[WParameters] = None) -> None:
        import wittgenstein_tpu.protocols  # noqa: F401

        reg = protocol_registry[name]
        if parameters is None:
            parameters = reg.default_params()
        if isinstance(parameters, dict):
            parameters = reg.params_cls.from_dict(parameters)
        self._protocol = reg.factory(parameters)
        self._protocol.init()

    @property
    def protocol(self):
        if self._protocol is None:
            raise RuntimeError("no protocol initialized — POST /w/network/init first")
        return self._protocol

    def run_ms(self, ms: int) -> None:
        self.protocol.network().run_ms(ms)

    def get_time(self) -> int:
        return self.protocol.network().time

    # -- inspection ----------------------------------------------------------
    def get_node_info(self, node_id: Optional[int] = None):
        net = self.protocol.network()
        if node_id is None:
            return [node_to_dict(n) for n in net.all_nodes]
        return node_to_dict(net.get_node_by_id(node_id))

    def get_messages(self) -> List[dict]:
        # msgs.peekMessages (Network.java:279-287 via WServer.java:67-70)
        return [ei.to_dict() for ei in self.protocol.network().msgs.peek_messages()]

    def get_status(self) -> dict:
        """Live-simulation counter summary (the telemetry tier the
        reference never had): aggregate node counters + the network's
        occupancy census and send-time drop count."""
        net = self.protocol.network()
        nodes = net.all_nodes
        return {
            "protocol": type(self._protocol).__name__,
            "time": net.time,
            "nodeCount": len(nodes),
            "liveNodes": sum(1 for n in nodes if not n.is_down()),
            "doneNodes": sum(1 for n in nodes if n.done_at > 0),
            "msgReceived": sum(n.msg_received for n in nodes),
            "msgSent": sum(n.msg_sent for n in nodes),
            "bytesReceived": sum(n.bytes_received for n in nodes),
            "bytesSent": sum(n.bytes_sent for n in nodes),
            "occupancy": net.occupancy(),
            "dropped": net.dropped,
        }

    def metrics_text(self) -> str:
        """Prometheus text exposition of the live sim (GET /metrics).
        Always renders — an uninitialized server reports only its own
        up-ness, so a scraper can attach before the first init."""
        from ..telemetry.export import PromText

        p = PromText("witt")
        p.add("server_up", 1, "wittgenstein-tpu control server alive")
        self._add_cost_metrics(p)
        if self._protocol is None:
            return p.render()
        s = self.get_status()
        p.add("sim_time_ms", s["time"], "simulated time, ms")
        p.add("nodes", s["nodeCount"], "total nodes")
        p.add("live_nodes", s["liveNodes"], "nodes not down")
        p.add("done_nodes", s["doneNodes"], "nodes with doneAt > 0")
        p.add("node_msg_sent_total", s["msgSent"], "node msgSent sum", "counter")
        p.add(
            "node_msg_received_total",
            s["msgReceived"],
            "node msgReceived sum",
            "counter",
        )
        p.add("node_bytes_sent_total", s["bytesSent"],
              "node bytesSent sum", "counter")
        p.add("node_bytes_received_total", s["bytesReceived"],
              "node bytesReceived sum", "counter")
        p.add(
            "messages_dropped_total",
            s["dropped"],
            "sends filtered at send time (down/partition/discard)",
            "counter",
        )
        occ = s["occupancy"]
        p.add("store_pending", occ["pending_msgs"], "in-flight messages")
        p.add("store_pending_buckets", occ["pending_buckets"], "occupied ms buckets")
        p.add("conditional_tasks", occ["conditional_tasks"], "registered conditional tasks")
        return p.render()

    @staticmethod
    def _add_cost_metrics(p) -> None:
        """witt_run_cache_* (compiled-program cache counters + compile
        seconds, from parallel.replica_shard) and witt_probe_* (TTL'd
        TPU probe verdict, from profiling.probe) — the ISSUE-7 cost/
        visibility families.  Failures never break /metrics: these are
        best-effort observability, rendered as absent when the process
        has no jax / no probe cache."""
        try:
            from ..parallel.replica_shard import run_cache_info

            info = run_cache_info()
            p.add("run_cache_size", info["size"],
                  "cached compiled run programs", "gauge")
            p.add("run_cache_hits_total", info["hits"],
                  "run-cache lookups served from cache", "counter")
            p.add("run_cache_misses_total", info["misses"],
                  "run-cache lookups that built a new entry", "counter")
            p.add("run_cache_evictions_total", info["evictions"],
                  "run-cache entries dropped by the FIFO bound", "counter")
            p.add("run_cache_compiles_total", info["compiles"],
                  "XLA compiles performed by the run cache", "counter")
            p.add("run_cache_compile_seconds_total",
                  round(info["compile_seconds_total"], 3),
                  "wall-clock spent in run-cache XLA compiles", "counter")
            # the split of the above and the dispatch path, each the
            # total of one witt.host.* span (docs/observability.md)
            p.add("run_cache_lower_seconds_total",
                  round(info["lower_seconds_total"], 6),
                  "wall-clock spent tracing and lowering run programs "
                  "to StableHLO", "counter")
            p.add("run_cache_backend_compile_seconds_total",
                  round(info["backend_compile_seconds_total"], 6),
                  "wall-clock spent in XLA's compile or the persistent "
                  "compilation cache's load", "counter")
            p.add("run_cache_lookup_seconds_total",
                  round(info["lookup_seconds_total"], 6),
                  "wall-clock spent finding the cached entry and "
                  "program of a call", "counter")
            p.add("run_cache_execute_seconds_total",
                  round(info["execute_seconds_total"], 6),
                  "wall-clock spent enqueueing compiled run programs "
                  "(call to return, not device time)", "counter")
            p.add("run_cache_calls_total", info["calls"],
                  "compiled run programs enqueued", "counter")
            # the work census (engine.core.Census): what the chunks did,
            # counted on the device; each peak beside the static limit
            # it sizes (docs/observability.md names the knob)
            for name, what in (
                ("steps", "loop trips that executed a step"),
                ("store_rows", "rows accepted into the wheel or the lane"),
                ("view_overflow_steps",
                 "steps whose due rows passed due_view_rows (whole-lane branch)"),
                ("landed_rows", "rows the every-tick channel sends' claim let land"),
                ("extra_commit_rounds",
                 "commit rounds beyond a send's first (its landing capacity passed)"),
                ("fired_rows",
                 "rows the every-tick channel sends carried with their mask set"),
                ("firing_overflows",
                 "every-tick channel sends whose fired rows passed firing_capacity"),
                ("fanout_senders",
                 "senders a fan-out expanded into their rows (apply_fanout)"),
                ("fanout_overflows",
                 "fan-outs whose firing senders passed their capacity (another round)"),
                ("masked_sends",
                 "rows a send's mask set and its reach check did not store "
                 "(an end down, across a partition line, past the discard time)"),
                ("discarded_rows",
                 "due rows the delivery's reach check did not deliver"),
            ):
                p.add(f"run_cache_census_{name}_total",
                      info[f"census_{name}_total"], what, "counter")
            for name, knob in (
                ("due_rows_peak", "due_view_rows"),
                ("wheel_fill_peak", "wheel_slots"),
                ("lane_live_peak", "overflow_capacity"),
                ("firing_peak", "firing_capacity(rows)"),
                ("fanout_peak", "the protocol's largest FanOut.capacity"),
                ("landing_peak", "landing_capacity(M)"),
            ):
                p.add(f"run_cache_census_{name}", info[f"census_{name}"],
                      f"most seen since process start, against {knob}", "gauge")
                p.add(f"run_cache_census_{name}_limit",
                      info[f"census_{name}_limit"],
                      f"{knob} of the program that reached the peak", "gauge")
            p.add("run_cache_census_seconds_total",
                  round(info["census_seconds_total"], 6),
                  "wall-clock spent starting the census copies and "
                  "folding them", "counter")
            p.add("run_cache_gc_pause_seconds_total",
                  round(info["gc_pause_seconds_total"], 6),
                  "wall-clock inside Python's cyclic collector", "counter")
            p.add("run_cache_gc_collections_total",
                  info["gc_collections_total"],
                  "collections of Python's cyclic collector", "counter")
        except Exception:
            pass
        try:
            from ..search.driver import search_metrics

            sm = search_metrics()
            p.add("search_generations_total", sm["generations_total"],
                  "adversary-search generations evaluated", "counter")
            p.add("search_evals_total", sm["evals_total"],
                  "adversary-search replica rows evaluated", "counter")
            p.add("search_eval_seconds_total",
                  round(sm["eval_seconds_total"], 3),
                  "wall-clock spent in adversary-search sweeps", "counter")
            p.add("search_pinned_total", sm["pinned_total"],
                  "champions pinned as regression scenarios", "counter")
            p.add("search_best_objective", sm["best_objective"],
                  "last champion objective value seen", "gauge")
        except Exception:
            pass
        try:
            from ..profiling.probe import add_probe_metrics

            add_probe_metrics(p)
        except Exception:
            pass

    # -- control -------------------------------------------------------------
    def start_node(self, node_id: int) -> None:
        self.protocol.network().get_node_by_id(node_id).start()

    def stop_node(self, node_id: int) -> None:
        self.protocol.network().get_node_by_id(node_id).stop()

    def set_external(self, node_id: int, address: str) -> None:
        from .external import ExternalMockImplementation, ExternalRest

        node = self.protocol.network().get_node_by_id(node_id)
        if address == "mock" or address.startswith("mock:"):
            node.external = ExternalMockImplementation(self.protocol.network())
        else:
            node.external = ExternalRest(address)

    def send_message(self, msg) -> None:
        """Inject a SendMessage (Server.java:152-161)."""
        if isinstance(msg, dict):
            inner = msg.get("message")
            if isinstance(inner, dict):
                inner = message_from_dict(inner)
            msg = SendMessage(
                msg["from"], list(msg["to"]), msg["sendTime"],
                msg.get("delayBetweenSend", 0), inner,
            )
        net = self.protocol.network()
        frm = net.get_node_by_id(msg.from_id)
        dests = [net.get_node_by_id(i) for i in msg.to]
        send_time = max(msg.send_time, net.time + 1)
        net.send(msg.message, send_time, frm, dests, msg.delay_between_send)
