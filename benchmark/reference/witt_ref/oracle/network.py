"""The oracle discrete-event engine.

Reference semantics: core Network.java (event loop, message storage,
send paths, tasks, partitions) and Envelope.java (single/multi-dest
envelopes with latencies recomputed from a per-envelope random seed).

Exactness notes (each is an observable ordering/determinism invariant):
  * one JavaRandom(0) per network, consumed in the same order as the
    reference (Network.java:32);
  * within one millisecond, deliveries are LIFO in insertion order
    (MsgsSlot head-insertion, Network.java:113-147);
  * multi-dest sends consume ONE random int and derive each destination's
    jitter from getPseudoRandom(destId, seed) — the xorshift hash at
    Network.java:493-503;
  * conditional tasks are polled once per empty millisecond over a snapshot
    taken lazily per nextMessage call (Network.java:533-570);
  * messages to another partition or to/from down nodes are dropped at send
    time, but the sender's counters still tick (Network.java:469-487).
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Generic, List, Optional, TypeVar

from ..core.latency import IC3NetworkLatency, NetworkLatency
from ..core.node import MAX_X, Node
from ..utils.javaops import i32, java_abs, java_mod, lshift32, ushift_r
from ..utils.javarand import JavaRandom
from .messages import ConditionalTask, Message, PeriodicTask, SendMessage, Task

TN = TypeVar("TN", bound=Node)


def get_pseudo_random(node_id: int, random_seed: int) -> int:
    """Deterministic per-destination delta in [0, 99]
    (Network.getPseudoRandom, Network.java:493-503)."""
    a = i32(node_id)
    a = i32(a ^ lshift32(a, 13))
    a = i32(a ^ ushift_r(a, 17))
    a = i32(a ^ lshift32(a, 5))
    x = i32(a ^ i32(random_seed))
    return java_abs(java_mod(x, 100))


class EnvelopeInfo:
    """Serializable view of an in-flight message (EnvelopeInfo.java)."""

    def __init__(self, from_id: int, to_id: int, sent_at: int, arriving_at: int, msg: Message):
        self.from_id = from_id
        self.to = to_id
        self.sent_at = sent_at
        self.arriving_at = arriving_at
        self.msg = msg

    def _cmp(self, o: "EnvelopeInfo") -> int:
        # Exact port of the (quirky) reference comparator
        # (EnvelopeInfo.java:33-47): several branches re-compare arrivingAt,
        # making them no-ops; the sort is stable, so relative order holds.
        if self.arriving_at != o.arriving_at:
            return -1 if self.arriving_at < o.arriving_at else 1
        if self.sent_at != o.sent_at:
            return 0
        if self.from_id != o.from_id:
            return -1 if self.from_id < o.from_id else 1
        return 0

    sort_key = functools.cmp_to_key(_cmp)

    def to_dict(self) -> dict:
        return {
            "from": self.from_id,
            "to": self.to,
            "sentAt": self.sent_at,
            "arrivingAt": self.arriving_at,
            "msg": type(self.msg).__name__,
        }


# ---------------------------------------------------------------------------
# Envelopes (Envelope.java)
# ---------------------------------------------------------------------------


class _Envelope:
    __slots__ = ("send_time",)

    def __init__(self, send_time: int):
        self.send_time = send_time

    def get_message(self) -> Message: ...
    def next_dest_id(self) -> int: ...
    def next_arrival_time(self, network: "Network") -> int: ...
    def mark_read(self) -> None: ...
    def has_next_reader(self) -> bool: ...
    def from_id(self) -> int: ...
    def infos(self, network: "Network") -> List[EnvelopeInfo]: ...

    def cur_infos(self, network: "Network") -> EnvelopeInfo:
        return EnvelopeInfo(
            self.from_id(),
            self.next_dest_id(),
            self.send_time,
            self.next_arrival_time(network),
            self.get_message(),
        )


class SingleDestEnvelope(_Envelope):
    __slots__ = ("message", "_from_id", "_to_id", "_arrival")

    def __init__(self, message, from_node, to_node, send_time, arrival_time):
        super().__init__(send_time)
        self.message = message
        self._from_id = from_node.node_id
        self._to_id = to_node.node_id
        self._arrival = arrival_time

    def get_message(self):
        return self.message

    def next_dest_id(self):
        return self._to_id

    def next_arrival_time(self, network):
        return self._arrival

    def mark_read(self):
        pass

    def has_next_reader(self):
        return False

    def from_id(self):
        return self._from_id

    def infos(self, network):
        return [
            EnvelopeInfo(self._from_id, self._to_id, self.send_time, self._arrival, self.message)
        ]


class MultipleDestEnvelope(_Envelope):
    """One envelope for thousands of destinations; per-destination latency is
    recomputed on demand from (randomSeed, destId) — the reference's memory
    trick (Envelope.java:46-56), which maps to counter-based RNG on TPU."""

    __slots__ = ("message", "_from_id", "random_seed", "dest_ids", "cur_pos")

    def __init__(self, message, from_node, arrivals, send_time, random_seed):
        super().__init__(send_time)
        self.message = message
        self._from_id = from_node.node_id
        self.random_seed = random_seed
        self.dest_ids = [a[0].node_id for a in arrivals]
        self.cur_pos = 0

    def _arrival_time(self, network: "Network", dest_id: int) -> int:
        delta = get_pseudo_random(dest_id, self.random_seed)
        f = network.get_node_by_id(self._from_id)
        t = network.get_node_by_id(dest_id)
        return self.send_time + network.transit_ms(self.message, f, t, delta)

    def get_message(self):
        return self.message

    def next_dest_id(self):
        return self.dest_ids[self.cur_pos]

    def next_arrival_time(self, network):
        return self._arrival_time(network, self.next_dest_id())

    def mark_read(self):
        self.cur_pos += 1

    def has_next_reader(self):
        return self.cur_pos < len(self.dest_ids)

    def from_id(self):
        return self._from_id

    def infos(self, network):
        return [
            EnvelopeInfo(
                self._from_id,
                d,
                self.send_time,
                self._arrival_time(network, d),
                self.message,
            )
            for d in self.dest_ids[self.cur_pos :]
        ]


class MultipleDestWithDelayEnvelope(_Envelope):
    __slots__ = ("message", "_from_id", "dest_ids", "arrival_times", "cur_pos")

    def __init__(self, message, from_node, arrivals, send_time):
        super().__init__(send_time)
        self.message = message
        self._from_id = from_node.node_id
        self.dest_ids = [a[0].node_id for a in arrivals]
        self.arrival_times = [a[1] for a in arrivals]
        self.cur_pos = 0

    def get_message(self):
        return self.message

    def next_dest_id(self):
        return self.dest_ids[self.cur_pos]

    def next_arrival_time(self, network):
        return self.arrival_times[self.cur_pos]

    def mark_read(self):
        self.cur_pos += 1

    def has_next_reader(self):
        return self.cur_pos < len(self.dest_ids)

    def from_id(self):
        return self._from_id

    def infos(self, network):
        return [
            EnvelopeInfo(self._from_id, d, self.send_time, a, self.message)
            for d, a in zip(self.dest_ids[self.cur_pos :], self.arrival_times[self.cur_pos :])
        ]


# ---------------------------------------------------------------------------
# Message storage: per-ms buckets, LIFO within a bucket
# ---------------------------------------------------------------------------


class MessageStorage:
    """Per-millisecond buckets with LIFO order inside a bucket — semantically
    identical to the reference's rolling slot array (Network.java:116-299);
    the slot machinery there is a Java-heap optimization we don't need."""

    def __init__(self, network: "Network"):
        self._network = network
        self._buckets: Dict[int, List[_Envelope]] = {}

    def add_msg(self, m: _Envelope) -> None:
        na = m.next_arrival_time(self._network)
        if na < self._network.time:
            raise RuntimeError(
                f"Can't add a message arriving in the past! time={self._network.time}, arriving at {na}"
            )
        self._buckets.setdefault(na, []).append(m)

    def peek(self, time: int) -> Optional[_Envelope]:
        lst = self._buckets.get(time)
        return lst[-1] if lst else None

    def poll(self, time: int) -> Optional[_Envelope]:
        lst = self._buckets.get(time)
        if lst:
            m = lst.pop()
            if not lst:
                del self._buckets[time]
            return m
        return None

    def size(self) -> int:
        return sum(len(v) for v in self._buckets.values())

    def size_at(self, time: int) -> int:
        return len(self._buckets.get(time, ()))

    def clear(self) -> None:
        self._buckets.clear()

    def peek_first(self) -> Optional[_Envelope]:
        if not self._buckets:
            return None
        t = min(self._buckets)
        return self._buckets[t][-1]

    def poll_first(self) -> Optional[_Envelope]:
        m = self.peek_first()
        if m is None:
            return None
        return self.poll(m.next_arrival_time(self._network))

    def peek_first_message_content(self) -> Optional[Message]:
        m = self.peek_first()
        return None if m is None else m.get_message()

    def peek_messages(self) -> List[EnvelopeInfo]:
        res: List[EnvelopeInfo] = []
        for t in sorted(self._buckets):
            for m in reversed(self._buckets[t]):  # head-of-chain first
                res.extend(m.infos(self._network))
        res.sort(key=EnvelopeInfo.sort_key)
        return res


# ---------------------------------------------------------------------------
# The Network
# ---------------------------------------------------------------------------


class Network(Generic[TN]):
    def __init__(self):
        self.msgs = MessageStorage(self)
        self.conditional_tasks: List[ConditionalTask] = []
        self.all_nodes: List[TN] = []
        self.rd = JavaRandom(0)
        self.partitions_in_x: List[int] = []
        self.msg_discard_time = 2**31 - 1
        self.network_latency: NetworkLatency = IC3NetworkLatency()
        self.network_throughput = None  # optional Mathis model (opt-in)
        self.time = 0
        # observability (telemetry parity with the batched engine's
        # SimState.dropped / occupancy()): sends filtered at send time —
        # down endpoint, cross-partition, discard-time (the reference
        # drops these silently at Network.java:476-487)
        self.dropped = 0

    # -- helpers -----------------------------------------------------------
    @staticmethod
    def choose_bad_nodes(rd: JavaRandom, node_count: int, nodes_down: int) -> set:
        """Random bad-node set; node 1 always kept up (Network.java:52-64)."""
        bad = set()
        while len(bad) < nodes_down:
            down = rd.next_int(node_count)
            if down != 1 and down not in bad:
                bad.add(down)
        return bad

    def get_node_by_id(self, nid: int) -> TN:
        return self.all_nodes[nid]

    def get_first_live_node(self) -> Optional[TN]:
        for n in self.all_nodes:
            if not n.is_down():
                return n
        return None

    def get_dead_nodes(self) -> set:
        return {n.node_id for n in self.all_nodes if n.is_down()}

    def live_nodes(self) -> List[TN]:
        return [n for n in self.all_nodes if not n.is_down()]

    def set_msg_discard_time(self, t: int) -> "Network[TN]":
        self.msg_discard_time = t
        return self

    def has_message(self) -> bool:
        return self.msgs.size() != 0

    def occupancy(self) -> dict:
        """Store census, shape-compatible with the batched engine's
        occupancy() (wserver surfaces both through the same endpoints)."""
        return {
            "pending_msgs": self.msgs.size(),
            "pending_buckets": len(self.msgs._buckets),
            "conditional_tasks": len(self.conditional_tasks),
        }

    # -- time --------------------------------------------------------------
    def run(self, seconds: int) -> bool:
        return self.run_ms(seconds * 1000)

    def run_ms(self, ms: int) -> bool:
        if ms <= 0:
            raise ValueError(f"Should be greater than 0. ms={ms}")
        if self.time == 0:
            for n in self.all_nodes:
                if not n.is_down():
                    n.start()
        end_at = self.time + ms
        did_something = self._receive_until(end_at)
        self.time = end_at
        return did_something

    # -- send paths --------------------------------------------------------
    def send_all(self, m: Message, from_node: TN, send_time: Optional[int] = None) -> None:
        if send_time is None:
            send_time = self.time + 1
        self.send(m, send_time, from_node, self.all_nodes)

    def send(self, m: Message, a, b, c=None, delays_between_message: int = 0) -> None:
        """Overload resolution mirroring the Java API:
        send(m, fromNode, toNode) / send(m, fromNode, dests) /
        send(m, sendTime, fromNode, toNode) / send(m, sendTime, fromNode, dests[, delay])."""
        if isinstance(a, int):
            send_time, from_node, dest = a, b, c
        else:
            send_time, from_node, dest = self.time + 1, a, b
            if isinstance(dest, list):
                if not dest:
                    return
                if len(dest) == 1:
                    dest = dest[0]
        if isinstance(dest, list):
            self._send_multi(m, send_time, from_node, dest, delays_between_message)
        else:
            self._send_single(m, send_time, from_node, dest)

    def _check_in_network(self, n: Node) -> None:
        if n.node_id >= len(self.all_nodes) or self.all_nodes[n.node_id] is not n:
            raise ValueError(f"The node is not in the network: {n}")

    def _send_single(self, mc: Message, send_time: int, from_node: TN, to_node: TN) -> None:
        self._check_in_network(from_node)
        self._check_in_network(to_node)
        ms = self._create_message_arrival(mc, from_node, to_node, send_time, self.rd.next_int())
        if ms is not None:
            self.msgs.add_msg(
                SingleDestEnvelope(mc, from_node, to_node, send_time, ms[1])
            )

    def _send_multi(
        self, m: Message, send_time: int, from_node: TN, dests: List[TN], delays: int
    ) -> None:
        self._check_in_network(from_node)
        random_seed = self.rd.next_int()
        da = self._create_message_arrivals(m, send_time, from_node, dests, random_seed, delays)
        if not da:
            return
        if len(da) == 1:
            dest, arrival = da[0]
            env: _Envelope = SingleDestEnvelope(m, from_node, dest, send_time, arrival)
        elif delays == 0:
            env = MultipleDestEnvelope(m, from_node, da, send_time, random_seed)
        else:
            env = MultipleDestWithDelayEnvelope(m, from_node, da, send_time)
        self.msgs.add_msg(env)

    def send_arrive_at(self, mc: Message, arrive_at: int, from_node: TN, to_node: TN) -> None:
        if arrive_at <= self.time:
            raise ValueError(f"wrong arrival time: arriveAt={arrive_at}, time={self.time}")
        self.msgs.add_msg(SingleDestEnvelope(mc, from_node, to_node, self.time, arrive_at))

    def _create_message_arrivals(
        self, m, send_time, from_node, dests, random_seed, delays
    ) -> List[tuple]:
        da = []
        for n in dests:
            ma = self._create_message_arrival(m, from_node, n, send_time, random_seed)
            send_time += delays + (1 if delays > 0 else 0)
            if ma is not None:
                da.append(ma)
        da.sort(key=lambda x: x[1])  # stable, by arrival only (Java parity)
        return da

    def _create_message_arrival(
        self, m, from_node: Node, to_node: Node, send_time: int, random_seed: int
    ) -> Optional[tuple]:
        if send_time <= self.time:
            raise RuntimeError(f"{m}, sendTime={send_time}, time={self.time}")
        assert not isinstance(m, Task)
        from_node.msg_sent += 1
        from_node.bytes_sent += m.size()
        if (
            self.partition_id(from_node) == self.partition_id(to_node)
            and not from_node.is_down()
            and not to_node.is_down()
        ):
            nt = self.transit_ms(
                m, from_node, to_node, get_pseudo_random(to_node.node_id, random_seed)
            )
            if nt < self.msg_discard_time:
                return (to_node, send_time + nt)
        self.dropped += 1
        return None

    # -- tasks -------------------------------------------------------------
    def register_task(self, task: Callable[[], None], start_at: int, from_node: TN) -> None:
        sw = Task(task)
        self.msgs.add_msg(SingleDestEnvelope(sw, from_node, from_node, self.time, start_at))

    def register_periodic_task(
        self, task, start_at: int, period: int, from_node: TN, condition=None
    ) -> None:
        sw = PeriodicTask(task, from_node, period, condition)
        self.msgs.add_msg(SingleDestEnvelope(sw, from_node, from_node, self.time, start_at))

    def register_conditional_task(
        self, task, start_at: int, duration: int, from_node: TN, start_if, repeat_if
    ) -> None:
        self.conditional_tasks.append(
            ConditionalTask(start_if, repeat_if, task, start_at, from_node, duration)
        )

    # -- event loop --------------------------------------------------------
    def _next_message(self, until: int) -> Optional[_Envelope]:
        cts: Optional[List[ConditionalTask]] = None
        while self.time <= until:
            m = self.msgs.poll(self.time)
            if m is not None:
                return m
            self.time += 1
            if cts is None:
                cts = list(self.conditional_tasks)
            i = 0
            while i < len(cts):
                ct = cts[i]
                if ct.min_start_time > until or ct.from_node.is_down():
                    cts.pop(i)
                    continue
                if ct.min_start_time <= self.time:
                    cts.pop(i)
                    if ct.start_if():
                        ct.r()
                        ct.min_start_time = self.time + ct.duration
                        if not ct.repeat_if():
                            try:
                                self.conditional_tasks.remove(ct)
                            except ValueError:
                                pass
                    continue
                i += 1
        return None

    def _receive_until(self, until: int) -> bool:
        previous_time = self.time
        next_env = self._next_message(until)
        if next_env is None:
            return False
        while next_env is not None:
            m = next_env
            na = m.next_arrival_time(self)
            if na != previous_time and self.time > na:
                raise RuntimeError(f"time:{self.time}, arrival={na}, m:{m}")

            from_node = self.all_nodes[m.from_id()]
            to_node = self.all_nodes[m.next_dest_id()]

            if not to_node.is_down() and self.partition_id(from_node) == self.partition_id(
                to_node
            ):
                msg = m.get_message()
                if not isinstance(msg, Task):
                    if msg.size() == 0:
                        raise RuntimeError(f"Message size should be greater than zero: {m}")
                    to_node.msg_received += 1
                    to_node.bytes_received += msg.size()
                if to_node.external is not None:
                    ei = m.cur_infos(self)
                    sms: List[SendMessage] = to_node.external.receive(ei)
                    for sm in sms:
                        dest = [self.get_node_by_id(i) for i in sm.to]
                        self.send(
                            sm.message,
                            sm.send_time,
                            self.get_node_by_id(sm.from_id),
                            dest,
                            sm.delay_between_send,
                        )
                else:
                    msg.action(self, from_node, to_node)

            m.mark_read()
            if m.has_next_reader():
                self.msgs.add_msg(m)
            previous_time = self.time
            next_env = self._next_message(until)
        return True

    # -- partitions --------------------------------------------------------
    def partition_id(self, node: Node) -> int:
        pid = 0
        for x in self.partitions_in_x:
            if x > node.x:
                return pid
            pid += 1
        return pid

    def partition(self, part: float) -> None:
        if part <= 0 or part >= 1:
            raise ValueError("part needs to be a percentage between 0 & 100 excluded")
        x_point = int(MAX_X * part)
        if x_point in self.partitions_in_x:
            raise ValueError("this partition exists already")
        self.partitions_in_x.append(x_point)
        self.partitions_in_x.sort()

    def end_partition(self) -> None:
        self.partitions_in_x.clear()

    # -- population --------------------------------------------------------
    def add_node(self, node: TN) -> None:
        while len(self.all_nodes) <= node.node_id:
            self.all_nodes.append(None)  # type: ignore[arg-type]
        if self.all_nodes[node.node_id] is not None:
            raise RuntimeError(f"There is already a node with this id ({node.node_id})")
        self.all_nodes[node.node_id] = node

    def set_network_latency(self, nl) -> "Network[TN]":
        if self.msgs.size() != 0:
            raise RuntimeError(
                "You can't change the latency while the system as on going messages"
            )
        if isinstance(nl, tuple):
            from ..core.latency import MeasuredNetworkLatency

            nl = MeasuredNetworkLatency(nl[0], nl[1])
        self.network_latency = nl
        return self

    def set_network_throughput(self, tp) -> "Network[TN]":
        """Enable TCP-throughput-aware delays (MathisNetworkThroughput):
        message transit becomes size-dependent.  The reference defines the
        model (NetworkThroughput.java:17-57) but never wires it into its
        Network; making it enableable is this rebuild's upgrade."""
        if self.msgs.size() != 0:
            raise RuntimeError(
                "You can't change the throughput while the system as on going messages"
            )
        self.network_throughput = tp
        return self

    def transit_ms(self, m, from_node, to_node, delta: int) -> int:
        """One-way transit time: latency, or the Mathis size-dependent
        delay when a throughput model is set."""
        if self.network_throughput is not None:
            return self.network_throughput.delay(
                from_node, to_node, delta, m.size(), nl=self.network_latency
            )
        return self.network_latency.get_latency(from_node, to_node, delta)


class Protocol:
    """Contract per core Protocol.java: network(), copy(), init(); plus the
    registry convention of a constructor taking one parameters object."""

    def network(self) -> Network:
        raise NotImplementedError

    def copy(self) -> "Protocol":
        raise NotImplementedError

    def init(self) -> None:
        raise NotImplementedError
