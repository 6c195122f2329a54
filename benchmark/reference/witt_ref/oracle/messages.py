"""Message hierarchy for the oracle DES.

Reference semantics: core messages/*.java.  Messages are immutable and may
be shared between many in-flight deliveries (multi-dest envelopes).
"""

from __future__ import annotations

from typing import Callable, List, Optional, TYPE_CHECKING

if TYPE_CHECKING:
    from .network import Network
    from .p2p import P2PNode


class Message:
    """action() is the protocol callback on delivery (Message.java:21);
    size() feeds the traffic counters (default 1)."""

    def action(self, network: "Network", from_node, to_node) -> None:
        raise NotImplementedError

    def size(self) -> int:
        return 1

    def __repr__(self) -> str:
        fields = ", ".join(
            f"{k}={v}" for k, v in vars(self).items() if not k.startswith("_")
        )
        return f"{type(self).__name__}{{{fields}}}"


class Task(Message):
    """A runnable wrapped as a self-addressed message; size 0 so it doesn't
    count as network traffic (messages/Task.java)."""

    def __init__(self, r: Callable[[], None]):
        assert r is not None
        self.r = r

    def size(self) -> int:
        return 0

    def action(self, network, from_node, to_node) -> None:
        self.r()


class PeriodicTask(Task):
    """Re-sends itself every `period` ms while the continuation condition
    holds (messages/PeriodicTask.java:40-47)."""

    def __init__(self, r, from_node, period: int, condition=None):
        super().__init__(r)
        self.period = period
        self.sender = from_node
        self.continuation_condition = condition if condition is not None else (lambda: True)

    def action(self, network, from_node, to_node) -> None:
        self.r()
        if self.continuation_condition():
            network.send_arrive_at(self, network.time + self.period, self.sender, self.sender)


class ConditionalTask(Task):
    """Polled by the engine on empty milliseconds (Network.nextMessage);
    fields per messages/ConditionalTask.java."""

    def __init__(self, start_if, repeat_if, r, min_start_time: int, from_node, duration: int):
        super().__init__(r)
        self.start_if = start_if
        self.repeat_if = repeat_if
        self.duration = duration
        self.min_start_time = min_start_time
        self.from_node = from_node


class FloodMessage(Message):
    """Gossip primitive: dedup per (node, msgId), then re-broadcast to the
    node's peers in shuffled order with local/per-peer delays
    (messages/FloodMessage.java:47-56)."""

    def __init__(self, size: int = 0, local_delay: int = 0, delay_between_peers: int = 0):
        self._size = size
        self.local_delay = local_delay
        self.delay_between_peers = delay_between_peers

    def msg_id(self) -> int:
        return -1

    def add_to_received(self, to: "P2PNode") -> bool:
        s = to.get_msg_received(self.msg_id())
        if self in s:
            return False
        s.add(self)
        return True

    def action(self, network, from_node, to_node) -> None:
        if self.add_to_received(to_node):
            to_node.on_flood(from_node, self)
            dest = [n for n in to_node.peers if n is not from_node]
            network.rd.shuffle(dest)
            network.send(
                self,
                network.time + 1 + self.local_delay,
                to_node,
                dest,
                self.delay_between_peers,
            )

    def size(self) -> int:
        return self._size

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other


class StatusFloodMessage(FloodMessage):
    """Versioned flood: only the highest seq per msgId is kept/propagated
    (messages/StatusFloodMessage.java:31-44)."""

    def __init__(self, msg_id: int, seq: int, size: int, local_delay: int, delay_between_peers: int):
        super().__init__(size, local_delay, delay_between_peers)
        if msg_id < 0:
            raise ValueError(f"id less than zero are reserved, msgId={msg_id}")
        self._msg_id = msg_id
        self.seq = seq

    def msg_id(self) -> int:
        return self._msg_id

    def add_to_received(self, to: "P2PNode") -> bool:
        s = to.get_msg_received(self._msg_id)
        previous = next(iter(s)) if s else None
        if previous is not None and previous.seq >= self.seq:
            return False
        s.clear()
        s.add(self)
        return True


class SendMessage:
    """Wire DTO for message injection via the API / External hook
    (messages/SendMessage.java)."""

    def __init__(
        self,
        from_id: int,
        to: List[int],
        send_time: int,
        delay_between_send: int,
        message: Optional[Message],
    ):
        self.from_id = from_id
        self.to = to
        self.send_time = send_time
        self.delay_between_send = delay_between_send
        self.message = message
