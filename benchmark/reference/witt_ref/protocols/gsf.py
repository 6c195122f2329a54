"""GSFSignature: "Gossiping San Fermin" BLS signature aggregation.

Reference semantics: protocols/GSFSignature.java — per-node binary levels
(the allSigsAtLevel bitmask trick, :361-374), a periodic doCycle drumbeat
per level (:313-324), level timeouts level*timeoutPerLevelMs (:292),
accelerated calls on level completion (:438-451), signature scoring
evaluateSig (:478-520), and verification modeled as a conditional task
costing pairingTime per check (:630-631).

Bitsets are Python ints (or/and/andNot/cardinality are int ops).  One
Java-visible subtlety is preserved: a SendSigs object multicast to several
peers shares ONE sigs bitset, and updateVerifiedSignatures mutates it
(or-ing indivVerifiedSig / merging non-intersecting sets) before the point
where the Java code rebinds the local variable — so mutations must write
through to the message (`holder.sigs`) exactly until that rebind.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

from ..core.node import Node
from ..core.params import WParameters, register_protocol
from ..core.registries import registry_network_latencies, registry_node_builders
from ..oracle.messages import Message
from ..oracle.network import Network, Protocol
from ..utils.bitset import cardinality as _card, include as _include, to_ids as _bits_to_ids
from ..utils.more_math import round_pow2


@dataclasses.dataclass
class GSFSignatureParameters(WParameters):
    node_count: int = 32768 // 32
    threshold: float = -1  # int count, or a (0,1] ratio; -1 = 99% default
    pairing_time: int = 3
    timeout_per_level_ms: int = 50
    period_duration_ms: int = 10
    accelerated_calls_count: int = 10
    nodes_down: float = 0  # int count or a [0,1) ratio
    node_builder_name: Optional[str] = None
    network_latency_name: Optional[str] = None

    def __post_init__(self):
        from ._aggregation import normalize_agg_params

        normalize_agg_params(self)


class SendSigs(Message):
    """Signature-set message (GSFSignature.java:143-164); `sigs` is shared
    mutable state across all receivers of one multicast."""

    def __init__(self, from_node: "GSFNode", sigs: int, level: "SFLevel"):
        self.sigs = sigs
        self.from_node = from_node
        self.level = level.level
        # Size = level byte + bit field + the aggregated sig + our own sig
        self._size = 1 + level.expected_sigs() // 8 + 96
        self.level_finished = level.verified_signatures == level.waited_sigs
        self.received = _card(level.verified_signatures)

    def size(self) -> int:
        return self._size

    def action(self, network, from_node, to_node) -> None:
        to_node.on_new_sig(from_node, self)


class SFLevel:
    """One San Fermin level (GSFSignature.java:236-358)."""

    def __init__(self, node: "GSFNode", previous: Optional["SFLevel"] = None, all_previous: int = 0):
        self._node = node
        if previous is None:
            self.level = 0
            self.waited_sigs = 1 << node.node_id
            self.verified_signatures = 1 << node.node_id
            self.peers: List["GSFNode"] = []
            self.remaining_calls = 0
        else:
            self.level = previous.level + 1
            self.waited_sigs = node.all_sigs_at_level(self.level) & ~all_previous
            self.verified_signatures = 0
            self.peers = node.random_subset(self.waited_sigs, 2**31 - 1)
            self.remaining_calls = len(self.peers)
        self.individual_signatures = 0
        self.indiv_verified_sig = 0
        self.received: Dict["GSFNode", int] = {}
        self.pos_in_level = 0

    def expected_sigs(self) -> int:
        return _card(self.waited_sigs)

    def has_started(self, to_send: int) -> bool:
        """Level starts on timeout or once we hold all it needs
        (GSFSignature.java:289-309)."""
        net = self._node.network_ref
        if net.time >= self.level * self._node.params.timeout_per_level_ms:
            return True
        if _card(to_send) >= self.expected_sigs():
            return True
        return False

    def do_cycle(self, to_send: int) -> None:
        if self.remaining_calls == 0 or not self.has_started(to_send):
            return
        dest = self.get_remaining_peers(1)
        if dest:
            ss = SendSigs(self._node, to_send, self)
            self._node.network_ref.send(ss, self._node, dest[0])

    def get_remaining_peers(self, peers_ct: int) -> List["GSFNode"]:
        """Round-robin through the level's peer list; the reference's
        received-map filter is disabled by an `|| true` (GSFSignature.java:
        327-343), so every candidate is taken."""
        res: List["GSFNode"] = []
        while peers_ct > 0 and self.remaining_calls > 0:
            self.remaining_calls -= 1
            p = self.peers[self.pos_in_level]
            self.pos_in_level += 1
            if self.pos_in_level >= len(self.peers):
                self.pos_in_level = 0
            res.append(p)
            peers_ct -= 1
        return res

    def has_received_all(self) -> bool:
        wanted = self.waited_sigs & self.verified_signatures
        return _card(wanted) >= 0.8 * self.expected_sigs()


class GSFNode(Node):
    __slots__ = (
        "network_ref",
        "params",
        "to_verify",
        "levels",
        "verified_signatures",
        "node_pairing_time",
        "done",
        "sig_checked",
        "sig_queue_size",
    )

    def __init__(self, network: Network, nb, params: GSFSignatureParameters):
        super().__init__(network.rd, nb)
        self.network_ref = network
        self.params = params
        self.to_verify: List[SendSigs] = []
        self.levels: List[SFLevel] = []
        self.verified_signatures = 1 << self.node_id
        self.node_pairing_time = int(max(1, params.pairing_time * self.speed_ratio))
        self.done = False
        self.sig_checked = 0
        self.sig_queue_size = 0

    def init_level(self) -> None:
        rounded = round_pow2(self.params.node_count)
        all_previous = 0
        last = SFLevel(self)
        self.levels.append(last)
        l = 1
        while 2**l <= rounded:
            all_previous |= last.waited_sigs
            last = SFLevel(self, last, all_previous)
            self.levels.append(last)
            l += 1

    def get_last_finished_level(self) -> int:
        res = 0
        sfl = self.levels[0]
        while True:
            if sfl.waited_sigs == sfl.verified_signatures:
                res |= sfl.waited_sigs
                if sfl.level < len(self.levels) - 1:
                    sfl = self.levels[sfl.level + 1]
                else:
                    return res
            else:
                return res

    def do_cycle(self) -> None:
        to_send = self.get_last_finished_level()
        for sfl in self.levels:
            sfl.do_cycle(to_send)
            to_send |= sfl.verified_signatures

    def all_sigs_at_level(self, round_: int) -> int:
        """Binary-tree membership trick (GSFSignature.java:361-374)."""
        from ._aggregation import all_sigs_at_level

        return all_sigs_at_level(self.node_id, round_, self.params.node_count)

    def update_verified_signatures(self, from_node: "GSFNode", level: int, holder: SendSigs) -> None:
        """Merge a verified signature set (GSFSignature.java:379-460).
        Mutations write through holder.sigs until the Java code rebinds."""
        sfl = self.levels[level]

        if _card(holder.sigs) == 1:
            sfl.indiv_verified_sig |= 1 << from_node.node_id
        holder.sigs |= sfl.indiv_verified_sig
        sigs = holder.sigs
        rebound = False

        reset_remaining = False
        if _card(sigs) > sfl.expected_sigs():
            # sender included our lower levels too: absorb level by level
            i = 1
            while i < len(self.levels) and _include(sigs, self.levels[i].waited_sigs):
                lv = self.levels[i]
                if lv.verified_signatures != lv.waited_sigs:
                    lv.verified_signatures |= lv.waited_sigs
                    self.verified_signatures |= lv.waited_sigs
                    reset_remaining = True
                if reset_remaining:
                    lv.remaining_calls = len(lv.peers)
                i += 1
            sigs = sfl.waited_sigs
            rebound = True

        if _card(sfl.verified_signatures) > 0 and (sigs & sfl.verified_signatures) == 0:
            # disjoint sets aggregate
            sigs |= sfl.verified_signatures
            if not rebound:
                holder.sigs = sigs

        if _card(sigs) > _card(sfl.verified_signatures) or reset_remaining:
            for i in range(sfl.level, len(self.levels)):
                self.levels[i].remaining_calls = len(self.levels[i].peers)

            # replacement, not completion
            sfl.verified_signatures &= ~sfl.waited_sigs
            sfl.verified_signatures |= sigs
            self.verified_signatures &= ~sfl.waited_sigs
            self.verified_signatures |= sigs

            if self.params.accelerated_calls_count > 0:
                best_to_send = self.get_last_finished_level()
                while _include(best_to_send, sfl.waited_sigs) and sfl.level < len(self.levels) - 1:
                    sfl = self.levels[sfl.level + 1]
                    send_sigs = SendSigs(self, best_to_send, sfl)
                    peers = sfl.get_remaining_peers(self.params.accelerated_calls_count)
                    if peers:
                        self.network_ref.send(send_sigs, self, peers)
            if self.done_at == 0 and _card(self.verified_signatures) >= self.params.threshold:
                self.done_at = self.network_ref.time

    def random_subset(self, bits: int, node_ct: int) -> List["GSFNode"]:
        res = [self.network_ref.get_node_by_id(i) for i in _bits_to_ids(bits)]
        self.network_ref.rd.shuffle(res)
        return res[:node_ct] if len(res) > node_ct else res

    def evaluate_sig(self, l: SFLevel, sig: int) -> int:
        """Interest score of verifying `sig` (GSFSignature.java:478-520)."""
        if _card(l.verified_signatures) >= l.expected_sigs():
            return 0

        with_indiv = l.indiv_verified_sig | sig

        if _card(l.verified_signatures) == 0:
            new_total = _card(sig)
            added_sigs = new_total
        elif sig & l.verified_signatures:
            new_total = _card(with_indiv)
            added_sigs = new_total - _card(l.verified_signatures)
        else:
            with_indiv |= l.verified_signatures
            new_total = _card(with_indiv)
            added_sigs = new_total - _card(l.verified_signatures)

        if added_sigs <= 0:
            if _card(sig) == 1 and not (sig & l.indiv_verified_sig):
                return 1
            return 0

        if new_total == l.expected_sigs():
            return 1000000 - l.level * 10
        return 100000 - l.level * 100 + added_sigs

    def on_new_sig(self, from_node: "GSFNode", ssigs: SendSigs) -> None:
        l = self.levels[ssigs.level]
        if ssigs.level_finished:
            l.received[from_node] = 1
        self.to_verify.append(ssigs)
        # individual sig tracked for byzantine resistance
        if not (l.individual_signatures >> from_node.node_id) & 1:
            si = SendSigs(from_node, 1 << from_node.node_id, l)
            self.to_verify.append(si)
            l.individual_signatures |= 1 << from_node.node_id
        self.sig_queue_size = len(self.to_verify)

    def check_sigs(self) -> None:
        best = None
        score = 0
        kept = []
        for cur in self.to_verify:
            l = self.levels[cur.level]
            ns = self.evaluate_sig(l, cur.sigs)
            if ns > score:
                score = ns
                best = cur
                kept.append(cur)
            elif ns == 0:
                continue  # drop worthless entries (iterator remove)
            else:
                kept.append(cur)
        self.to_verify = kept
        if best is not None:
            self.to_verify.remove(best)
            self.sig_checked += 1
            self.sig_queue_size = len(self.to_verify)
            t_best = best
            self.network_ref.register_task(
                lambda: self.update_verified_signatures(
                    t_best.from_node, t_best.level, t_best
                ),
                self.network_ref.time + self.node_pairing_time,
                self,
            )

    def __repr__(self) -> str:
        return (
            f"GSFNode{{nodeId={self.node_id}, doneAt={self.done_at}"
            f", sigs={_card(self.verified_signatures)}, msgReceived={self.msg_received}"
            f", msgSent={self.msg_sent}, KBytesSent={self.bytes_sent // 1024}"
            f", KBytesReceived={self.bytes_received // 1024}}}"
        )


@register_protocol("GSFSignature", GSFSignatureParameters)
class GSFSignature(Protocol):
    def __init__(self, params: GSFSignatureParameters):
        self.params = params
        self.nb = registry_node_builders.get_by_name(params.node_builder_name)
        self._network: Network[GSFNode] = Network()
        self._network.set_network_latency(
            registry_network_latencies.get_by_name(params.network_latency_name)
        )

    def __str__(self) -> str:
        p = self.params
        return (
            f"GSFSignature, nodes={p.node_count}, threshold={p.threshold}"
            f", pairing={p.pairing_time}ms, level waitTime={p.timeout_per_level_ms}ms"
            f", period={p.period_duration_ms}ms"
            f", acceleratedCallsCount={p.accelerated_calls_count}"
            f", dead nodes={p.nodes_down}, builder={p.node_builder_name}"
        )

    def copy(self) -> "GSFSignature":
        return GSFSignature(self.params)

    def init(self) -> None:
        p = self.params
        for _ in range(p.node_count):
            self._network.add_node(GSFNode(self._network, self.nb, p))

        set_down = 0
        while set_down < p.nodes_down:
            down = self._network.rd.next_int(p.node_count)
            n = self._network.all_nodes[down]
            if not n.is_down() and down != 1:
                # node 1 kept up to help debugging (GSFSignature.java:621)
                n.stop()
                set_down += 1

        for n in self._network.all_nodes:
            if not n.is_down():
                n.init_level()
                self._network.register_periodic_task(
                    n.do_cycle, 1, p.period_duration_ms, n
                )
                self._network.register_conditional_task(
                    n.check_sigs,
                    1,
                    n.node_pairing_time,
                    n,
                    lambda n=n: len(n.to_verify) > 0,
                    lambda n=n: not n.done,
                )

    def network(self) -> Network:
        return self._network
