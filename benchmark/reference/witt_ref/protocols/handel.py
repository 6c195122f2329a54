"""Handel: practical multi-signature aggregation for large Byzantine
committees (arXiv:1906.05132) — the reference's flagship protocol.

Reference semantics: protocols/Handel.java.  Per-node binary levels with
reception-rank matrices (:940-948), emission lists built from ranks
(:991-1013), a periodic dissemination drumbeat (:331-343), verification as
a conditional task costing pairingTime per check with windowed scoring
(:566-630, window adaptation :150-210), fastPath bursts on level
completion (:738-742), and two attacks: byzantineSuicide (forged sigs →
blacklist, :538-559/:687-694) and hiddenByzantine (flooding the last level
with nearly-useless valid sigs, :840-917).

Bitsets are Python ints.  SigToVerify instances use identity equality,
matching Java's default equals in list remove/contains.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

from ..core.node import Node
from ..core.params import WParameters, register_protocol
from ..core.registries import registry_network_latencies, registry_node_builders
from ..oracle.messages import Message
from ..oracle.network import Network, Protocol
from ..utils.bitset import cardinality as _card, include as _include, to_ids as _bits_to_ids
from ..utils.more_math import round_pow2

INT_MAX = 2**31 - 1


@dataclasses.dataclass
class HandelParameters(WParameters):
    node_count: int = 32768 // 1024
    threshold: float = -1
    pairing_time: int = 3
    level_wait_time: int = 50
    extra_cycle: int = 10
    dissemination_period_ms: int = 10
    fast_path: int = 10
    nodes_down: int = 0
    node_builder_name: Optional[str] = None
    network_latency_name: Optional[str] = None
    desynchronized_start: int = 0
    byzantine_suicide: bool = False
    hidden_byzantine: bool = False
    bad_nodes: Optional[int] = None  # bitset of forced-down nodes
    window_initial: int = 16
    window_minimum: int = 1
    window_maximum: int = 128
    window_increase_factor: float = 2.0
    window_decrease_factor: float = 4.0
    # batched-engine knob (no oracle effect): in-flight channel slots per
    # (receiver, level); None = the engine default.  Trades HBM for lower
    # message displacement — see BatchedHandel.CHANNEL_DEPTH
    channel_depth: Optional[int] = None
    # batched-engine knob (no oracle effect): verification-candidate slots
    # per (receiver, level); None = the engine default.  Sized from the
    # measured occupancy high-water mark by scripts/density_autotune.py —
    # bit-identical while occupancy stays under the slot count (the K
    # buffer is re-sorted every tick, so a top-K' of an under-occupied
    # top-K retains the same entries).  See BatchedHandel.CAND_SLOTS
    cand_slots: Optional[int] = None

    def __post_init__(self):
        from ._aggregation import normalize_agg_params

        normalize_agg_params(self)
        if self.node_count.bit_count() != 1:
            raise ValueError("We support only power of two nodes in this simulation")
        if self.byzantine_suicide and self.hidden_byzantine:
            raise ValueError("Only one attack at a time")

    # -- window adaptation (WindowParameters + ScoringExp, :150-210) --------
    def window_new_size(self, current: int, correct: bool) -> int:
        import math

        if correct:
            updated = math.ceil(current * self.window_increase_factor)
        else:
            updated = math.floor(current / self.window_decrease_factor)
        return max(self.window_minimum, min(self.window_maximum, updated))


class SigToVerify:
    """Identity-equality value (Handel.java:920-941)."""

    __slots__ = ("from_id", "level", "rank", "sig", "bad_sig")

    def __init__(self, from_id: int, level: int, rank: int, sig: int, bad_sig: bool):
        self.from_id = from_id
        self.level = level
        self.rank = rank
        self.sig = sig
        self.bad_sig = bad_sig


class SendSigs(Message):
    """Handel.SendSigs (:239-276)."""

    def __init__(self, sigs: int, level: "HLevel"):
        self.sigs = sigs
        self.level = level.level
        # Size = level + bit field + the signatures included + our own sig
        self._size = 1 + level.expected_sigs() // 8 + 96 * 2
        self.level_finished = level.incoming_complete()
        self.bad_sig = False
        if sigs == 0 or _card(sigs) > level.size:
            raise RuntimeError(f"bad level: {level.level}")

    def size(self) -> int:
        return self._size

    def action(self, network, from_node, to_node) -> None:
        to_node.on_new_sig(from_node, self)


class HLevel:
    """One Handel level (Handel.java:363-651)."""

    def __init__(self, node: "HNode", previous: Optional["HLevel"] = None, all_previous: int = 0):
        self._node = node
        self.finished_peers = 0
        self.outgoing_finished = False
        self.pos_in_level = 0
        self.last_agg_verified = 0
        self.total_incoming = 0
        self.verified_ind_signatures = 0
        self.to_verify_agg: List[SigToVerify] = []
        self.to_verify_ind = 0
        self.suicide_biz_after = 0 if node.params.byzantine_suicide else -1
        if previous is None:
            self.level = 0
            self.size = 1
            self.outgoing_finished = True
            self.waited_sigs = 0
            self.last_agg_verified = 1 << node.node_id
            self.verified_ind_signatures = 1 << node.node_id
            self.total_incoming = 1 << node.node_id
            self.total_outgoing = 0
            self.peers: List["HNode"] = []
        else:
            self.level = previous.level + 1
            self.waited_sigs = node.all_sigs_at_level(self.level) & ~all_previous
            self.total_outgoing = 1 << node.node_id
            self.size = _card(self.waited_sigs)
            self.peers = []

    def expected_sigs(self) -> int:
        return self.size

    def expected_nodes(self) -> List["HNode"]:
        net = self._node.network_ref
        return [net.get_node_by_id(i) for i in _bits_to_ids(self.waited_sigs)]

    def is_open(self) -> bool:
        """Level opens on timeout or once outgoing is complete (:452-467)."""
        if self.outgoing_finished:
            return False
        if self._node.network_ref.time >= (self.level - 1) * self._node.params.level_wait_time:
            return True
        if self.outgoing_complete():
            return True
        return False

    def do_cycle(self) -> None:
        if not self.is_open():
            return
        dest = self.get_remaining_peers(1)
        if dest:
            ss = SendSigs(self.total_outgoing, self)
            self._node.network_ref.send(ss, self._node, dest[0])

    def get_remaining_peers(self, peers_ct: int) -> List["HNode"]:
        res: List["HNode"] = []
        start = self.pos_in_level
        while peers_ct > 0 and not self.outgoing_finished:
            p = self.peers[self.pos_in_level]
            self.pos_in_level += 1
            if self.pos_in_level >= len(self.peers):
                self.pos_in_level = 0
            if (
                not (self.finished_peers >> p.node_id) & 1
                and not (self._node.blacklist >> p.node_id) & 1
            ):
                res.append(p)
                peers_ct -= 1
            else:
                if self.pos_in_level == start:
                    self.outgoing_finished = True
        return res

    def build_emission_list(self, emissions: List[Optional[List["HNode"]]]) -> None:
        """Emission order: peers that gave us a good reception rank first,
        ties shuffled (:505-517)."""
        if self.peers:
            raise RuntimeError()
        for ranks in emissions:
            if ranks:
                if len(ranks) > 1:
                    self._node.network_ref.rd.shuffle(ranks)
                self.peers.extend(ranks)

    def incoming_complete(self) -> bool:
        return self.waited_sigs == self.total_incoming

    def outgoing_complete(self) -> bool:
        return _card(self.total_outgoing) == self.size

    def size_if_included(self, sig: SigToVerify) -> int:
        c = sig.sig
        if not (c & self.total_incoming):
            c = c | self.total_incoming
        c |= self.verified_ind_signatures
        return _card(c)

    def create_suicide_byzantine_sig(self, max_rank: int) -> Optional[SigToVerify]:
        """Forged-signature attack feeder (:538-559)."""
        node = self._node
        reset = False
        for i in range(self.suicide_biz_after, len(self.peers)):
            p = self.peers[i]
            if p.is_down() and not (node.blacklist >> p.node_id) & 1:
                if not reset:
                    self.suicide_biz_after = i
                    reset = True
                if node.reception_ranks[p.node_id] < max_rank:
                    return SigToVerify(
                        p.node_id,
                        self.level,
                        node.reception_ranks[p.node_id],
                        self.waited_sigs,
                        True,
                    )
        if not reset:
            self.suicide_biz_after = -1
        return None

    def best_to_verify(self) -> Optional[SigToVerify]:
        """Windowed scoring: rank-based outside the window, score-based
        inside (:566-630)."""
        node = self._node
        if not self.to_verify_agg:
            return None
        if node.curr_window_size < 1:
            raise RuntimeError()

        window_index = min(s.rank for s in self.to_verify_agg)

        if self.suicide_biz_after >= 0:
            b_sig = self.create_suicide_byzantine_sig(window_index + node.curr_window_size)
            if b_sig is not None:
                self.to_verify_agg.append(b_sig)
                node.sig_queue_size += 1
                return b_sig

        cur_signature_size = _card(self.total_incoming)
        best_outside: Optional[SigToVerify] = None
        best_inside: Optional[SigToVerify] = None
        best_score_inside = 0

        removed = 0
        curated: List[SigToVerify] = []
        for stv in self.to_verify_agg:
            s = self.size_if_included(stv)
            if not (node.blacklist >> stv.from_id) & 1 and s > cur_signature_size:
                curated.append(stv)
                if stv.rank <= window_index + node.curr_window_size:
                    score = node.score(self, stv.sig)
                    if score > best_score_inside:
                        best_score_inside = score
                        best_inside = stv
                else:
                    if best_outside is None or stv.rank < best_outside.rank:
                        best_outside = stv
            else:
                removed += 1

        if removed > 0:
            node.sig_queue_size -= len(self.to_verify_agg)
            self.to_verify_agg[:] = curated
            node.sig_queue_size += len(curated)
            if node.sig_queue_size < 0:
                raise RuntimeError(f"sigQueueSize={node.sig_queue_size}")

        if best_inside is not None:
            return best_inside
        return best_outside


class HNode(Node):
    __slots__ = (
        "network_ref",
        "params",
        "start_at",
        "levels",
        "node_pairing_time",
        "reception_ranks",
        "blacklist",
        "curr_window_size",
        "added_cycle",
        "hidden_byzantine",
        "done",
        "sigs_checked",
        "sig_queue_size",
        "msg_filtered",
    )

    def __init__(self, network: Network, start_at: int, nb, byzantine: bool, params: HandelParameters):
        super().__init__(network.rd, nb, byzantine)
        self.network_ref = network
        self.params = params
        self.start_at = start_at
        self.levels: List[HLevel] = []
        self.node_pairing_time = int(max(1, params.pairing_time * self.speed_ratio))
        self.reception_ranks = [0] * params.node_count
        self.blacklist = 0
        self.curr_window_size = params.window_initial
        self.added_cycle = params.extra_cycle
        self.hidden_byzantine = (
            HiddenByzantine() if params.hidden_byzantine and not byzantine else None
        )
        self.done = False
        self.sigs_checked = 0
        self.sig_queue_size = 0
        self.msg_filtered = 0

    def __repr__(self) -> str:
        return f"HNode{{{self.node_id}}}"

    def init_level(self) -> None:
        rounded = round_pow2(self.params.node_count)
        all_previous = 0
        last = HLevel(self)
        self.levels.append(last)
        l = 1
        while 2**l <= rounded:
            all_previous |= last.waited_sigs
            last = HLevel(self, last, all_previous)
            self.levels.append(last)
            l += 1

    def dissemination(self) -> None:
        if self.done_at > 0:
            if self.added_cycle > 0:
                self.added_cycle -= 1
            else:
                return
        for sfl in self.levels:
            sfl.do_cycle()

    def has_sig_to_verify(self) -> bool:
        return self.sig_queue_size != 0

    def total_sig_size(self) -> int:
        last = self.levels[-1]
        return _card(last.total_outgoing) + _card(last.total_incoming)

    def level_of(self, dest: "HNode") -> int:
        for i in range(len(self.levels) - 1, -1, -1):
            if (self.levels[i].waited_sigs >> dest.node_id) & 1:
                return i
        raise RuntimeError()

    def score(self, l: HLevel, sig: int) -> int:
        """Added-signature count if verified (:585-600)."""
        if _card(l.last_agg_verified) >= l.expected_sigs():
            return 0
        if not (l.last_agg_verified & sig):
            return _card(l.last_agg_verified) + _card(sig)
        with_indiv = l.verified_ind_signatures | sig
        return max(0, _card(with_indiv) - _card(l.last_agg_verified))

    def all_sigs_at_level(self, round_: int) -> int:
        """Binary-tree membership trick (Handel.java:634-647)."""
        from ._aggregation import all_sigs_at_level

        return all_sigs_at_level(self.node_id, round_, self.params.node_count)

    def update_verified_signatures(self, vs: SigToVerify) -> None:
        """Verification completion (:686-750)."""
        if vs.bad_sig:
            self.blacklist |= 1 << vs.from_id
            if not self.params.byzantine_suicide:
                raise RuntimeError("We should not have invalid signatures in this scenario")
            return

        vsl = self.levels[vs.level]
        if not _include(vsl.waited_sigs, vs.sig):
            raise RuntimeError("bad signature received")

        vsl.to_verify_ind &= ~(1 << vs.from_id)
        try:
            vsl.to_verify_agg.remove(vs)
        except ValueError:
            pass

        vsl.verified_ind_signatures |= 1 << vs.from_id

        improved = False
        if not (vsl.total_incoming >> vs.from_id) & 1:
            vsl.total_incoming |= 1 << vs.from_id
            improved = True

        all_ = vs.sig | vsl.verified_ind_signatures
        if _card(all_) > _card(vsl.verified_ind_signatures):
            improved = True
            if vsl.last_agg_verified & vs.sig:
                vsl.last_agg_verified = 0
            vsl.last_agg_verified |= vs.sig
            vsl.total_incoming = vsl.last_agg_verified | vsl.verified_ind_signatures

        if not improved:
            return

        just_completed = vsl.incoming_complete()

        cur = 0
        for l in self.levels:
            if l.level > vsl.level:
                l.total_outgoing = cur
                if (
                    just_completed
                    and self.params.fast_path > 0
                    and not l.outgoing_finished
                    and l.outgoing_complete()
                ):
                    peers = l.get_remaining_peers(self.params.fast_path)
                    send_sigs = SendSigs(l.total_outgoing, l)
                    self.network_ref.send(send_sigs, self, peers)
            cur |= l.total_incoming

        if self.done_at == 0 and _card(cur) >= self.params.threshold:
            self.done_at = self.network_ref.time

    def on_new_sig(self, from_node: "HNode", ssigs: SendSigs) -> None:
        """(:752-786)"""
        if self.done_at > 0:
            self.msg_filtered += 1
            return
        if self.network_ref.time < self.start_at or (self.blacklist >> from_node.node_id) & 1:
            return

        l = self.levels[ssigs.level]
        if not _include(l.waited_sigs, ssigs.sigs):
            raise RuntimeError("bad signatures received")
        cs = ssigs.sigs & l.waited_sigs
        if cs != ssigs.sigs or ssigs.sigs == 0:
            raise RuntimeError("bad message")

        if ssigs.level_finished:
            l.finished_peers |= 1 << from_node.node_id
        if not (l.verified_ind_signatures >> from_node.node_id) & 1:
            l.to_verify_ind |= 1 << from_node.node_id

        self.sig_queue_size += 1
        l.to_verify_agg.append(
            SigToVerify(
                from_node.node_id,
                l.level,
                self.reception_ranks[from_node.node_id],
                cs,
                ssigs.bad_sig,
            )
        )

    def check_sigs(self) -> None:
        """(:792-837)"""
        by_levels: List[SigToVerify] = []
        for l in self.levels:
            ss = l.best_to_verify()
            if ss is not None:
                by_levels.append(ss)
        if not by_levels:
            return

        best = by_levels[self.network_ref.rd.next_int(len(by_levels))]

        if self.hidden_byzantine is not None and best.level == len(self.levels) - 1:
            best = self.hidden_byzantine.attack(self, best)

        l = self.levels[best.level]
        new_size = self.params.window_new_size(self.curr_window_size, not best.bad_sig)
        self.curr_window_size = min(new_size, l.size)

        # push to the end of the ranking, with Java int overflow clamp
        self.reception_ranks[best.from_id] += self.params.node_count
        if self.reception_ranks[best.from_id] > INT_MAX:
            self.reception_ranks[best.from_id] = INT_MAX

        self.sigs_checked += 1
        f_best = best
        self.network_ref.register_task(
            lambda: self.update_verified_signatures(f_best),
            self.network_ref.time + self.node_pairing_time,
            self,
        )


class HiddenByzantine:
    """Flood the last level with valid but nearly-useless signatures
    (:840-917)."""

    def __init__(self):
        self.no_byzantine_peers = False
        self.last: Optional[SigToVerify] = None

    def first_byzantine(self, t: HNode, l: HLevel) -> Optional[HNode]:
        best = None
        best_rank = INT_MAX
        for p in l.peers:
            if (
                p.is_down()
                and t.reception_ranks[p.node_id] < best_rank
                and not (l.total_incoming >> p.node_id) & 1
            ):
                best_rank = t.reception_ranks[p.node_id]
                best = p
                if best_rank == 0:
                    return p
        return best

    def attack(self, target: HNode, current_best: SigToVerify) -> SigToVerify:
        if self.no_byzantine_peers:
            return current_best
        if self.last is current_best:
            self.last = None
            return current_best

        l = target.levels[current_best.level]
        if self.last is not None:
            if any(s is self.last for s in l.to_verify_agg):
                return current_best
            if not (l.total_incoming >> self.last.from_id) & 1:
                raise RuntimeError("byz signature pruned!")
            self.last = None

        first_byz = self.first_byzantine(target, l)
        if first_byz is None:
            self.no_byzantine_peers = True
            return current_best

        if target.reception_ranks[first_byz.node_id] >= current_best.rank:
            return current_best

        bad = SigToVerify(
            first_byz.node_id,
            l.level,
            target.reception_ranks[first_byz.node_id],
            1 << first_byz.node_id,
            False,
        )
        l.to_verify_agg.append(bad)
        target.sig_queue_size += 1

        new_best = l.best_to_verify()
        if new_best is not bad:
            self.last = bad
        return new_best


@register_protocol("Handel", HandelParameters)
class Handel(Protocol):
    def __init__(self, params: HandelParameters):
        self.params = params
        self._network: Network[HNode] = Network()
        self._network.set_network_latency(
            registry_network_latencies.get_by_name(params.network_latency_name)
        )

    def __str__(self) -> str:
        p = self.params
        return (
            f"Handel, nodes={p.node_count}, threshold={p.threshold}"
            f", pairing={p.pairing_time}ms, levelWaitTime={p.level_wait_time}ms"
            f", period={p.dissemination_period_ms}ms"
            f", acceleratedCallsCount={p.fast_path}, dead nodes={p.nodes_down}"
            f", builder={p.node_builder_name}"
        )

    def copy(self) -> "Handel":
        return Handel(self.params)

    def init(self) -> None:
        p = self.params
        nb = registry_node_builders.get_by_name(p.node_builder_name)

        if p.bad_nodes is not None:
            bad_nodes = p.bad_nodes
        else:
            bad = Network.choose_bad_nodes(self._network.rd, p.node_count, p.nodes_down)
            bad_nodes = 0
            for b in bad:
                bad_nodes |= 1 << b

        for i in range(p.node_count):
            start_at = (
                0
                if p.desynchronized_start == 0
                else self._network.rd.next_int(p.desynchronized_start)
            )
            byz = (p.byzantine_suicide or p.hidden_byzantine) and bool(
                (bad_nodes >> i) & 1
            )
            n = HNode(self._network, start_at, nb, byz, p)
            if (bad_nodes >> i) & 1:
                n.stop()
            self._network.add_node(n)

        for n in self._network.all_nodes:
            n.init_level()
            if not n.is_down():
                self._network.register_periodic_task(
                    n.dissemination, n.start_at + 1, p.dissemination_period_ms, n
                )
                self._network.register_conditional_task(
                    n.check_sigs,
                    n.start_at + 1,
                    n.node_pairing_time,
                    n,
                    n.has_sig_to_verify,
                    lambda n=n: not n.done,
                )

        self._set_receiving_ranks()

        # emission lists: contact first the peers that rank us well (:991-1013)
        for sender in self._network.all_nodes:
            if sender.is_down():
                continue
            for l in sender.levels:
                emission_list: List[Optional[List[HNode]]] = [None] * p.node_count
                for receiver in l.expected_nodes():
                    rec_rank = receiver.reception_ranks[sender.node_id]
                    if emission_list[rec_rank] is None:
                        emission_list[rec_rank] = []
                    emission_list[rec_rank].append(receiver)
                l.build_emission_list(emission_list)

    def _set_receiving_ranks(self) -> None:
        """One shared, repeatedly-shuffled list — exact RNG stream parity
        with setReceivingRanks (:940-948)."""
        expected = list(self._network.all_nodes)
        for n in self._network.all_nodes:
            self._network.rd.shuffle(expected)
            for i, e in enumerate(expected):
                n.reception_ranks[e.node_id] = i

    def network(self) -> Network:
        return self._network

    @staticmethod
    def new_cont_if():
        def cont(p: "Handel") -> bool:
            for n in p.network().live_nodes():
                if n.done_at == 0 or n.added_cycle > 0:
                    return True
            return False

        return cont
