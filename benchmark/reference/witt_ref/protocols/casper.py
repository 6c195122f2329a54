"""Casper IMD — beacon chain stage 1 (no justification, no dynasty changes),
per the ethresear.ch mini-spec: one block producer per 8-second slot,
attester committees voting per slot, GHOST-like fork choice counting
attestations down to the first common ancestor.

Reference semantics: protocols/CasperIMD.java (Attestation :105-149,
CasperBlock :151-194, fork choice `best`/countAttestations :204-288,
slot-clock gate in onBlock :298-314, buildBlock merge :383-428, init task
schedule :472-508, Byzantine producers :511-707).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set

from ..core.params import WParameters, register_protocol
from ..core.registries import registry_network_latencies, registry_node_builders
from ..oracle.blockchain import Block, BlockChainNetwork, BlockChainNode, SendBlock
from ..oracle.messages import Message
from ..oracle.network import Protocol

SLOT_DURATION = 8000


@dataclasses.dataclass
class CasperParameters(WParameters):
    cycle_length: int = 4  # rounds per cycle; 64 in the spec
    random_on_ties: bool = True
    block_producers_count: int = 2
    attesters_per_round: Optional[int] = None  # upstream's 20 unless node_count states the validators
    block_construction_time: int = 1000
    attestation_construction_time: int = 1
    node_builder_name: Optional[str] = None
    network_latency_name: Optional[str] = None
    # the validator (attester) count, where the caller states it (the
    # sweep axis); None where the committee size was stated instead
    node_count: Optional[int] = None

    def __post_init__(self):
        """The one place the committee size is derived.  A stated
        `node_count` gives `attesters_per_round = node_count //
        cycle_length`; with neither stated the committee is upstream's 20.
        Both stated must agree: `dataclasses.replace` of one of the three
        numbers on parameters that state `node_count` is an error, not a
        change that is silently dropped (pass `node_count=None` with it)."""
        if self.node_count is None:
            if self.attesters_per_round is None:
                self.attesters_per_round = 20
            return
        if self.node_count <= 0 or self.node_count % self.cycle_length:
            raise ValueError(
                f"node_count {self.node_count} is not a positive multiple of "
                f"cycle_length {self.cycle_length}"
            )
        derived = self.node_count // self.cycle_length
        if self.attesters_per_round not in (None, derived):
            raise ValueError(
                f"node_count {self.node_count} over cycle_length {self.cycle_length} is "
                f"{derived} attesters a round, attesters_per_round says {self.attesters_per_round}"
            )
        self.attesters_per_round = derived

    @property
    def attesters_count(self) -> int:
        """The validators, however they were stated (upstream's attestersCount)."""
        return self.attesters_per_round * self.cycle_length


class Attestation(Message):
    """A vote for a head is a vote for all its ancestors within cycleLength
    (CasperIMD.java:105-149); `hs` holds the ancestor ids of head's PARENT."""

    def __init__(self, attester: "Attester", height: int):
        self.attester = attester
        self.height = height
        self.head = attester.head
        self.hs: Set[int] = set()
        cycle_length = attester._p.params.cycle_length
        cur = attester.head.parent
        while cur is not None and cur.height >= attester.head.height - cycle_length:
            self.hs.add(cur.id)
            cur = cur.parent

    def action(self, network, from_node, to_node):
        to_node.on_attestation(self)

    def attests(self, cb: Block) -> bool:
        return cb.id in self.hs

    def __repr__(self):
        return (
            f"Attestation{{attester={self.attester.node_id}, height={self.height}, "
            f"ids={len(self.hs)}}}"
        )


class CasperBlock(Block):
    def __init__(
        self,
        block_producer: Optional["BlockProducer"] = None,
        height: int = 0,
        father: Optional["CasperBlock"] = None,
        attestations_by_height: Optional[Dict[int, Set[Attestation]]] = None,
        time: int = 0,
        genesis: bool = False,
    ):
        if genesis:
            super().__init__(height=0, genesis=True)
            self.attestations_by_height: Dict[int, Set[Attestation]] = {}
            return
        super().__init__(block_producer, height, father, True, time)
        self.attestations_by_height = attestations_by_height or {}

    def __repr__(self):
        if self.id == 0:
            return "genesis"
        return (
            f"{{ height={self.height}, id={self.id}, proposalTime={self.proposal_time}, "
            f"parent={self.parent.id}}}"
        )


class CasperNode(BlockChainNode):
    __slots__ = ("attestations_by_head", "blocks_to_reevaluate", "_p")

    def __init__(self, p: "CasperIMD", byzantine: bool, genesis: CasperBlock):
        super().__init__(p.network().rd, p.nb, byzantine, genesis)
        self._p = p
        self.attestations_by_head: Dict[int, Set[Attestation]] = {}
        self.blocks_to_reevaluate: Set[CasperBlock] = set()

    def best(self, o1: CasperBlock, o2: CasperBlock) -> CasperBlock:
        """GHOST-ish fork choice (CasperIMD.java:204-257)."""
        net, params = self._p.network(), self._p.params
        if o1 is o2:
            return o1
        if o1.height == o2.height:
            # two blocks for one height: slashable, unsupported
            raise RuntimeError(f"same height: {o1}, {o2}")
        if o1.has_direct_link(o2):
            return o2 if o1.height < o2.height else o1

        # phase 1: find the first common ancestor 'H'
        b1, b2 = o1, o2
        while b1.parent is not b2.parent:
            assert b1.parent.height != b2.parent.height
            if b1.parent.height > b2.parent.height:
                b1 = b1.parent
            else:
                b2 = b2.parent
        h = b1.parent

        # phase 2: count the votes on each branch
        b1_votes = self.count_attestations(o1, h)
        b2_votes = self.count_attestations(o2, h)
        if b1_votes > b2_votes:
            return o1
        if b1_votes < b2_votes:
            return o2
        if params.random_on_ties:
            return o1 if net.rd.next_boolean() else o2
        return o1 if b1.id >= b2.id else o2

    def count_attestations(self, start: CasperBlock, h: CasperBlock) -> int:
        """Attestations for 'h' on the branch ending at 'start', counting
        in-block and directly-received ones once (CasperIMD.java:262-288)."""
        a1: Set[Attestation] = set()
        cur = start
        while cur is not h:
            assert cur is not None
            for i in range(cur.height - 1, h.height, -1):
                for a in cur.attestations_by_height.get(i, ()):
                    if a.attests(h):
                        a1.add(a)
            for a in self.attestations_by_head.get(cur.id, ()):
                if a.attests(h):
                    a1.add(a)
            cur = cur.parent
        return len(a1)

    def on_block(self, b: CasperBlock) -> bool:
        """Slot-clock gate (CasperIMD.java:298-314)."""
        net, params = self._p.network(), self._p.params
        delta = net.time - self.genesis.proposal_time + b.height * SLOT_DURATION
        if delta >= 0:
            self.blocks_to_reevaluate.add(self.head)  # head may win later
            self.blocks_to_reevaluate.add(b)
            return super().on_block(b)
        net.register_task(lambda: self.on_block(b), -delta, self)
        return False

    def on_attestation(self, a: Attestation) -> None:
        """(CasperIMD.java:316-337) — attestations are keyed by the head
        they were made on, never reused across branches."""
        self.attestations_by_head.setdefault(a.head.id, set()).add(a)
        if a.head.id in self.blocks_received_by_block_id:
            self.blocks_to_reevaluate.add(a.head)

    def reevaluate_head(self) -> None:
        """Lazy head re-election before emitting (CasperIMD.java:348-353)."""
        for b in self.blocks_to_reevaluate:
            self.head = self.best(self.head, b)
        self.blocks_to_reevaluate.clear()

    def periodic_task(self):
        return None

    @property
    def head_height(self) -> int:
        """The head as a number (the batched state's `proto.head`)."""
        return self.head.height

    @property
    def head_votes(self) -> int:
        """countAttestations for the head against its ancestor
        `cycle_length` blocks back (genesis where the chain is shorter):
        what `best` would weigh for this head in a fork at that ancestor.
        Read by nobody in the protocol; it makes the count's value
        visible where no fork does."""
        h = self.head
        for _ in range(self._p.params.cycle_length):
            if h.parent is None:
                break
            h = h.parent
        return self.count_attestations(self.head, h)

    @property
    def head_score(self) -> int:
        """(head height, head votes) as one integer, the height first: the
        votes never pass the validator count (the batched state's
        `proto.head_score`)."""
        return self.head.height * (self._p.params.attesters_count + 1) + self.head_votes

    def __repr__(self):
        return f"CasperNode{{nodeId={self.node_id}}}"


class BlockProducer(CasperNode):
    __slots__ = ()

    def __init__(self, p: "CasperIMD", genesis: CasperBlock, byzantine: bool = False):
        super().__init__(p, byzantine, genesis)

    def periodic_task(self):
        def task():
            self.reevaluate_head()
            self.create_and_send_block(self._p.network().time // SLOT_DURATION)

        return task

    def build_block(self, base: CasperBlock, height: int) -> CasperBlock:
        """Include every known attestation not yet on the chain
        (CasperIMD.java:383-428)."""
        params, net = self._p.params, self._p.network()
        res: Dict[int, Set[Attestation]] = {}
        i = height - 1
        while i >= 0 and i >= height - params.cycle_length:
            res[i] = set()
            i -= 1

        # phase 1: attestations already included in parent blocks
        all_from_blocks: Set[Attestation] = set()
        cur = base
        while cur is not self.genesis and cur.height >= height - params.cycle_length:
            for ats in cur.attestations_by_height.values():
                all_from_blocks.update(ats)
            cur = cur.parent

        # phase 2: add the missing ones we received directly
        cur = base
        while cur is not None and cur.height >= height - params.cycle_length:
            for a in self.attestations_by_head.get(cur.id, ()):
                if a.height < height and a not in all_from_blocks:
                    res.setdefault(a.height, set()).add(a)
            cur = cur.parent

        return CasperBlock(self, height, base, res, net.time)

    def create_and_send_block(self, height: int) -> None:
        net, params = self._p.network(), self._p.params
        self.head = self.build_block(self.head, height)
        net.send_all(SendBlock(self.head), self, net.time + params.block_construction_time)

    def __repr__(self):
        return f"BlockProducer{{nodeId={self.node_id}}}"


class Attester(CasperNode):
    __slots__ = ()

    def __init__(self, p: "CasperIMD", genesis: CasperBlock):
        super().__init__(p, False, genesis)

    def periodic_task(self):
        def task():
            self.vote(self._p.network().time // SLOT_DURATION)

        return task

    def vote(self, height: int) -> None:
        """Re-elect the head 4 s into the slot, then attest
        (CasperIMD.java:455-464)."""
        net, params = self._p.network(), self._p.params
        self.reevaluate_head()
        v = Attestation(self, height)
        net.send_all(v, self, net.time + params.attestation_construction_time)

    def __repr__(self):
        return f"Attester{{nodeId={self.node_id}}}"


class ByzBlockProducer(BlockProducer):
    """Waits `delay` ms before sending its block (CasperIMD.java:511-580)."""

    __slots__ = ("to_send", "h", "delay", "on_direct_father", "on_older_ancestor",
                 "inc_not_the_best_father")

    def __init__(self, p: "CasperIMD", delay: int, genesis: CasperBlock):
        super().__init__(p, genesis, byzantine=True)
        self.to_send = 1
        self.h = 0
        self.delay = delay
        self.on_direct_father = 0
        self.on_older_ancestor = 0
        self.inc_not_the_best_father = 0

    def reevaluate_h(self, time: int) -> None:
        """Recompute head & slot accounting for our delay
        (CasperIMD.java:529-542)."""
        self.reevaluate_head()
        while self.head.height >= self.to_send:
            self.head = self.head.parent
        slot_time = time - self.delay
        self.h = slot_time // SLOT_DURATION
        if self.h != self.to_send:
            raise RuntimeError(f"h={self.h}, toSend={self.to_send}")

    def periodic_task(self):
        def task():
            self.reevaluate_h(self._p.network().time)
            if self.head.height == self.h - 1:
                self.on_direct_father += 1
            else:
                self.on_older_ancestor += 1
                # deterministic pick (the reference takes an arbitrary
                # HashSet element here)
                rcv = self.blocks_received_by_height.get(self.h - 1, set())
                possible_father = min(rcv, key=lambda b: b.id) if rcv else None
                if possible_father is not None and possible_father.parent.height != self.h - 1:
                    self.inc_not_the_best_father += 1
            self.create_and_send_block(self.to_send)
            self.to_send += self._p.params.block_producers_count

        return task

    def __repr__(self):
        return (
            f"{type(self).__name__}{{delay={self.delay}, "
            f"onDirectFather={self.on_direct_father}, "
            f"onOlderAncestor={self.on_older_ancestor}, "
            f"incNotTheBestFather={self.inc_not_the_best_father}}}"
        )


class ByzBlockProducerSF(ByzBlockProducer):
    """Skips its father's block to steal its transactions
    (CasperIMD.java:583-604)."""

    __slots__ = ()

    def periodic_task(self):
        def task():
            self.reevaluate_h(self._p.network().time)
            if self.head.id != 0 and self.head.height == self.h - 1:
                self.head = self.head.parent
                self.on_direct_father += 1
            else:
                self.on_older_ancestor += 1
            self.create_and_send_block(self.to_send)
            self.to_send += self._p.params.block_producers_count

        return task


class ByzBlockProducerNS(ByzBlockProducer):
    """Skips its father if the father skipped the grandfather
    (CasperIMD.java:610-640)."""

    __slots__ = ("skipped",)

    def __init__(self, p: "CasperIMD", delay: int, genesis: CasperBlock):
        super().__init__(p, delay, genesis)
        self.skipped = 0

    def periodic_task(self):
        def task():
            self.reevaluate_h(self._p.network().time)
            if (
                self.head.id != 0
                and self.head.height == self.h - 1
                and self.head.parent.height == self.h - 3
            ):
                rcv = self.blocks_received_by_height.get(self.h - 2, set())
                b = min(rcv, key=lambda blk: blk.id) if rcv else None
                if b is not None:
                    self.head = b
                    self.skipped += 1
            self.create_and_send_block(self.to_send)
            self.to_send += self._p.params.block_producers_count

        return task

    def __repr__(self):
        return f"ByzantineBPNS{{delay={self.delay}, skipped={self.skipped}}}"


class ByzBlockProducerWF(ByzBlockProducer):
    """Waits for the previous block before applying its delay
    (CasperIMD.java:647-707)."""

    __slots__ = ("late", "on_time")

    def __init__(self, p: "CasperIMD", delay: int, genesis: CasperBlock):
        super().__init__(p, delay, genesis)
        self.late = 0
        self.on_time = 0

    def periodic_task(self):
        def task():
            if self.head is self.genesis and self.to_send == 1:
                # first producer kicks off the system
                self.reevaluate_h(self._p.network().time)
                self.create_and_send_block(self.h)
                self.to_send += self._p.params.block_producers_count

        return task

    def on_block(self, b: CasperBlock) -> bool:
        net, params = self._p.network(), self._p.params
        if super().on_block(b):
            if b.height == self.to_send - 1:
                perfect_date = SLOT_DURATION * self.to_send + self.delay
                th = self.to_send

                def r():
                    self.head = self.build_block(b, th)
                    net.send_all(
                        SendBlock(self.head), self, net.time + params.block_construction_time
                    )

                self.to_send += params.block_producers_count
                if net.time >= perfect_date:
                    r()
                    self.late += 1
                else:
                    net.register_task(r, perfect_date, self)
                    self.on_time += 1
            return True
        return False

    def __repr__(self):
        return f"ByzantineBPWF{{delay={self.delay}, late={self.late}, onTime={self.on_time}}}"


class _ObserverNode(CasperNode):
    __slots__ = ()


@register_protocol("CasperIMD", CasperParameters)
class CasperIMD(Protocol):
    def __init__(self, params: CasperParameters):
        self.params = params
        self._network: BlockChainNetwork = BlockChainNetwork()
        self.nb = registry_node_builders.get_by_name(params.node_builder_name)
        self._network.set_network_latency(
            registry_network_latencies.get_by_name(params.network_latency_name)
        )
        self.genesis = CasperBlock(genesis=True)
        self.attesters: List[Attester] = []
        self.bps: List[BlockProducer] = []
        self._network.add_observer(_ObserverNode(self, False, self.genesis))

    def network(self) -> BlockChainNetwork:
        return self._network

    def copy(self) -> "CasperIMD":
        return CasperIMD(self.params)

    def init(self, byzantine_node: Optional[ByzBlockProducer] = None) -> None:
        """Task schedule (CasperIMD.java:472-508): producer i fires at slot
        i+1, attester committee c fires 4 s into slot 1+c."""
        p, net = self.params, self._network
        if byzantine_node is None:
            byzantine_node = ByzBlockProducerWF(self, 0, self.genesis)
        self.bps.append(byzantine_node)
        net.add_node(byzantine_node)
        net.register_periodic_task(
            byzantine_node.periodic_task(),
            SLOT_DURATION + byzantine_node.delay,
            SLOT_DURATION * p.block_producers_count,
            byzantine_node,
        )
        for i in range(1, p.block_producers_count):
            n = BlockProducer(self, self.genesis)
            self.bps.append(n)
            net.add_node(n)
            net.register_periodic_task(
                n.periodic_task(),
                SLOT_DURATION * (i + 1),
                SLOT_DURATION * p.block_producers_count,
                n,
            )
        for i in range(p.attesters_count):
            n = Attester(self, self.genesis)
            self.attesters.append(n)
            net.add_node(n)
            net.register_periodic_task(
                n.periodic_task(),
                SLOT_DURATION * (1 + i % p.cycle_length) + 4000,
                SLOT_DURATION * p.cycle_length,
                n,
            )
