"""Dfinity consensus: three node roles — block producers, attester
committees, and a random-beacon committee — driving a notarized chain with
3-second rounds.

Reference semantics: protocols/Dfinity.java (block comparator :107-130,
messages :132-186, BlockProducerNode :215-263, AttesterNode :265-351,
RandomBeaconNode :353-424, init :426-450).  Quirks kept: the parameters
object owns the genesis/node lists (so copy() shares them — the reason the
reference's own copy test is disabled), the networkLatencyName parameter is
never read (callers set latency on the network directly, as DfinityTest
does), and RandomBeaconNode.onBlock's inverted return values.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Set

from ..core.params import WParameters, register_protocol
from ..core.registries import registry_node_builders
from ..oracle.blockchain import Block, BlockChainNetwork, BlockChainNode, SendBlock
from ..oracle.messages import Message
from ..oracle.network import Protocol


class DfinityBlock(Block):
    @staticmethod
    def create_genesis() -> "DfinityBlock":
        return DfinityBlock(genesis=True)

    def __init__(self, producer=None, height=0, head=None, valid=True, time=0, genesis=False):
        if genesis:
            super().__init__(height=0, genesis=True)
        else:
            super().__init__(producer, height, head, valid, time)


def dfinity_block_cmp(o1: DfinityBlock, o2: DfinityBlock) -> int:
    """(Dfinity.java:107-130) — note the reference's self-comparison quirk
    on the last line (compares o1's producer id with itself, i.e. ties
    resolve to 0)."""
    if o1 is o2:
        return 0
    if not o2.valid:
        return 1
    if not o1.valid:
        return -1
    if o1.has_direct_link(o2):
        return -1 if o1.height < o2.height else 1
    if o1.height != o2.height:
        return -1 if o1.height < o2.height else 1
    assert o1.producer is not None
    return 0  # Long.compare(o1.producer.nodeId, o1.producer.nodeId)


@dataclasses.dataclass
class DfinityParameters(WParameters):
    block_producers_count: int = 10
    attesters_count: Optional[int] = None  # upstream's 10 unless node_count states the attesters
    attesters_per_round: int = 10
    block_construction_time: int = 1
    attestation_construction_time: int = 1
    percentage_dead_attester: int = 0
    node_builder_name: Optional[str] = None
    network_latency_name: Optional[str] = None  # never read — reference quirk
    # the attester count, where the caller states it (the sweep axis; the
    # producers, the beacon committee and the observer come on top)
    node_count: Optional[int] = None
    # upstream's attestersPerRound / 2 + 1 unless stated: not a parameter
    # upstream, stated only by a control that puts a wrong count in its place
    majority: Optional[int] = None

    round_time: int = dataclasses.field(default=3000, init=False, repr=False)
    block_producers_per_round: int = dataclasses.field(default=5, init=False, repr=False)

    def __post_init__(self):
        """A stated `node_count` IS the attester count; `attesters_count`
        stated beside it must agree (an error, not a number silently
        dropped); with neither stated the attesters are upstream's 10."""
        if self.node_count is None:
            if self.attesters_count is None:
                self.attesters_count = 10
        else:
            if self.node_count <= 0:
                raise ValueError(f"node_count {self.node_count} is not positive")
            if self.attesters_count not in (None, self.node_count):
                raise ValueError(
                    f"node_count {self.node_count} states the attesters, "
                    f"attesters_count says {self.attesters_count}"
                )
            self.attesters_count = self.node_count
        self.block_producers_round = self.block_producers_count // self.block_producers_per_round
        self.attesters_round = self.attesters_count // self.attesters_per_round
        # simplification: the beacon committee has the attesters' size
        self.random_beacon_count = self.attesters_per_round
        if self.majority is None:
            self.majority = (self.attesters_per_round // 2) + 1
        # mutable protocol state living on the params object, like the
        # reference (Dfinity.java:35-40)
        self.genesis = DfinityBlock.create_genesis()
        self.attesters: List[AttesterNode] = []
        self.bps: List[BlockProducerNode] = []
        self.rds: List[RandomBeaconNode] = []


class BlockProposal(Message):
    def __init__(self, block: DfinityBlock):
        self.block = block

    def action(self, network, from_node, to_node):
        to_node.on_proposal(self.block)


class Vote(Message):
    def __init__(self, vote_for: DfinityBlock):
        self.vote_for = vote_for

    def action(self, network, from_node, to_node):
        to_node.on_vote(from_node, self.vote_for)


class RandomBeaconExchange(Message):
    def __init__(self, height: int):
        self.height = height

    def action(self, network, from_node, to_node):
        to_node.on_random_beacon_exchange(from_node, self.height)


class RandomBeaconResult(Message):
    def __init__(self, height: int, rd: int):
        self.height = height
        self.rd = rd

    def action(self, network, from_node, to_node):
        to_node.on_random_beacon(self.height, self.rd)


class DfinityNode(BlockChainNode):
    __slots__ = ("committee_majority_blocks", "committee_majority_height", "last_random_beacon", "_p")

    def __init__(self, p: "Dfinity", genesis: DfinityBlock):
        super().__init__(p.network().rd, p.nb, False, genesis)
        self._p = p
        self.committee_majority_blocks: Set[int] = set()
        self.committee_majority_height: Set[int] = set()
        self.last_random_beacon = 0

    def best(self, o1: DfinityBlock, o2: DfinityBlock) -> DfinityBlock:
        return o1 if dfinity_block_cmp(o1, o2) >= 0 else o2

    def on_vote(self, voter, vote_for: DfinityBlock) -> None:
        pass

    @property
    def votes_held(self) -> int:
        """The largest voter set the node holds (an attester's alone)."""
        return 0

    @property
    def chain_score(self) -> int:
        """One integer a node for what the chain and the vote count have
        come to: the head's height, and the most voters the node has
        counted for one block (the batched program's leaf
        `proto.chain_score`).  A committee counts to the majority and
        stops, so a wrong majority moves the count, a wrong beacon or a
        late vote the height."""
        return self.head.height * (self._p.params.attesters_per_round + 1) + self.votes_held

    def on_random_beacon(self, height: int, rd: int) -> None:
        """Can be called multiple times for a single node."""
        if self.last_random_beacon < height:
            self.last_random_beacon = height
            self.on_random_beacon_once(height, rd)

    def on_random_beacon_once(self, height: int, rd: int) -> None:
        pass

    def on_proposal(self, b: DfinityBlock) -> None:  # only attesters receive these
        raise NotImplementedError


class BlockProducerNode(DfinityNode):
    __slots__ = ("my_round", "wait_for_block_height")

    def __init__(self, p: "Dfinity", my_round: int, genesis: DfinityBlock):
        super().__init__(p, genesis)
        self.my_round = my_round
        self.wait_for_block_height = -1

    def create_proposal(self, height: int) -> None:
        """(Dfinity.java:225-240)."""
        net, params = self._p.network(), self._p.params
        if self.head.height != height - 1:
            raise ValueError(f"head={self.head.height}, height={height}")
        new_block = DfinityBlock(self, height, self.head, True, net.time)
        attesters_s = list(params.attesters)
        net.rd.shuffle(attesters_s)
        net.send(
            BlockProposal(new_block),
            net.time + params.block_construction_time,
            self,
            attesters_s,
        )
        self.wait_for_block_height = -1

    def on_block(self, b: DfinityBlock) -> bool:
        if not super().on_block(b):
            return False
        if self.head.height == self.wait_for_block_height:
            self.create_proposal(self.wait_for_block_height + 1)
        return True

    def on_random_beacon_once(self, h: int, rd: int) -> None:
        """If randomly selected, propose (or wait for the parent block)."""
        if rd % self._p.params.block_producers_round == self.my_round:
            if self.head.height == h - 1:
                self.create_proposal(h)


class AttesterNode(DfinityNode):
    __slots__ = ("votes", "proposals", "my_round", "vote_for_height")

    def __init__(self, p: "Dfinity", my_round: int, genesis: DfinityBlock):
        super().__init__(p, genesis)
        self.votes: Dict[int, Set[int]] = {}
        self.proposals: List[DfinityBlock] = []
        self.my_round = my_round
        self.vote_for_height = -1

    @property
    def votes_held(self) -> int:
        return max((len(v) for v in self.votes.values()), default=0)

    def on_vote(self, voter, vote_for: DfinityBlock) -> None:
        voters = self.votes.setdefault(vote_for.id, set())
        if self.vote_for_height == vote_for.height:
            if voter.node_id not in voters:
                voters.add(voter.node_id)
                if len(voters) >= self._p.params.majority:
                    self._send_block(vote_for)

    def _send_block(self, vote_for: DfinityBlock) -> None:
        self.committee_majority_blocks.add(vote_for.id)
        self.committee_majority_height.add(vote_for.height)
        self.vote_for_height = -1
        self._p.network().send_all(SendBlock(vote_for), self)

    def on_proposal(self, b: DfinityBlock) -> None:
        """Vote for proposals at our height; at majority, notarize and
        broadcast (Dfinity.java:298-318)."""
        net, params = self._p.network(), self._p.params
        if self.vote_for_height == b.height:
            voters = self.votes.setdefault(b.id, set())
            if self.node_id not in voters:
                voters.add(self.node_id)
                if len(voters) >= params.majority:
                    self._send_block(b)
                else:
                    v = Vote(b)
                    attesters_s = list(params.attesters)
                    net.rd.shuffle(attesters_s)
                    net.send(
                        v, net.time + params.attestation_construction_time, self, attesters_s
                    )
        elif b.height > self.head.height:
            # buffer proposals received in advance
            self.proposals.append(b)

    def on_block(self, b: DfinityBlock) -> bool:
        if not super().on_block(b):
            return False
        self.committee_majority_blocks.add(b.id)
        self.committee_majority_height.add(b.height)
        if self.vote_for_height == b.height:
            self.vote_for_height = -1
        return True

    def on_random_beacon_once(self, h: int, rd: int) -> None:
        """(Dfinity.java:335-350)."""
        net, params = self._p.network(), self._p.params
        if rd % params.attesters_round == self.my_round and h not in self.committee_majority_height:
            self.vote_for_height = h
            sent: Set[DfinityBlock] = set()
            for b in self.proposals:
                if b.height == h and b not in sent:
                    sent.add(b)
                    v = Vote(b)
                    attesters_s = list(params.attesters)
                    net.rd.shuffle(attesters_s)
                    net.send(
                        v, net.time + params.attestation_construction_time, self, attesters_s
                    )
            self.proposals.clear()


class RandomBeaconNode(DfinityNode):
    __slots__ = ("rd_value", "height", "last_rd_sent", "exchanged")

    def __init__(self, p: "Dfinity", genesis: DfinityBlock):
        super().__init__(p, genesis)
        self.rd_value = 0
        self.height = 1
        self.last_rd_sent = 0
        self.exchanged: Dict[int, Set[int]] = {}

    def on_random_beacon_exchange(self, from_node: "RandomBeaconNode", height: int) -> None:
        if height >= self.height and height > self.last_rd_sent:
            voters = self.exchanged.setdefault(height, set())
            if from_node.node_id not in voters:
                voters.add(from_node.node_id)
                if height == self.height and len(voters) >= self._p.params.majority:
                    self.send_rb()

    def send_rb(self) -> None:
        net, params = self._p.network(), self._p.params
        self.rd_value = self.height  # height as a stand-in for threshold sigs
        self.last_rd_sent = self.height
        rb = RandomBeaconResult(self.height, self.rd_value)
        net.send_all(rb, self, net.time + params.attestation_construction_time)

    def on_block(self, b: DfinityBlock) -> bool:
        """A block at our height starts the next beacon round.  Note the
        reference's inverted returns (true on reject, false on success —
        Dfinity.java:387-410), kept verbatim."""
        net, params = self._p.network(), self._p.params
        if not super().on_block(b):
            return True
        if self.head.height == self.height:
            self.height += 1
            voters = self.exchanged.setdefault(self.height, set())
            if self.node_id not in voters:
                voters.add(self.node_id)
                if len(voters) >= params.majority:
                    self.send_rb()
                    return False
            # the len-check replays the reference's `voters.add(id) &&
            # size >= majority` short-circuit: add failed or not enough
            assert self.head.parent is not None
            wt = self.head.parent.proposal_time + params.round_time * 2
            if wt <= net.time:
                wt = net.time + params.attestation_construction_time
            rbe = RandomBeaconExchange(self.height)
            rds_sends = list(params.rds)
            net.rd.shuffle(rds_sends)
            net.send(rbe, wt, self, rds_sends)
        return False

    def on_random_beacon_once(self, h: int, rd: int) -> None:
        """Accept a beacon generated by others before we finished."""
        if h > self.height:
            self.last_rd_sent = self.height
            self.height = h
            self.rd_value = rd


class _ObserverNode(DfinityNode):
    """The anonymous DfinityNode subclass used as observer (Dfinity.java:89)."""
    __slots__ = ()


@register_protocol("Dfinity", DfinityParameters)
class Dfinity(Protocol):
    def __init__(self, params: DfinityParameters):
        self.params = params
        self._network: BlockChainNetwork = BlockChainNetwork()
        self.nb = registry_node_builders.get_by_name(params.node_builder_name)
        # NOTE: network_latency_name is not applied — the reference never
        # reads it (Dfinity.java:86-90); callers override network latency
        # directly (DfinityTest.java:18)
        self._network.add_observer(_ObserverNode(self, params.genesis))

    def network(self) -> BlockChainNetwork:
        return self._network

    def copy(self) -> "Dfinity":
        return Dfinity(self.params)

    def init(self) -> None:
        """(Dfinity.java:426-450)."""
        p, net = self.params, self._network
        for i in range(p.attesters_count):
            n = AttesterNode(self, i % p.attesters_round, p.genesis)
            p.attesters.append(n)
            net.add_node(n)
        for i in range(p.block_producers_count):
            n = BlockProducerNode(self, i % p.block_producers_round, p.genesis)
            p.bps.append(n)
            net.add_node(n)
        for _ in range(p.random_beacon_count):
            n = RandomBeaconNode(self, p.genesis)
            p.rds.append(n)
            net.add_node(n)
        net.rd.shuffle(p.bps)
        for n in p.rds:
            n.send_rb()


def main():
    from ..oracle.blockchain import Block

    Block.reset_block_ids()
    bc = Dfinity(DfinityParameters())
    bc.init()
    bc.network().run(50)
    bc.network().partition(0.20)
    bc.network().run(2_000)
    bc.network().end_partition()
    bc.network().run(50)
    bc.network().print_stat(False)


if __name__ == "__main__":
    main()
