"""Blockchain abstractions: blocks, fork-choice-bearing nodes, and a network
that re-floods heads when a partition ends.

Reference semantics: core Block.java / BlockChainNode.java /
BlockChainNetwork.java.
"""

from __future__ import annotations

from typing import Dict, Generic, Optional, Set, TypeVar

from ..core.node import Node, NodeBuilder
from ..utils.javarand import JavaRandom
from .messages import Message
from .network import Network

TB = TypeVar("TB", bound="Block")


class Block:
    """Immutable block; ids are globally unique via a class counter
    (Block.java:10-13).  Use reset_block_ids() between independent runs if id
    determinism across runs matters."""

    _block_id = 1

    @classmethod
    def get_last_block_id(cls) -> int:
        return Block._block_id

    @classmethod
    def reset_block_ids(cls) -> None:
        Block._block_id = 1

    def __init__(
        self,
        producer: Optional["BlockChainNode"] = None,
        height: int = 0,
        parent: Optional["Block"] = None,
        valid: bool = True,
        time: int = 0,
        genesis: bool = False,
    ):
        if genesis:
            self.height = height
            self.last_tx_id = 0
            self.id = 0
            self.parent = None
            self.producer = None
            self.proposal_time = 0
            self.valid = True
            return
        if height <= 0:
            raise ValueError("Only the genesis block has a special height")
        if parent is not None and time < parent.proposal_time:
            raise ValueError(f"bad time: parent is ({parent}), our time:{time}")
        if parent is not None and parent.height >= height:
            raise ValueError(f"Bad parent. me height:{height}, parent:{parent}")
        self.producer = producer
        self.height = height
        self.id = Block._block_id
        Block._block_id += 1
        self.parent = parent
        self.valid = valid
        self.last_tx_id = time
        self.proposal_time = time

    def tx_count(self) -> int:
        if self.id == 0:
            return 0
        assert self.parent is not None
        res = self.last_tx_id - self.parent.last_tx_id
        if res < 0:
            raise RuntimeError(f"{self}, bad txCount:{res}")
        return res

    def is_ancestor(self, b: "Block") -> bool:
        """True if self is a strict ancestor of b (Block.java:75-86)."""
        if self is b:
            return False
        cur = b
        while cur.height > self.height:
            cur = cur.parent
            assert cur is not None
        return cur is self

    def has_direct_link(self, b: "Block") -> bool:
        if b is self:
            return True
        if b.height == self.height:
            return False
        older = self if self.height > b.height else b
        young = self if self.height < b.height else b
        while older.height > young.height:
            older = older.parent
            assert older is not None
        return older is young

    def __repr__(self) -> str:
        if self.id == 0:
            return "genesis"
        return (
            f"h:{self.height}, id={self.id}, creationTime:{self.proposal_time}, "
            f"producer={self.producer.node_id if self.producer else 'null'}, "
            f"parent:{self.parent.id if self.parent else 'null'}"
        )


class BlockChainNode(Node, Generic[TB]):
    __slots__ = (
        "genesis",
        "blocks_received_by_block_id",
        "blocks_received_by_father_id",
        "blocks_received_by_height",
        "head",
    )

    def __init__(self, rd: JavaRandom, nb: NodeBuilder, byzantine: bool, genesis: TB):
        super().__init__(rd, nb, byzantine)
        self.genesis = genesis
        self.blocks_received_by_block_id: Dict[int, TB] = {genesis.id: genesis}
        self.blocks_received_by_father_id: Dict[int, Set[TB]] = {}
        self.blocks_received_by_height: Dict[int, Set[TB]] = {}
        self.head = genesis

    def on_block(self, b: TB) -> bool:
        if not b.valid:
            return False
        if b.id in self.blocks_received_by_block_id:
            return False
        self.blocks_received_by_block_id[b.id] = b
        self.blocks_received_by_father_id.setdefault(b.parent.id, set()).add(b)
        self.blocks_received_by_height.setdefault(b.height, set()).add(b)
        self.head = self.best(self.head, b)
        return True

    def best(self, cur: TB, alt: TB) -> TB:
        """Fork choice; must be provided by the protocol."""
        raise NotImplementedError

    def txs_created_in_chain(self, head: Block) -> int:
        txs = 0
        cur = head
        while cur is not None:
            if cur.producer is self:
                txs += cur.tx_count()
            cur = cur.parent
        return txs

    def blocks_created_in_chain(self, head: Block) -> int:
        blocks = 0
        cur = head
        while cur is not None:
            if cur.producer is self:
                blocks += 1
            cur = cur.parent
        return blocks


class SendBlock(Message):
    def __init__(self, to_send: Block):
        self.to_send = to_send

    def action(self, network, from_node, to_node) -> None:
        to_node.on_block(self.to_send)

    def __repr__(self) -> str:
        return f"SendBlock{{toSend={self.to_send.id}}}"


class BlockChainNetwork(Network):
    """Adds an observer node and full head re-broadcast when a partition
    ends (BlockChainNetwork.java:43-55)."""

    def __init__(self):
        super().__init__()
        self.observer: Optional[BlockChainNode] = None

    def add_observer(self, observer: BlockChainNode) -> None:
        self.observer = observer
        self.add_node(observer)

    def end_partition(self) -> None:
        super().end_partition()
        for n in self.all_nodes:
            self.send_all(SendBlock(n.head), n)

    def print_stat(self, small: bool) -> None:
        production_count: Dict[int, Set[Block]] = {}
        block_producers = []
        cur = self.observer.head
        block_in_chain = 0
        while cur is not self.observer.genesis:
            assert cur is not None and cur.producer is not None
            if not small:
                print(f"block: {cur}")
            block_in_chain += 1
            production_count.setdefault(cur.producer.node_id, set()).add(cur)
            if cur.producer not in block_producers:
                block_producers.append(cur.producer)
            cur = cur.parent
        if not small:
            print(
                f"block count:{block_in_chain} on {Block.get_last_block_id()}, "
                f"all tx: {self.observer.head.last_tx_id}"
            )
        for bp in sorted(block_producers, key=lambda o: o.node_id):
            bp_tx = sum(b.tx_count() for b in production_count[bp.node_id])
            if not small or bp.byzantine:
                print(
                    f"{bp}; {len(production_count[bp.node_id])}; {bp_tx}; "
                    f"{bp.msg_sent}; {bp.msg_received}"
                )
