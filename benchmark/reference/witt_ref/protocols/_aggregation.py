"""Shared pieces of the San Fermin-style aggregation protocols
(GSFSignature, Handel, handeleth2): the binary-tree membership trick and
the common parameter normalization/validation."""

from __future__ import annotations


def all_sigs_at_level(node_id: int, round_: int, node_count: int) -> int:
    """All the signatures a node should have when `round_` is finished —
    the sibling-subtree bitmask trick (Handel.java:634-647,
    GSFSignature.java:361-374)."""
    if round_ < 1:
        raise ValueError(f"round={round_}")
    c_mask = (1 << round_) - 1
    start = (c_mask | node_id) ^ c_mask
    end = min(node_id | c_mask, node_count - 1)
    res = ((1 << (end + 1)) - 1) ^ ((1 << start) - 1)
    res &= ~(1 << node_id)
    return res


def normalize_agg_params(p) -> None:
    """Threshold/nodes_down normalization + validation shared by the
    aggregation parameter classes: -1 -> 99% default, float -> ratio of
    node_count (mirroring the reference's int vs ratio constructor
    overloads)."""
    if p.threshold == -1:
        p.threshold = int(p.node_count * 0.99)
    elif isinstance(p.threshold, float):
        p.threshold = int(p.threshold * p.node_count)
    if isinstance(p.nodes_down, float):
        p.nodes_down = int(p.nodes_down * p.node_count)
    if (
        p.nodes_down >= p.node_count
        or p.nodes_down < 0
        or p.threshold > p.node_count
        or (p.nodes_down + p.threshold > p.node_count)
    ):
        raise ValueError(f"nodeCount={p.node_count}, threshold={p.threshold}")
