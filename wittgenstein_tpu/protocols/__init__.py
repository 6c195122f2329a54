"""Protocol implementations.

Each protocol has an oracle implementation (exact DES semantics, classes on
wittgenstein_tpu.oracle) and — for the performance-critical families — a
batched TPU implementation (kernels on wittgenstein_tpu.core.engine).
Importing this package registers every protocol in
wittgenstein_tpu.core.params.protocol_registry (the API-discovery contract).
"""

from . import (  # noqa: F401
    casper,
    dfinity,
    dfinity_part,
    enr_gossiping,
    ethpow,
    gsf,
    handel,
    handeleth2,
    optimistic_p2p_signature,
    p2pflood,
    p2phandel,
    paxos,
    pingpong,
    sanfermin,
    sanfermin_cappos,
    slush,
    snowflake,
)

__all__ = [
    "casper",
    "dfinity",
    "dfinity_part",
    "enr_gossiping",
    "ethpow",
    "gsf",
    "handel",
    "handeleth2",
    "optimistic_p2p_signature",
    "p2pflood",
    "p2phandel",
    "paxos",
    "pingpong",
    "sanfermin",
    "sanfermin_cappos",
    "slush",
    "snowflake",
]
