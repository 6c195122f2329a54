"""Dfinity with a part of the network cut off: the one experiment
upstream's Dfinity ships (Dfinity.java `main()` :452-465: `init()`,
`run(50)`, `network.partition(0.20f)`, `run(2_000)`, `endPartition()`,
`run(50)`; results :466-480, 5685 blocks on a bad network, 4665 with the
20% partition), as a deployment a parameter object can state.

Upstream's `DfinityParameters` has no field for the partition (it is
`main()`'s), and a harness builds a protocol from parameters alone, calls
`init()` and runs: so the line is a parameter here and `init()` draws it.
Departures from upstream's `main()`, `protocols/dfinity.py` `main`, which
stays as it is:

  * the line stands from t=0, not after 50 sound seconds: the chain is at
    genesis and not at about height 16 when it is cut;
  * it is drawn BEFORE the beacon's first results leave (`init()` ends
    with every beacon node's `send_rb()`), so those results are filtered
    where they are sent, as every later message is, and counted in
    `Network.dropped`; drawn after them they would be in flight under the
    line and discarded where they are due, uncounted.  The nodes'
    positions come from the generator either way (`partition` draws
    nothing), so who is behind the line is the same;
  * it is never lifted: `BlockChainNetwork.end_partition` (every node
    sends its head to every node) is the caller's to run.

`partition` 0 draws no line: the protocol is then `Dfinity` itself, which
is what a control states to put the sound network in this one's place.

Who is behind the line is part of the deployment: the nodes' positions
come from the network's generator, and with ten block producers a
population in 160 has six of them behind a line at 0.20, a chain that
sends two thirds of what the usual two behind it send.  A caller that
seeds the generator run by run (a harness: `rd.set_seed(seed)`, then
`init()`) would compare another deployment each time, so
`population_seed` states the generator's seed as a parameter and
`init()` sets it before anything is drawn.  None leaves the generator as
the caller seeded it (the tests' populations).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from ..core.params import register_protocol
from .dfinity import Dfinity, DfinityParameters


@dataclasses.dataclass
class PartitionedDfinityParameters(DfinityParameters):
    # the share of the x axis left of the line (Network.partition's `part`):
    # upstream's main() cuts at 0.20; 0 draws no line
    partition: float = 0.20
    # the seed of the generator the population is drawn from (positions,
    # and so who is behind the line; the producers' order); None: the caller's
    population_seed: Optional[int] = None


@register_protocol("PartitionedDfinity", PartitionedDfinityParameters)
class PartitionedDfinity(Dfinity):
    def copy(self) -> "PartitionedDfinity":
        return PartitionedDfinity(self.params)

    def init(self) -> None:
        if self.params.population_seed is not None:
            self.network().rd.set_seed(self.params.population_seed)
        if self.params.partition:
            self.network().partition(self.params.partition)
        super().init()
