"""Cost-attribution profiling: the machine side of ROADMAP item 1.

PR 2's telemetry counts *protocol* events; this package attributes
*machine cost* and keeps every performance claim a measured artifact:

  xla_cost.py  normalized `compile().cost_analysis()` (FLOPs, bytes,
               transcendentals) + `memory_analysis()` (argument/output/
               temp/code bytes) for any jitted entry point — the
               capture behind the run cache's per-program accounting
               (parallel.replica_shard.run_cache_metrics).
  hbm.py       pytree-leaf HBM footprint model: bytes/replica from the
               actual SimState leaves, HBM-bounded replicas/chip — the
               number behind the "~106 MiB/replica at D=32" claim and
               the feasibility budget's R.
  probe.py     the TTL'd TPU probe-verdict cache file + the run-record /
               Prometheus surface of the verdict (nothing writes one;
               ROADMAP C1).
  budget.py    the chip-independent feasibility arithmetic: measured
               ticks/sim × HBM-bounded replicas/chip → required tick_µs
               for the 21 sims/s/chip north star (BUDGET.json via
               scripts/budget_report.py).

See docs/profiling.md for the phase map and per-backend caveats.
"""

from .budget import (
    budget_from_parts,
    budget_staleness,
    load_budget,
    required_tick_us,
)
from .hbm import hbm_report, replicas_per_chip, state_bytes_per_replica
from .probe import (
    PROBE_CACHE_TTL_S,
    probe_cache_path,
    probe_verdict_fields,
    read_probe_cache,
    write_probe_cache,
)
from .xla_cost import compiled_cost_summary, cost_analysis_dict, memory_analysis_dict

__all__ = [
    "PROBE_CACHE_TTL_S",
    "budget_from_parts",
    "budget_staleness",
    "compiled_cost_summary",
    "cost_analysis_dict",
    "hbm_report",
    "load_budget",
    "memory_analysis_dict",
    "probe_cache_path",
    "probe_verdict_fields",
    "read_probe_cache",
    "replicas_per_chip",
    "required_tick_us",
    "state_bytes_per_replica",
    "write_probe_cache",
]
