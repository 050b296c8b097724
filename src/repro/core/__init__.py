"""R2CCL core: fault-tolerant collective communication in JAX.

The paper's contribution as a composable library:

  topology     — cluster / node / NIC (rail) model, PCIe-distance chains
  failures     — failure taxonomy (Table 2) + injection schedules
  detection    — bilateral awareness + probe triangulation (Section 4.1-4.2)
  migration    — multi-NIC registration + DMA-buffer rollback (Section 4.3)
  balance      — R2CCL-Balance NIC-level redistribution (Section 5.1)
  partition    — Appendix-A optimal split Y*, threshold ng/(3ng-2)
  allreduce    — R2CCL-AllReduce program builder (Section 5.2)
  reranking    — bridge-based logical re-ranking, Algorithm 1 (Section 6)
  recursive    — recursive decomposition over bandwidth spectra (Section 6)
  planner      — alpha-beta strategy selection (Table 1)
  schedule     — collective schedule IR + ring builders
  executor_np  — numpy rank-parallel oracle executor
  collectives  — JAX shard_map/ppermute execution (the data plane)
  event_sim    — discrete-event cluster simulator (per-link fair sharing,
                 timestamped failure injection, rollback accounting)
  comm_sim     — alpha-beta cluster simulator (SimAI-lite) for evaluation,
                 with mode="event" delegating to event_sim
  spans        — names of the profiler's host spans and device scopes
"""

from . import (  # noqa: F401
    allreduce,
    balance,
    detection,
    event_sim,
    executor_np,
    failures,
    migration,
    partition,
    planner,
    recursive,
    reranking,
    schedule,
    topology,
)
from .event_sim import EventSimReport, simulate_program, simulate_schedule  # noqa: F401
from .failures import Failure, FailureState, FailureType  # noqa: F401
from .planner import CommConfig, Planner, Strategy  # noqa: F401

# collectives / comm_sim import jax lazily-heavy modules; keep them available
# as attributes without forcing jax import order issues for pure-math users.
from . import collectives, comm_sim  # noqa: F401  (jax-dependent)
from .collectives import all_reduce, all_reduce_mean, sync_gradients  # noqa: F401
