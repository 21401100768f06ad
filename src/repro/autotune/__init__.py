"""repro.autotune — cost-model-guided strategy autotuner.

The paper's thesis is that parallelisation strategies are chosen at the
functional level and compiled strategy-preservingly; this package chooses
them *automatically*.  It generalises the seed's dot-only exhaustive search
(ICFP'15 style, cf. ELEVATE arXiv:2002.02268) into a real autotuner:

  space    — strategy-space enumeration over the DPIA rewrites
             (split_join / blocked_reduce / fuse_map_into_reduce /
             vectorize / level assignment) for dot/reduce, map, matmul,
             rmsnorm and softmax-like kernels
  cost     — analytical roofline cost model (FLOPs, HBM/VMEM bytes,
             grid/loop overhead) ranking candidates without executing,
             plus an HLO-derived refinement via repro.analysis.hlo_counter
  measure  — compile-and-time refinement of the analytic top-k through the
             stage1 -> stage2 -> stage3 pipeline (jnp / pallas-interpret)
  cache    — persistent on-disk JSON tuning cache keyed by
             (kernel, shape, dtype, backend, mesh), with in-process memo
  api      — ``tune(...)`` / ``get_tuned(...)`` / ``@autotuned`` entry points

See docs/autotune.md for the cache format and the strategy-space tables.
"""
from . import api, cache, cost, measure, space  # noqa: F401
from .api import (  # noqa: F401
    TuneResult, autotuned, get_tuned, model_kernel_shapes, pick_kv_layout,
    tune, warm_for_model,
)
from .cache import TuningCache, default_cache  # noqa: F401
from .cost import (  # noqa: F401
    PEAKS, CostEstimate, HwModel, KvLayoutCost, device_kind, estimate,
    hw_model, kv_layout_cost, xla_cost,
)
from .space import Candidate, candidate_from_params, default_params, enumerate_space  # noqa: F401
