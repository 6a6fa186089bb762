"""Unified reduction engine: one dispatch layer over every MMA-reduction path.

The paper's contribution is a single algorithmic idea -- encode the reduction
of ``n`` numbers as chained m x m MMA operations, ``T(n) = 5 log_{m^2}(n)`` --
and this package is its single entry point. ``reduce()`` serves every kind
("sum", "mean", "sumsq", "norm2", "moments") over every registered backend:

  xla          -- jnp baseline / oracle
  mma_jnp      -- the paper's hierarchy as pure-JAX dots (runs anywhere)
  pallas_hier  -- Pallas TPU kernel, paper-faithful multi-launch recurrence
  pallas_fused -- Pallas TPU kernel, single-launch C-accumulator variant
  segmented    -- auto-route for multi-reduce problems (resolves per call)

with a cost-model-driven planner (``ReducePlan`` / ``plan_for`` -- memoized,
with an opt-in empirical ``autotune``) choosing the backend, tile size ``m``,
block depth, lane count ``num_cores`` (the Pallas kernels stream a striped
("parallel", "arbitrary") grid -- one accumulator lane per TPU core, with a
deterministic fixed-order combine), and dtypes per problem shape, and a
Kahan-compensated precision policy as an orthogonal option. Everything is differentiable (custom VJP:
broadcast of the cotangent, per segment for the batched paths).

``reduce_many`` batches N independent reductions into ONE backend pass (one
segment_sum / one eq. (9) dot / one multi-operand Pallas launch), and
``reduce_tree`` rides the same machinery so a whole pytree's clipping
statistic costs a single kernel launch.

``scan`` (also exported as ``repro.scan``) extends the same encoding to
PREFIX sums with triangular MMA operands (Dakkak et al., PAPERS.md): a
``ScanPlan`` / ``scan_plan_for`` route over the same registry (xla
cumsum, mma_jnp triangular einsum, pallas_fused triangular kernel), the
same zero-copy native ingest and quarantine machinery, and a custom VJP
(cumsum cotangent = reversed cumsum).

Zero-copy ingestion: the Pallas paths read the caller's buffer directly --
flat native-dtype (bf16/f16/f32) BlockSpecs with the tile reshape, compute
cast, and tail masking done in-VMEM -- so a bf16 reduction moves n*2 HBM
bytes instead of the staged read-n*2 + write-n*4 + read-n*4. In-kernel
prologues extend the same property to the norm kinds: sumsq/norm2 square
(and moments pairs, via a dual accumulator) INSIDE the kernel body, so the
whole norm path -- including reduce_tree's clipping statistic -- streams
the raw leaf exactly once with no host-side elementwise pass.
``repro.reduce.inspect`` proves the property on lowered jaxprs
(``assert_staging_free`` / ``measured_hbm_bytes``) and
``cost_model.hbm_bytes`` models it; ``benchmarks/check_bench.py`` gates CI
on both.

Model, optimizer, launch and benchmark code all route reductions through
here; ``repro.core.mma_reduce`` and ``repro.kernels.mma_reduce`` are the
backend *implementations* and should not be called directly by new code.
"""

from repro.reduce.api import (  # noqa: F401
    KINDS,
    reduce,
    reduce_many,
    reduce_tree,
)
from repro.reduce.scan import (  # noqa: F401
    SCAN_KINDS,
    scan,
)
from repro.reduce.backends import (  # noqa: F401
    Backend,
    available_backends,
    get_backend,
    register_backend,
)
from repro.reduce.plan import (  # noqa: F401
    BACKEND_ENV,
    ReducePlan,
    ScanPlan,
    autotune,
    autotune_failures,
    backend_for_flags,
    default_backend,
    plan_cache_clear,
    plan_cache_info,
    plan_for,
    quarantine_backend,
    quarantined_backends,
    reinstate_backend,
    scan_plan_cache_info,
    scan_plan_for,
    segmented_backend_for,
    set_default_backend,
)
