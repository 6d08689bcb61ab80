"""repro.reduce — one front door for every reduction in the repo.

The paper's contribution is a single contract: stream in back-to-back
variable-length sets, emit one in-order result per set with bounded
state.  This package exposes that contract once, with three orthogonal
first-class knobs:

  * **op** (the algebra): ``sum`` / ``mean`` / ``weighted_sum`` /
    ``sumsq`` / ``moments`` / ``poly`` — a registry (``algebra.py``,
    extensible via ``@register_op``) of row-local pre/post hooks around
    the one block schedule, so every op inherits every policy/backend
    guarantee below (see docs/algebra.md).
  * **policy** (accuracy): ``fast`` (f32 fixed pairing tree),
    ``compensated`` (Kahan/two-sum), ``exact`` (INTAC single-limb int32),
    ``exact2`` (integer carry-save limbs + residual-digit superaccumulator:
    full resolution at any N, <=1 ulp of the f64 reference for arbitrary
    f32, all-int32 carry), and ``procrastinate`` (exponent-indexed bins —
    <=1 ulp for arbitrary f32 absent catastrophic cancellation)
    — ``policy.py``, extensible via ``@register_policy``.
  * **backend** (executor): ``ref`` / ``blocked`` / ``pallas`` /
    ``shard_map`` (multi-device) — all run the same block schedule so
    results match bitwise per policy; all-integer carry state (every
    component of exact / exact2 / procrastinate) additionally matches
    bitwise at any shard count, mesh shape, and device permutation —
    ``backends.py``, extensible via ``@register_backend``.

What a backend executes is a *staged block-program* (``program.py``): a
planned (``plan_program``) pair of declared stages per schedule block —
the memory-bound gather/contrib stage (one-hot dot or PhasedAccu-style
lane-parallel scatter, chosen by cost model) and the compute-bound carry
update — with byte/flop hints that tell executors what to overlap (the
pallas kernel double-buffers tiles against the update) and the roofline
tooling what to plot.

Entry points:
  ``reduce(values, segment_ids=..., num_segments=..., op=..., ...)``
      the call — see ``api.py``; ``ReduceSpec`` for reusable static specs.
  ``Accumulator`` protocol (``accumulator.py``)
      streaming init/push/merge/finalize — TreeAccumulator (gradient
      juggler), FlashAccumulator (online softmax) and CascadeAccumulator
      compose with lax.scan and trees.
  ``collective_mean`` (``collective.py``)
      the same policy knob for cross-device gradient means (the integer
      tiers run their ``Policy``, as ``reduce`` does);
      ``elastic_reduce_mean`` for the topology-elastic (resume-anywhere)
      global mean.
  ``ReduceStatus`` (``api.py``)
      opt-in guard rails — ``reduce(..., with_status=True)`` reports
      NaN/Inf payloads, int32 carry saturation, degradation, and the
      kept-row count; ``on_overflow="degrade"`` re-plans instead of
      rejecting (see docs/robustness.md).
  ``OUT_OF_RANGE_LABEL``
      the repo-wide padding sentinel: rows so labeled drop out of every
      sum and count, on every backend.
"""

from .accumulator import (Accumulator, CascadeAccumulator,  # noqa: F401
                          FlashAccumulator, TreeAccumulator,
                          accumulate_microbatch_grads, merge_across,
                          merge_tree, reduce_microbatch_grads,
                          scan_accumulate)
from .algebra import (REDUCE_OPS, ReduceOp, cascade_poly_coeffs,  # noqa: F401
                      cascade_weights, fir_weights, get_op, poly_weights,
                      register_op)
from .api import ReduceSpec, ReduceStatus, reduce  # noqa: F401
from .backends import (BACKENDS, Backend, OUT_OF_RANGE_LABEL,  # noqa: F401
                       ambient_mesh, default_mesh, get_backend,
                       mask_out_of_range, register_backend, select_backend,
                       select_local_backend)
from .collective import (COLLECTIVE_POLICIES, collective_mean,  # noqa: F401
                         collective_mean_tree, collective_moments,
                         collective_weighted_mean, elastic_reduce_mean,
                         merge_carry_across)
from .policy import (POLICIES, Policy, fused_psum,  # noqa: F401
                     get_policy, register_policy, two_sum)
from .program import (BlockProgram, BlockStage,  # noqa: F401
                      block_contrib, plan_program)

# Make the module itself callable so ``repro.reduce(values, ...)`` is the
# front door, while ``repro.reduce.ReduceSpec`` etc. keep working.
import sys as _sys


class _CallableModule(_sys.modules[__name__].__class__):
    def __call__(self, *args, **kwargs):
        return reduce(*args, **kwargs)


_sys.modules[__name__].__class__ = _CallableModule

__all__ = [
    "reduce", "ReduceSpec", "ReduceStatus", "OUT_OF_RANGE_LABEL",
    "Policy", "POLICIES", "register_policy", "get_policy", "two_sum",
    "fused_psum",
    "ReduceOp", "REDUCE_OPS", "register_op", "get_op",
    "poly_weights", "fir_weights", "cascade_weights",
    "cascade_poly_coeffs",
    "BlockProgram", "BlockStage", "plan_program", "block_contrib",
    "Backend", "BACKENDS", "register_backend", "get_backend",
    "select_backend", "select_local_backend", "mask_out_of_range",
    "ambient_mesh", "default_mesh",
    "Accumulator", "TreeAccumulator", "FlashAccumulator",
    "CascadeAccumulator",
    "scan_accumulate", "merge_tree", "merge_across",
    "accumulate_microbatch_grads", "reduce_microbatch_grads",
    "collective_mean", "collective_mean_tree", "COLLECTIVE_POLICIES",
    "collective_weighted_mean", "collective_moments",
    "merge_carry_across", "elastic_reduce_mean",
]
