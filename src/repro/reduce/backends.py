"""Backend registry for ``repro.reduce`` — one schedule, four executors.

Every backend runs the *same* fixed block schedule (the JugglePAC pairing
contract): the (N, D) stream is padded to row blocks with
``OUT_OF_RANGE_LABEL``, each block contributes a one-hot matmul
``contrib = onehot(ids).T @ vals`` (the MXU form of "pair everything in
this block by label"), and blocks fold into the policy carry strictly in
stream order.  Because the schedule — not the executor — defines the
addition order, results are bitwise identical across backends:

  * ``ref``       — unrolled Python loop over blocks; the readable oracle
                    of the schedule (not of the math — that is
                    ``core.segmented.segment_sum_ref``).
  * ``blocked``   — ``lax.scan`` over blocks; jit-friendly, the CPU/GPU
                    default.
  * ``pallas``    — the TPU kernel (interpret mode off-TPU), with the VMEM
                    accumulator budget enforced by label-space tiling —
                    "2–8 PIS registers, not a BRAM".
  * ``shard_map`` — the multi-device executor: whole blocks of the same
                    schedule split across a device mesh, each shard runs a
                    local backend over its blocks, and the per-shard policy
                    carries merge with the policy's own combiner
                    (``merge_carry_across`` -> ``Policy.merge_across``)
                    before one finalize.  Integer carry components merge
                    by associative int32 psum — bitwise identical to the
                    single-device schedule *at any shard count* (every
                    carry component of exact / exact2 / procrastinate,
                    exact2's residual included since its digit redesign);
                    float carry state (fast/compensated carries) keeps
                    documented tolerance via an order-pinned fold instead
                    (see docs/architecture.md and docs/robustness.md).

New executors (GPU pallas, ...) drop in with ``@register_backend``; the
supported-policies capability set gates both explicit selection and
``select_backend``'s auto choice, and ``distributed=True`` marks executors
that take the mesh/axis plumbing.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, FrozenSet, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .policy import Policy
from .program import BlockProgram, block_contrib, plan_program  # noqa: F401

#: The one padding sentinel for every reduction entry point in this repo.
#: Negative => never equal to a real label in [0, num_segments), so one-hot
#: comparisons drop padded rows for free; scatter paths must mask it
#: explicitly (negative indices wrap in JAX) — see ``mask_out_of_range``.
OUT_OF_RANGE_LABEL: int = -1

BACKENDS: Dict[str, "Backend"] = {}


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered executor of the block schedule.

    ``run(values, ids, num_segments, policy=..., block_size=...,
    interpret=...)`` returns the policy carry tuple of (num_segments, D)
    arrays, *not yet finalized*.  By default it receives domain-prepared
    (N, D) values (f32 or int32 — ``Policy.prepare`` already ran).  A
    staged executor also takes ``to_domain=``/``prep_state=``: then the
    values arrive raw, and each schedule block is masked (dropped rows
    zeroed), mapped with ``to_domain(block, *prep_state)`` and folded in
    one step, so the domain is never built for the whole stream.
    """

    name: str
    run: Callable
    policies: FrozenSet[str]          # capability: policies it can execute
    description: str = ""
    #: distributed executors additionally accept ``mesh=``/``axis_names=``
    #: (threaded by ``reduce`` from its own kwargs or the ambient mesh)
    distributed: bool = False
    #: staged executors additionally accept ``program=`` (a planned
    #: ``BlockProgram``: contrib mode + stage cost hints) and
    #: ``to_domain=``/``prep_state=``, so the domain map runs per block
    #: (and, under shard_map, per shard).  Off by default so pre-staged
    #: custom backends keep their old ``run`` signature.
    staged: bool = False
    #: whether ``contrib="auto"`` may plan the lane-parallel scatter form
    #: for this executor (the compiled pallas kernel runs only the dot)
    plans_lanes: bool = True

    def supports(self, policy: Policy) -> bool:
        return "*" in self.policies or policy.name in self.policies


def register_backend(name: str, *, policies, description: str = "",
                     distributed: bool = False, staged: bool = False,
                     plans_lanes: bool = True):
    """Decorator: register ``fn`` as backend ``name``.

    ``policies``: iterable of policy names the executor implements, or the
    string "*" for schedule-generic executors that thread any policy carry.
    ``distributed=True`` marks executors that want the mesh plumbing
    (``run`` then also receives ``mesh=`` and ``axis_names=``).

    >>> import jax.numpy as jnp
    >>> import repro
    >>> @register_backend("doubled_demo", policies=("fast",),
    ...                   description="blocked, then doubled (demo)")
    ... def _run_doubled(values, ids, n, *, policy, block_size=512,
    ...                  interpret=None):
    ...     carry = get_backend("blocked").run(
    ...         values, ids, n, policy=policy, block_size=block_size)
    ...     return tuple(2 * c for c in carry)
    >>> float(repro.reduce(jnp.arange(4.0), backend="doubled_demo"))
    12.0
    >>> del BACKENDS["doubled_demo"]          # keep the registry clean
    """
    def deco(fn):
        if isinstance(policies, str):
            if policies != "*":
                raise ValueError(
                    f"register_backend({name!r}): policies must be an "
                    f"iterable of policy names or the string '*', got "
                    f"{policies!r} (did you mean ({policies!r},)?)")
            caps = frozenset({"*"})
        else:
            caps = frozenset(policies)
        BACKENDS[name] = Backend(name=name, run=fn, policies=caps,
                                 description=description,
                                 distributed=distributed, staged=staged,
                                 plans_lanes=plans_lanes)
        return fn
    return deco


def get_backend(name: str) -> Backend:
    try:
        return BACKENDS[name]
    except KeyError:
        raise ValueError(f"unknown backend {name!r}; registered: "
                         f"{sorted(BACKENDS)}") from None


def ambient_mesh() -> Optional[Mesh]:
    """The mesh of an enclosing ``with jax.set_mesh(mesh):`` context, or
    None.

    The ``shard_map`` backend and ``select_backend`` both consult this so
    ``repro.reduce(...)`` scales out without explicit plumbing whenever the
    caller already activated a mesh.  Resolution happens *before* the jit
    boundary (in ``reduce``), so the dispatch cache keys on the concrete
    mesh, never on mutable thread state.  JAX hands out the concrete mesh
    only outside traced code: inside ``jit`` under ``jax.set_mesh`` this
    raises, and the caller passes ``mesh=`` instead.
    """
    if jax.sharding.get_abstract_mesh().empty:
        return None
    return jax.sharding.get_mesh()


def default_mesh() -> Mesh:
    """One flat 'shards' axis over every visible device."""
    return Mesh(np.asarray(jax.devices()), ("shards",))


def select_backend(policy: Policy, mesh: Optional[Mesh] = None, *,
                   traced: bool = False) -> Backend:
    """Auto-selection: shard_map under a multi-device mesh, the TPU kernel
    on TPU, the scanned form elsewhere.

    A mesh (explicit, or — unless the call is ``traced`` — the ambient
    ``jax.set_mesh`` context) spanning more than one device selects the
    ``shard_map`` backend, which shards the stream and runs the local
    auto-choice per shard.  The pallas wrapper already
    tiles the label space to its VMEM budget, so accumulator size never
    disqualifies it; off-TPU the kernel runs in interpret mode (a
    validation path, not a fast path), so ``blocked`` is the performance
    default.
    """
    if mesh is None and not traced:
        # Honor the ambient mesh only for calls on concrete arrays:
        # reduce() is also called from inside jit/shard_map-traced model
        # code (MoE combine, serving means), where auto-escalating to a
        # nested shard_map would be wrong.  An explicit mesh= always wins.
        mesh = ambient_mesh()
    if mesh is not None and mesh.size > 1:
        cand = get_backend("shard_map")
        if cand.supports(policy):
            return cand
    return select_local_backend(policy)


def interpret_default() -> bool:
    """Pallas interpret mode off-TPU; compiled Mosaic kernels on TPU.
    The one resolution every kernel wrapper shares."""
    return jax.default_backend() != "tpu"


def select_local_backend(policy: Policy) -> Backend:
    """The single-device auto-choice (also each shard_map shard's inner
    executor): pallas on TPU when capable, blocked otherwise."""
    if jax.default_backend() == "tpu":
        cand = get_backend("pallas")
        if cand.supports(policy):
            return cand
    return get_backend("blocked")


# ---------------------------------------------------------------------------
# Shared schedule helpers
# ---------------------------------------------------------------------------


def mask_out_of_range(segment_ids: jnp.ndarray,
                      num_segments: int) -> jnp.ndarray:
    """Map every label outside [0, num_segments) to OUT_OF_RANGE_LABEL."""
    ids = segment_ids.astype(jnp.int32)
    ok = (ids >= 0) & (ids < num_segments)
    return jnp.where(ok, ids, jnp.int32(OUT_OF_RANGE_LABEL))


def _pad_to_blocks(values, segment_ids, block_size):
    """Pad N to a multiple of block_size; padded rows carry the sentinel."""
    n, d = values.shape
    pad = (-n) % block_size
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        segment_ids = jnp.pad(segment_ids, (0, pad),
                              constant_values=OUT_OF_RANGE_LABEL)
    nb = (n + pad) // block_size
    return (values.reshape(nb, block_size, d),
            segment_ids.reshape(nb, block_size).astype(jnp.int32), nb)


def map_block(vals, ids, to_domain, prep_state):
    """The per-block domain map of the staged executors: zero the
    block's dropped rows (a sentinel row's payload — NaN, Inf, 1e38 —
    must never reach ``to_domain``), then map the block.  The identity
    when ``to_domain`` is None (values already in the domain)."""
    if to_domain is None:
        return vals
    kept = (ids >= 0).reshape(-1, 1)
    return to_domain(jnp.where(kept, vals, jnp.zeros((), vals.dtype)),
                     *prep_state)


def carry_width(policy: Policy, d: int, to_domain) -> int:
    """Domain width the carry is built for: raw rows map to
    ``policy.domain_width(d)`` columns."""
    return d if to_domain is None else policy.domain_width(d)


def _block_contrib(vals, ids, num_segments, policy, program=None):
    """One gather stage for one (B, W) block — the staged program's
    contrib step, shared verbatim with the pallas kernel body
    (``repro.reduce.program.block_contrib``), so every backend lowers to
    the same dot(s) / lane scatter and the cross-backend bitwise contract
    holds per (policy, program)."""
    return block_contrib(vals, ids, num_segments, policy, program)


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------


@register_backend("ref", policies="*", staged=True,
                  description="unrolled Python loop over blocks; the "
                              "readable schedule oracle")
def _run_ref(values, segment_ids, num_segments, *, policy: Policy,
             block_size: int = 512, interpret: Optional[bool] = None,
             program: Optional[BlockProgram] = None,
             to_domain=None, prep_state=()):
    vb, ib, nb = _pad_to_blocks(values, segment_ids, block_size)
    carry = policy.init(num_segments,
                        carry_width(policy, values.shape[1], to_domain))
    for b in range(nb):
        vals = map_block(vb[b], ib[b], to_domain, prep_state)
        contrib = _block_contrib(vals, ib[b], num_segments, policy,
                                 program)
        carry = policy.update(carry, contrib)
        # pin the block boundary: without it XLA may fuse the unrolled
        # blocks and reassociate degenerate (S=1) dots, breaking the
        # bitwise-equal-to-scan contract the scheduled backends share.
        carry = jax.lax.optimization_barrier(carry)
    return carry


@register_backend("blocked", policies="*", staged=True,
                  description="lax.scan over blocks; jit-friendly "
                              "CPU/GPU default")
def _run_blocked(values, segment_ids, num_segments, *, policy: Policy,
                 block_size: int = 512, interpret: Optional[bool] = None,
                 program: Optional[BlockProgram] = None,
                 to_domain=None, prep_state=()):
    vb, ib, nb = _pad_to_blocks(values, segment_ids, block_size)

    def step(carry, blk):
        vals, ids = blk
        vals = map_block(vals, ids, to_domain, prep_state)
        contrib = _block_contrib(vals, ids, num_segments, policy, program)
        return policy.update(carry, contrib), None

    carry0 = policy.init(num_segments,
                         carry_width(policy, values.shape[1], to_domain))
    carry, _ = jax.lax.scan(step, carry0, (vb, ib))
    return carry


@register_backend("pallas", policies=("fast", "compensated", "exact",
                                      "exact2", "procrastinate"),
                  staged=True, plans_lanes=False,
                  description="TPU Pallas kernel (interpret off-TPU), "
                              "double-buffered multi-block grid, "
                              "VMEM-budget label-space tiling")
def _run_pallas(values, segment_ids, num_segments, *, policy: Policy,
                block_size: int = 512, interpret: Optional[bool] = None,
                program: Optional[BlockProgram] = None,
                blocks_per_step: Optional[int] = None,
                to_domain=None, prep_state=()):
    from repro.kernels import jugglepac_segsum as _ss
    from repro.kernels.ops import seg_tile_for
    if interpret is None:
        interpret = interpret_default()
    d = values.shape[1]
    if to_domain is None:
        # same padding contract as every backend, flattened back for the
        # grid (raw rows are not copied: the kernel masks their tail)
        vb, ib, _ = _pad_to_blocks(values, segment_ids, block_size)
        values = vb.reshape(-1, d)
        segment_ids = ib.reshape(-1)
    # VMEM-budget label tiling, shared with kernels.ops.segment_sum
    seg_tile = seg_tile_for(num_segments,
                            carry_width(policy, d, to_domain),
                            policy.carry_len)
    parts = []
    for off in range(0, num_segments, seg_tile):
        s = min(seg_tile, num_segments - off)
        parts.append(_ss.segsum_policy_pallas(
            values, segment_ids, s, policy=policy,
            block_rows=block_size, seg_offset=off, interpret=interpret,
            program=program, blocks_per_step=blocks_per_step,
            to_domain=to_domain, prep_state=prep_state))
    if len(parts) == 1:
        return parts[0]
    return tuple(jnp.concatenate([p[i] for p in parts], axis=0)
                 for i in range(policy.carry_len))


@register_backend("shard_map", policies="*", distributed=True, staged=True,
                  description="multi-device: whole schedule blocks per "
                              "shard, per-shard domain prep, carries "
                              "merged with one fused collective per "
                              "carry dtype")
def _run_shard_map(values, segment_ids, num_segments, *, policy: Policy,
                   block_size: int = 512, interpret: Optional[bool] = None,
                   mesh: Optional[Mesh] = None, axis_names=None,
                   program: Optional[BlockProgram] = None,
                   to_domain=None, prep_state=()):
    """Split the block schedule across a device mesh.

    The (N, D) stream pads to ``nshards * block_size`` granularity with
    ``OUT_OF_RANGE_LABEL`` rows (sentinel blocks contribute the policy
    identity, so uneven N costs nothing but the padding), so every shard
    receives *whole, contiguous* schedule blocks.  Each shard folds its
    blocks with the local auto-backend — the identical kernel body the
    single-device path runs — and the per-shard carries merge via
    ``collective.merge_carry_across`` with the policy's combiner (one
    fused batched psum per carry dtype for the add-mergeable tiers).
    One finalize happens on the merged carry, outside this function,
    exactly as on every other backend.

    ``to_domain`` moves the domain map *inside* the shards: when given
    (the path ``reduce`` drives for the integer tiers), ``values`` arrive
    raw and each shard's local executor maps its own blocks into the
    policy domain — ``to_domain(block, *prep_state)`` with
    ``prep_state`` the globally-computed, replicated scalars of the
    finalize context (quantization scale / window anchor).
    ``Policy.map_rows`` is row-local by contract, so the per-block map
    is bit-identical to slicing a whole-stream domain — zero bits change
    — while the digitization scales with the shard count, and only the
    narrow raw rows cross the sharding boundary, not the widened domain
    planes.  ``to_domain=None`` keeps the legacy contract: ``values``
    already domain-prepared (direct ``backend.run`` callers).

    Invariant: integer carry state is bitwise identical to the
    single-device schedule at any shard count, because the quantization
    scale / window anchor is one global constant (computed before
    sharding, on the full masked stream) and integer carry addition is
    associative — that is the whole result for ``exact``,
    ``procrastinate``, *and* ``exact2`` (whose residual travels as
    exponent-indexed int32 digits, so even its finalized float is bitwise
    at any shard count, mesh shape, or device permutation — the elastic
    guarantee in docs/robustness.md).  The float tiers (fast /
    compensated) change their cross-shard combine order with the shard
    count — documented tolerance, not bitwise.
    """
    # deferred: collective imports this module's sentinel at load time
    from .collective import merge_carry_across
    if mesh is None:
        mesh = ambient_mesh() or default_mesh()
    axes = tuple(axis_names) if axis_names else tuple(mesh.axis_names)
    unknown = [a for a in axes if a not in mesh.axis_names]
    if unknown:
        raise ValueError(f"shard_map backend: axis_names {unknown} not in "
                         f"mesh axes {mesh.axis_names}")
    nshards = int(np.prod([mesh.shape[a] for a in axes]))
    inner = select_local_backend(policy)       # staged: pallas | blocked

    n, d = values.shape
    pad = (-n) % (nshards * block_size)
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        segment_ids = jnp.pad(segment_ids, (0, pad),
                              constant_values=OUT_OF_RANGE_LABEL)

    prep_state = tuple(prep_state)

    def shard_body(v, ids, *prep):
        carry = inner.run(v, ids, num_segments, policy=policy,
                          block_size=block_size, interpret=interpret,
                          program=program, to_domain=to_domain,
                          prep_state=prep)
        # the merge issues immediately after the local fold, with no
        # barrier in between: one fused collective per carry dtype, free
        # to overlap the tail of the last block's update on hardware
        # with async collectives
        return merge_carry_across(policy, carry, axes)

    row_spec = axes if len(axes) > 1 else axes[0]
    return jax.shard_map(shard_body, mesh=mesh,
                         in_specs=(P(row_spec, None), P(row_spec))
                         + (P(),) * len(prep_state),
                         out_specs=P(), check_vma=False)(
                             values, segment_ids.astype(jnp.int32),
                             *prep_state)
