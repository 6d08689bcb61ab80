"""Accuracy policies — the first-class knob of ``repro.reduce``.

JugglePAC's fixed-pairing argument says *what order* additions happen in;
the policy says *in what domain* they happen.  (A third layer, the
reduction algebra of ``algebra.py``, says *what is being summed*: ops
like ``weighted_sum``/``moments`` transform rows *before* ``prepare``
sees them, so the integer tiers quantize — and therefore weight — in
their own exact domain, and an op's extra components simply widen the
``domain_width`` every policy already parameterizes over.)  Five tiers,
all sharing the same block schedule (so a policy swap never changes the
data movement):

  * ``fast``          — plain f32 accumulation over the fixed block tree.
    Deterministic (the schedule depends only on shapes), O(log n) error
    growth, zero overhead.
  * ``compensated``   — Kahan/two-sum carried across blocks: the (S, D)
    accumulator travels with an equally-shaped compensation term that
    captures every cross-block rounding error.  ~f64 accuracy at f32 cost.
  * ``exact``         — INTAC: quantize once to a shared power-of-two scale,
    accumulate in int32 (associative => bitwise identical for *any* block
    size, backend, or device layout), dequantize once per reduction — the
    paper's "pay for normalization once per set".  The scale is sized so
    the *whole stream* fits single-limb int32 headroom, so resolution
    shrinks as 1/N: cheap state, but long streams lose precision.
  * ``exact2``        — three-limb all-integer carry-save: the per-block
    contribution splits into (hi, lo) limbs — headroom from the second
    limb instead of the scale — while the third limb carries the
    exactly-captured quantization residual
    ``x - descale(quantize(x, scale), scale)`` as per-element integer
    digit bins (a small superaccumulator, Neal arXiv 1505.05571, at the
    quantum-anchored ``intac.RES_BIN_BITS`` window).  Every carry
    component is an associatively-added int32 array, so the *finalized
    float* — not just the limbs — is bitwise invariant across block
    size, backend, shard count, mesh shape, and input permutation, and
    within 1 ulp of the f64 reference for *arbitrary* f32 inputs at any
    stream length up to 2^24 rows.
  * ``procrastinate`` — exponent-indexed bins after Liguori (arXiv
    2406.05866) / Neal (arXiv 1505.05571): each f32 value splits exactly
    into per-exponent-window integer digits, bins accumulate in int32,
    and *all* rounding procrastinates to one carry-resolve + compensated
    combine in ``finalize``.  Exact to <=1 ulp of the f32 result for any
    stream up to 2^22 rows whose result lands within ~2^24 of the
    largest |value| (the 48-bit window truncates below that, so under
    catastrophic cancellation the bound is absolute — N * 2^-49 of the
    max — not relative), at NUM_BINS x the accumulator state.

The integer tiers are bitwise order-independent end to end: any block
size, backend, input permutation, or device layout produces identical
bits for the ``exact``, ``exact2``, and ``procrastinate`` *results* (all
of their carry state is associatively-added int32, canonicalized once at
finalize).  The integer tiers also carry saturation guard rails: carry
updates run through ``intac.wrap_add`` and pool wrap events into an
overflow counter surfaced via ``carry_status`` (the
``ReduceStatus.saturated`` flag of ``reduce(..., with_status=True)``) —
within the documented ``max_block_size``/``max_blocks``/``max_terms``
bounds the flags provably cannot trip; they are the defense-in-depth
layer for direct ``backend.run`` callers and future tiers.

A policy declares a *staged block-program*, each hook pure and
shape-polymorphic:

  ``prepare_ctx(max_abs, num_terms)`` -> ctx: the finalize context as a
                                         pure function of global stream
                                         statistics (quantization scale,
                                         exponent-window anchor) — shards
                                         that agree on the stats agree on
                                         the grid
  ``to_domain(values, ctx)``          -> elementwise map of raw (N, D)
                                         rows into the accumulation
                                         domain; runs *per shard* on the
                                         distributed path (the stream
                                         never materializes its domain
                                         form on one device)
  ``prepare(values, num_terms)``      -> (domain_values, ctx): the
                                         single-device composition of the
                                         two stages above
  ``contrib(onehot, vals)``           -> the gather stage, dot form: the
                                         (S, W) one-hot matmul(s) mapping
                                         a (B, W) domain block into what
                                         ``update`` folds (integer domains
                                         through the exact bf16 plane dot,
                                         ``int_plane_dot``)
  ``contrib_lanes(ids, vals, S)``     -> the gather stage, lane form:
                                         PhasedAccu-style per-lane
                                         scatter-add partial sums folded
                                         in lane order — bitwise equal to
                                         the dot for integer domains
                                         (associativity), a different
                                         rounding order for float ones
  ``init / update``                   -> the carry-update stage (a tuple
                                         of ``carry_len`` arrays all
                                         backends thread identically; the
                                         pallas kernel executes the
                                         gather + update stages inside
                                         its grid loop)
  ``stage_costs(...)``                -> declared per-block byte/flop
                                         hints for the gather (memory-
                                         bound) and update (compute-
                                         bound) stages, consumed by
                                         ``plan_program`` and the
                                         roofline tooling
  ``merge(a, b)``                     -> combine two partial carries
                                         (cross-shard / cross-device); the
                                         combiner the ``shard_map`` backend
                                         folds with (``merge_across`` lifts
                                         it to named-axis collectives,
                                         fusing same-dtype components into
                                         one batched psum)
  ``finalize(carry, ctx)``            -> (S, D) f32

New tiers register with ``@register_policy`` and immediately work on every
schedule-generic backend (``ref``/``blocked``); the ``pallas`` backend
advertises the policies its kernel has been validated for via its
capability flags.  ``update`` must be pure elementwise/jnp ops (it is
traced into the kernel body) and ``init`` must be zeros.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# Direct submodule import (not ``from repro.core import ...``): this
# module loads while repro.core's __init__ may still be mid-execution
# (core.segmented -> reduce.backends -> here), and intac itself imports
# nothing from repro, so the submodule path always resolves.
import repro.core.intac as intac
from repro.core.intac import (choose_scale, dequantize, quantize,  # noqa: F401
                              two_sum)

POLICIES: Dict[str, "Policy"] = {}

#: lanes the generic lane-parallel contrib splits a block into (the
#: PhasedAccu phase count; each lane owns a contiguous row slice)
LANES_DEFAULT = 4

#: bits per plane of the exact integer dot: a plane value |p| <= 2^8 is
#: exact in bf16, so a one-hot column sum over B rows stays <= B * 2^8
PLANE_BITS = 8
#: the longest block the plane dot covers exactly: its f32 column sums
#: stay <= 2^24, where every integer is representable
MAX_PLANE_DOT_ROWS = 1 << (24 - PLANE_BITS)


def int_plane_dot(onehot: jnp.ndarray, parts) -> jnp.ndarray:
    """The exact one-hot dot of integer columns through bf16 planes.

    ``onehot`` is the (B, S) boolean one-hot; ``parts`` is a sequence of
    ``(values, bits)`` with ``values`` a (B, W_i) array of integers and
    ``|values| <= 2^bits``.  Each part splits into ``ceil(bits / 8)``
    planes — unsigned low bytes and one signed top plane, all exact in
    bf16 — and all planes run as one bf16 dot accumulated in f32, whose
    column sums stay below 2^24 and are therefore exact in any order.
    The planes then shift-add in int32, which wraps exactly as an int32
    dot would.  Returns (S, sum W_i) int32, bit for bit the int32 dot on
    every backend — TPU matrix units take no int32 x int32 product.
    """
    b = onehot.shape[0]
    if b > MAX_PLANE_DOT_ROWS:
        raise ValueError(f"int_plane_dot: {b}-row blocks exceed the exact "
                         f"f32 plane-sum bound ({MAX_PLANE_DOT_ROWS} rows)")
    planes, layout = [], []
    for vals, bits in parts:
        v = vals.astype(jnp.int32)
        n = max(1, -(-int(bits) // PLANE_BITS))
        for k in range(n):
            p = jnp.right_shift(v, k * PLANE_BITS)
            if k + 1 < n:
                p = jnp.bitwise_and(p, (1 << PLANE_BITS) - 1)
            planes.append(p.astype(jnp.float32).astype(jnp.bfloat16))
        layout.append((v.shape[1], n))
    x = planes[0] if len(planes) == 1 else jnp.concatenate(planes, axis=1)
    oh = onehot.astype(jnp.float32).astype(jnp.bfloat16).T
    sums = jnp.dot(oh, x, preferred_element_type=jnp.float32
                   ).astype(jnp.int32)
    out, off = [], 0
    for w, n in layout:
        acc = sums[:, off:off + w]
        # detlint: ok[DET002] int32 plane shift-adds: associative, exact
        # (wrap mod 2^32 like the int32 dot they replace)
        for k in range(1, n):
            lo = off + k * w
            acc = acc + jnp.left_shift(sums[:, lo:lo + w], k * PLANE_BITS)
        out.append(acc)
        off += n * w
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def fused_psum(arrays, axis_names):
    """One batched ``psum`` per dtype instead of one per array.

    Components of the same dtype ravel-concatenate, reduce in a single
    collective, and split back.  ``psum`` is elementwise, so the fused
    form is bitwise identical to per-component psums — it only collapses
    k collective launches (exact2's four carry components, a gradient
    pytree's many leaves) into one per dtype, which is what keeps the
    shard_map merge off the scaling-critical path.
    """
    arrays = tuple(arrays)
    axes = tuple(axis_names)
    by_dtype: Dict = {}
    for i, a in enumerate(arrays):
        by_dtype.setdefault(jnp.dtype(a.dtype), []).append(i)
    out = [None] * len(arrays)
    for idxs in by_dtype.values():
        if len(idxs) == 1:
            i = idxs[0]
            out[i] = jax.lax.psum(arrays[i], axes)
            continue
        flat = jnp.concatenate([arrays[i].ravel() for i in idxs])
        summed = jax.lax.psum(flat, axes)
        off = 0
        for i in idxs:
            size = arrays[i].size
            out[i] = summed[off:off + size].reshape(arrays[i].shape)
            off += size
    return tuple(out)


def register_policy(cls):
    """Class decorator: instantiate and add to the policy registry.

    The new tier immediately works on every schedule-generic backend
    (``ref``/``blocked``/``shard_map``) — only ``pallas`` gates on its
    validated capability set.

    >>> import jax.numpy as jnp
    >>> import repro
    >>> @register_policy
    ... class _NegatedPolicy(Policy):
    ...     '''Toy tier: accumulate in f32, negate once at finalize.'''
    ...     name = "negated_demo"
    ...     def finalize(self, carry, ctx):
    ...         return -carry[0]
    >>> float(repro.reduce(jnp.arange(4.0), policy="negated_demo"))
    -6.0
    >>> del POLICIES["negated_demo"]          # keep the registry clean
    """
    inst = cls()
    POLICIES[inst.name] = inst
    return cls


def get_policy(name: str) -> "Policy":
    """Look up a registered policy instance by name.

    >>> get_policy("exact2").carry_len
    4
    >>> get_policy("psychic")
    Traceback (most recent call last):
        ...
    ValueError: unknown policy 'psychic'; registered: ['compensated', \
'exact', 'exact2', 'fast', 'procrastinate']
    """
    try:
        return POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown policy {name!r}; registered: "
                         f"{sorted(POLICIES)}") from None


class Policy:
    """Base accuracy policy.  Subclasses set ``name`` and override hooks."""

    name: str = "?"
    #: number of carry arrays threaded through the block schedule
    carry_len: int = 1
    #: dtype the backends accumulate in (drives kernel specialization)
    acc_dtype = jnp.float32
    #: integer domains: every domain value satisfies |v| <= 2^domain_bits
    #: (sizes the planes of the exact dot, ``int_plane_dot``)
    domain_bits: int = 31
    #: largest schedule block the policy's headroom analysis covers
    #: (None = any); ``reduce`` validates ``block_size`` against it
    max_block_size: Optional[int] = None
    #: largest block *count* the per-block carry headroom covers (None =
    #: any); ``reduce`` validates ceil(n / block_size) against it
    max_blocks: Optional[int] = None
    #: largest total row count the carry headroom covers (None = any);
    #: ``prepare`` raises past it, and ``reduce(..., on_overflow=
    #: "degrade")`` chunks the stream at this bound instead
    max_terms: Optional[int] = None
    #: the next-stronger tier ``reduce(..., on_overflow="degrade")``
    #: re-runs through when this tier reports saturation (None = no
    #: stronger tier; saturation then raises)
    escalation: Optional[str] = None
    #: True when ``merge`` is plain elementwise addition, so a cross-device
    #: carry merge may lower to one batched ``lax.psum`` per carry *dtype*
    #: (the integer tiers: associative, any reduction topology gives the
    #: same bits).  False forces the gathered in-order fold (compensated:
    #: its two-sum merge is order-sensitive, so the fold order is pinned).
    merge_is_add: bool = True
    #: True when ``prepare_ctx`` consumes the stream's max-|value|
    #: statistic (the integer tiers size their scale / window anchor from
    #: it); False lets ``prepare`` skip the max-reduce entirely.
    needs_max_stat: bool = False
    #: rough elementwise-op count of one ``update`` per carry element —
    #: the compute-stage weight in ``stage_costs`` (fast: one add;
    #: compensated: a two_sum; the integer tiers: limb/bin wrap_adds).
    update_ops_per_elem: int = 1

    @property
    def carry_dtypes(self) -> Tuple:
        """dtype of each carry component; uniform ``acc_dtype`` unless a
        policy overrides it."""
        return (self.acc_dtype,) * self.carry_len

    def domain_width(self, d: int) -> int:
        """Column count of the accumulation domain for raw width ``d``
        (exact2/procrastinate widen by their digit-plane count)."""
        return d

    def prepare_ctx(self, max_abs, num_terms: int):
        """Stage 0a: global statistics -> the finalize context.

        A pure function of the stream's max-|value| statistic (``None``
        unless ``needs_max_stat``) and the static row count, so any two
        executors handed the same statistics build the identical context
        — the property that lets the shard_map backend run ``to_domain``
        per shard against one globally-computed ctx and stay bitwise.
        Eagerly raises on streams beyond the tier's headroom bounds.
        """
        return None

    def domain_args(self, ctx) -> Tuple:
        """Stage 0a': the ctx as the f32 scalars ``map_rows`` reads.

        Whatever needs ``log2``, ``frexp`` or ``ldexp`` (an exponent, the
        exact power-of-two factors of a descale) is computed here, once
        per call and outside any kernel, so the row map itself lowers in
        a kernel body."""
        return ()

    def map_rows(self, values: jnp.ndarray, *args):
        """Stage 0b: elementwise map of raw (N, D) rows into the
        accumulation domain, given ``domain_args(ctx)``.

        Row-local by contract (no cross-row reductions), so an executor
        may apply it to any row slice — a shard, one schedule block in
        VMEM — and get that slice of the whole-stream map, bit for bit.
        Only multiplies by exact powers of two, round-half-even, integer
        converts, shifts and concatenation, so the pallas kernel runs it
        per block.  The domain may be wider than (N, D) — e.g.
        per-element digit splits — as long as ``finalize`` maps the
        carry back to (S, D).
        """
        return values.astype(jnp.float32)

    def to_domain(self, values: jnp.ndarray, ctx):
        """Stage 0b under a fixed ``ctx``: ``map_rows`` of
        ``domain_args(ctx)``, so the whole-stream map and every
        executor's per-block map are one function."""
        return self.map_rows(values, *self.domain_args(ctx))

    def prepare(self, values: jnp.ndarray, num_terms: int, *,
                shared_max=None):
        """Map raw (N, D) values into the accumulation domain.

        Returns (domain_values, ctx); ctx is passed back to ``finalize``.
        The single-device composition of the two staged hooks:
        ``prepare_ctx`` (global statistics -> ctx) then ``to_domain``
        (elementwise).  ``shared_max`` overrides the local max-|value|
        statistic the integer tiers size their scale / window anchor
        from — collectives (``elastic_reduce_mean``) pass a pmax-shared
        global so every shard prepares on the identical grid.
        """
        v = values.astype(jnp.float32)
        m = None
        if self.needs_max_stat:
            m = jnp.max(jnp.abs(v)) if shared_max is None else shared_max
        ctx = self.prepare_ctx(m, num_terms)
        return self.to_domain(v, ctx), ctx

    def contrib(self, onehot: jnp.ndarray, vals: jnp.ndarray):
        """One schedule step: map a (B, S) boolean one-hot and a (B, W)
        domain block to the contribution ``update`` folds.

        Every backend (and the pallas kernel body) builds the same boolean
        one-hot and delegates here, so the dot lowering — and with it the
        cross-backend bitwise contract — is defined once, by the policy.
        Integer domains run the exact bf16 plane dot (``int_plane_dot``,
        values bounded by ``domain_bits``); float domains an f32 dot at
        full precision (a TPU's default f32 matmul rounds its inputs to
        bf16).
        """
        if jnp.issubdtype(self.acc_dtype, jnp.integer):
            return int_plane_dot(onehot, [(vals, self.domain_bits)])
        return jnp.dot(onehot.astype(vals.dtype).T, vals,
                       preferred_element_type=self.acc_dtype,
                       precision=jax.lax.Precision.HIGHEST)

    def contrib_lanes(self, ids: jnp.ndarray, vals: jnp.ndarray,
                      num_segments: int, *, seg_offset: int = 0,
                      lanes: int = LANES_DEFAULT):
        """The gather stage in lane form: segment-local per-lane partial
        sums (artiq ``PhasedAccu``), folded strictly in lane order.

        The block's rows split into ``lanes`` contiguous slices; each lane
        scatter-adds its rows into its own (S+1, W) partial (sentinel /
        out-of-tile labels park on the scratch row), and the partials fold
        lane 0 -> lane ``lanes-1``.  Per segment this is the same multiset
        of additions as the one-hot dot, so for integer ``acc_dtype`` the
        result is **bitwise equal** to ``contrib`` (integer addition is
        associative) while skipping the (B, S, W) dot flops — the win when
        the matmul is memory-bound (large S).  For float domains it is a
        *different rounding order* (like the shard_map fast merge):
        explicit opt-in only, never auto-selected.
        """
        b = ids.shape[0]
        v = vals.astype(self.acc_dtype)
        local = ids.reshape(b) - seg_offset
        safe = jnp.where((local >= 0) & (local < num_segments),
                         local, num_segments)
        nl = max(1, min(int(lanes), b))
        bounds = [(k * b) // nl for k in range(nl + 1)]
        total = None
        # detlint: ok[DET002] lane partials: integer domains add
        # associatively (exact); float lanes are the fast tier's
        # documented tolerance (docs/policies.md)
        for k in range(nl):
            lo, hi = bounds[k], bounds[k + 1]
            part = jnp.zeros((num_segments + 1, v.shape[1]),
                             self.acc_dtype).at[safe[lo:hi]].add(
                                 v[lo:hi], mode="drop")
            total = part if total is None else total + part
        return total[:num_segments]

    def stage_costs(self, block_size: int, domain_width: int,
                    num_segments: int, *, contrib: str = "dot") -> Dict:
        """Declared per-block cost hints for the two schedule stages.

        Returns ``{"contrib": {...}, "update": {...}}`` with ``bytes``,
        ``flops``, and the declared ``bound`` ("memory" for the gather /
        contrib stage, "compute" for the carry update) — what
        ``plan_program`` sizes its contrib-mode crossover from and what
        ``benchmarks/roofline.py`` projects onto the hardware roofline.
        Estimates, not measurements: one multiply-add per dot cell, one
        add per scatter cell, ``update_ops_per_elem`` per carry element.
        """
        b, w, s = block_size, domain_width, num_segments
        acc_bytes = jnp.dtype(self.acc_dtype).itemsize
        in_bytes = b * w * 4 + b * 4              # values tile + ids tile
        if contrib == "lanes":
            gather = {"bytes": float(in_bytes + (s + 1) * w * acc_bytes),
                      "flops": float(b * w), "bound": "memory"}
        else:
            gather = {"bytes": float(in_bytes + s * w * acc_bytes),
                      "flops": float(2.0 * b * s * w), "bound": "memory"}
        update = {"bytes": float(2 * self.carry_len * s * w * acc_bytes),
                  "flops": float(self.update_ops_per_elem
                                 * self.carry_len * s * w),
                  "bound": "compute"}
        return {"contrib": gather, "update": update}

    def init(self, num_segments: int, d: int):
        """Zero carry, one (num_segments, d) array per ``carry_dtypes``
        entry; ``d`` is the *domain* width — policies whose carries are
        narrower than their domain (exact2) override."""
        return tuple(jnp.zeros((num_segments, d), dt)
                     for dt in self.carry_dtypes)

    def update(self, carry, contrib):
        return (carry[0] + contrib,)

    def merge(self, a, b):
        """Combine two partial carries (the cross-shard combiner).

        Semantics: ``merge(run(blocks[:k]), run(blocks[k:]))`` must equal
        ``run(blocks)`` — exactly for the integer tiers, to documented
        tolerance for the float tiers.  The default (elementwise add) is
        correct for every policy whose ``update`` is itself an add into
        the carry; order-sensitive carries override it and clear
        ``merge_is_add``.
        """
        return tuple(x + y for x, y in zip(a, b))

    def merge_across(self, carry, axis_names):
        """Merge per-shard carries across mesh axes (inside shard_map).

        The collective face of ``merge``: when ``merge_is_add``, the
        components reduce with one *fused* associative ``lax.psum`` per
        carry dtype (``fused_psum`` — any reduction topology, same bits as
        per-component psums: the integer-tier contract, at one collective
        launch instead of ``carry_len``); otherwise the carries all-gather
        and fold strictly in device order with ``merge``, pinning the
        combine schedule the way the block schedule pins per-shard order.
        """
        axes = tuple(axis_names)
        if self.merge_is_add:
            return fused_psum(carry, axes)
        gathered = tuple(jax.lax.all_gather(c, axes, axis=0) for c in carry)
        nshards = gathered[0].shape[0]
        merged = tuple(g[0] for g in gathered)
        # detlint: ok[DET002] strict device-order merge is the contract:
        # merge chains are two_sum data-dependent or integer-exact
        for k in range(1, nshards):
            merged = self.merge(merged, tuple(g[k] for g in gathered))
        return merged

    def carry_status(self, carry):
        """Saturation guard rail: a scalar bool (True = some integer
        carry wrapped int32 and the result is not trustworthy), or None
        for tiers with no overflow mode (float carries, or a-priori
        scale sizing like ``exact``).  Cheap and jittable — the flags
        are threaded through the carry by ``update``/``merge``, so
        reading them costs one reduction."""
        return None

    def finalize(self, carry, ctx) -> jnp.ndarray:
        return carry[0]


@register_policy
class FastPolicy(Policy):
    """f32 accumulation over the fixed block tree (the default)."""

    name = "fast"


@register_policy
class CompensatedPolicy(Policy):
    """Kahan/two-sum compensated cross-block accumulation."""

    name = "compensated"
    carry_len = 2
    merge_is_add = False            # two-sum merge is order-sensitive
    update_ops_per_elem = 6         # one two_sum + the compensation add

    def update(self, carry, contrib):
        acc, comp = carry
        s, e = two_sum(acc, contrib)
        return (s, comp + e)

    def merge(self, a, b):
        """Two-sum the partial sums, pool the compensations + the new
        rounding error — the cross-shard analogue of ``update``."""
        s, e = two_sum(a[0], b[0])
        return (s, a[1] + b[1] + e)

    def finalize(self, carry, ctx) -> jnp.ndarray:
        acc, comp = carry
        return acc + comp


@register_policy
class ExactPolicy(Policy):
    """INTAC fixed point: int32 accumulation, one dequantize per reduction.

    ``prepare`` picks a shared power-of-two scale sized so the *entire*
    stream fits int32 headroom (the paper's a-priori bit-width step), so no
    partial sum can overflow anywhere in the schedule.  Integer addition is
    associative — the result is bitwise independent of backend, block size,
    and device layout.  The headroom-from-scale trade means resolution
    shrinks as 1/N; ``exact2``/``procrastinate`` remove that trade.
    """

    name = "exact"
    acc_dtype = jnp.int32
    needs_max_stat = True
    #: at saturation (possible only for direct backend.run misuse — the
    #: scale sizing makes overflow unreachable through ``reduce``), the
    #: two-limb tier removes the headroom-vs-resolution trade entirely
    escalation = "exact2"

    def prepare_ctx(self, max_abs, num_terms: int):
        return choose_scale(max_abs, max(num_terms, 1))

    def domain_args(self, ctx):
        return (ctx,)

    def map_rows(self, values: jnp.ndarray, scale):
        return quantize(values.astype(jnp.float32), scale)

    def finalize(self, carry, ctx) -> jnp.ndarray:
        return dequantize(carry[0], ctx)


@register_policy
class Exact2Policy(Policy):
    """Three-limb all-integer INTAC carry-save: headroom no longer trades
    against resolution, "exact" means exact off the dyadic grid too, and
    the finalized float is bitwise invariant at any topology.

    The scale is sized by magnitude alone (``QBITS`` bits below int32, so
    a 512-row block contribution cannot overflow), each block's int32
    contribution splits into (hi, lo) limbs on the way into the carry,
    and the third limb carries what quantization rounded away: the
    per-element residual, captured exactly and re-split into
    ``intac.RES_NUM_BINS`` integer digits of the quantum-anchored
    ``intac.RES_BIN_BITS`` superaccumulator window (Neal, arXiv
    1505.05571; the same bin machinery as the procrastinate tier), all in
    ``map_rows``.  All three limbs are then associatively-added int32
    state: one exact plane dot per block, up to 2^24 rows carry-free, and
    ``finalize`` is one ``limbs_resolve3_binned`` — a pure function of
    the canonical integer totals and the scale.  This class is the one
    implementation of the tier: ``reduce`` on every backend,
    ``elastic_reduce_mean`` and ``collective_mean`` all run it.

    Guarantee: the finalized float — not merely the hi/lo limbs — is
    bitwise independent of block size, backend, shard count, mesh shape,
    and input order, and within 1 ulp of the f64 reference for arbitrary
    f32 inputs (per-element residual truncation below the 49-bit window
    is <= max|x| * 2^-71 per element).  This is what makes elastic
    resume bit-identical: checkpoint on 2 devices, resume on 8, same
    bits.  Saturation guard rail: carry adds run through
    ``intac.wrap_add`` and pool wrap events into the ``ovf`` carry
    (``carry_status`` / ``ReduceStatus.saturated``) — unreachable within
    the enforced row/block bounds, exact at the int32 edge beyond them.
    """

    name = "exact2"
    #: (hi, lo) int32 limbs + binned int32 residual digits + ovf counter
    carry_len = 4
    acc_dtype = jnp.int32
    #: per-value quantization bits: block contribs stay below int32 for
    #: blocks up to 2^(30-QBITS) = 512 rows
    QBITS = 21
    max_block_size = 1 << (30 - QBITS)
    #: limb headroom: every block adds one lo remainder < 2^15, one hi
    #: part <= 2^15, and residual digits <= 2^15 (512 rows x 64 max per
    #: digit) to the carries, so the *block count* — not the row count —
    #: is what the int32 carry sums bound: 2^16 blocks is the hard
    #: ceiling; 2^15 keeps a 2x margin (2^24 rows at the max block size,
    #: proportionally fewer for smaller blocks — both guards enforced).
    max_blocks = 1 << (30 - intac.LIMB_SHIFT)
    MAX_TERMS = max_block_size * max_blocks
    max_terms = MAX_TERMS
    #: past saturation (unreachable through ``reduce``'s bounds), the
    #: procrastinate tier's per-element digits have magnitude-independent
    #: headroom
    escalation = "procrastinate"
    #: every carry component — limbs, residual bins, overflow counter —
    #: adds associatively, so cross-device merges are one int32 psum per
    #: component: bitwise identical at any shard count or mesh shape
    merge_is_add = True

    needs_max_stat = True
    #: two wrap_adds per limb element + the wrap-event pooling
    update_ops_per_elem = 4

    #: domain layout: [q | digit bin 0 | ... | digit bin RES_NUM_BINS-1]
    _PARTS = 1 + intac.RES_NUM_BINS

    def domain_width(self, d: int) -> int:
        return self._PARTS * d

    def prepare_ctx(self, max_abs, num_terms: int):
        if num_terms > self.MAX_TERMS:
            raise ValueError(
                f"exact2: {num_terms} rows exceed the two-limb headroom "
                f"bound ({self.MAX_TERMS}); split the stream and add the "
                f"carries")
        return choose_scale(max_abs, 1, qbits=self.QBITS)

    def domain_args(self, ctx):
        # the scale, and the two exact factors of ``dequantize``'s
        # descale by it (``prepare_ctx`` makes it a power of two)
        scale = jnp.asarray(ctx, jnp.float32)
        e = jnp.round(jnp.log2(jnp.maximum(scale, jnp.float32(1e-45)))) \
            .astype(jnp.int32)
        return (scale,) + intac.ldexp2_factors(-e)

    def map_rows(self, values: jnp.ndarray, scale, inv_a, inv_b):
        """Rows -> [q | residual digits], losslessly.

        ``q = quantize(x, scale)``; the residual ``x - q / scale`` is what
        the rounding dropped, and it is exact in f32: the scale is a power
        of two, so ``x * scale`` is exact, ``q / scale`` is x rounded to
        the scale's grid, and the difference of two so-close values is
        exact (Sterbenz; Dekker's split).  (q, residual) thus represents x
        with no loss, and the residual, in quantum units, splits into
        integer digits with nothing lost above the 49-bit window.
        """
        v = values.astype(jnp.float32)
        q = quantize(v, scale)
        # ``dequantize(q, scale)`` as its two power-of-two steps.  The
        # select changes no value (q = 0 descales to +0); it keeps the
        # product rounded on its own, never fused into the subtract, so
        # a descale that overflows reads inf as in ``dequantize``
        deq = q.astype(jnp.float32) * inv_a * inv_b
        res = v - jnp.where(q == 0, jnp.float32(0), deq)   # exact: Sterbenz
        # the residual in quantum units: |res * scale| <= 1/2, and the
        # power-of-two multiply is exact, so the digit split below loses
        # nothing above the 49-bit window (its anchor is the quantum:
        # ``bin_split(res * scale, 0, ...)`` without the unit rescale)
        digits = intac.bin_digits(res * scale, bits=intac.RES_BIN_BITS,
                                  num=intac.RES_NUM_BINS)
        # one (N, (1+NB)*D) f32 domain: quantized part | digit planes.
        # Every column holds an integer below 2^QBITS (q) or 2^6
        # (digits), so the f32 round-trip back to int32 in ``contrib``
        # is exact and a single plane dot covers the whole domain.
        return jnp.concatenate([q.astype(jnp.float32)]
                               + [dg.astype(jnp.float32) for dg in digits],
                               axis=1)

    def contrib(self, onehot: jnp.ndarray, vals: jnp.ndarray):
        """One exact plane dot per block over the whole quantized+digits
        domain (the same dot lowering on every backend): the quantized
        columns (|q| <= 2^QBITS) take three planes, the residual digits
        (|d| <= 2^(RES_BIN_BITS-1)) one."""
        dd = vals.shape[1] // self._PARTS
        return int_plane_dot(onehot, [
            (vals[:, :dd], self.QBITS),
            (vals[:, dd:], intac.RES_BIN_BITS - 1)])

    def init(self, num_segments: int, d: int):
        # d is the (N, (1+NB)*D) domain width: limb carries are (S, D)
        dd = d // self._PARTS
        z = jnp.zeros((num_segments, dd), jnp.int32)
        rb = jnp.zeros((num_segments, intac.RES_NUM_BINS * dd), jnp.int32)
        return (z, z, rb, z)

    def update(self, carry, contrib):
        hi, lo, rbins, ovf = carry
        dd = hi.shape[1]
        chi, clo = intac.limb_split(contrib[:, :dd])
        nhi, w1 = intac.wrap_add(hi, chi)
        nlo, w2 = intac.wrap_add(lo, clo)
        nrb, w3 = intac.wrap_add(rbins, contrib[:, dd:])
        wb = w1.astype(jnp.int32) + w2.astype(jnp.int32)
        # detlint: ok[DET002] int32 wrap-flag adds: associative, exact
        for k in range(intac.RES_NUM_BINS):
            wb = wb + w3[:, k * dd:(k + 1) * dd].astype(jnp.int32)
        return (nhi, nlo, nrb, ovf + wb)

    def carry_status(self, carry):
        return jnp.any(carry[3] != 0)

    def finalize(self, carry, ctx) -> jnp.ndarray:
        hi, lo, rbins, _ovf = carry
        s, wd = rbins.shape
        bins = jnp.moveaxis(rbins.reshape(s, intac.RES_NUM_BINS,
                                          wd // intac.RES_NUM_BINS), 1, 0)
        return intac.limbs_resolve3_binned(hi, lo, bins, ctx)


@register_policy
class ProcrastinatePolicy(Policy):
    """Exponent-indexed bin accumulation (Liguori/Neal procrastination).

    ``prepare`` splits every f32 value — exactly — into
    ``intac.NUM_BINS`` signed integer digits of a fixed-point window
    anchored at the stream's maximum exponent, laid out digit-major along
    the feature axis, so the one-hot block matmul accumulates all bins at
    once and the carry is a single (S, NUM_BINS*D) int32 array.  Integer
    bin adds are associative (bitwise order-independent); all rounding
    happens once, in ``finalize``'s carry-resolve + compensated combine.
    Exact to <=1 ulp of the f32 result for arbitrary f32 data up to
    ``intac.BIN_MAX_TERMS`` rows — provided the result is not
    cancellation-dominated: values below max|x| * 2^-24 truncate (once,
    per element) at the window's 2^-48-of-max quantum, so when large
    terms cancel to a tiny residual the error is bounded absolutely
    (N * 2^-49 of the max), not relatively.
    """

    name = "procrastinate"
    #: bin digits + the wrap-event overflow counter
    carry_len = 2
    acc_dtype = jnp.int32
    #: round-to-nearest digits: |d| <= 2^BIN_BITS — one plane
    domain_bits = intac.BIN_BITS
    max_terms = intac.BIN_MAX_TERMS
    needs_max_stat = True
    #: one wrap_add per bin element + the wrap-event pooling
    update_ops_per_elem = 3

    def domain_width(self, d: int) -> int:
        return intac.NUM_BINS * d

    def prepare_ctx(self, max_abs, num_terms: int):
        if num_terms > intac.BIN_MAX_TERMS:
            raise ValueError(
                f"procrastinate: {num_terms} rows exceed the per-bin "
                f"headroom bound ({intac.BIN_MAX_TERMS}); split the "
                f"stream and add the bin carries")
        return intac.bin_ref_exponent(max_abs)

    def domain_args(self, ctx):
        # ``bin_split``'s rescale to the window, as two exact factors
        return intac.ldexp2_factors(-jnp.asarray(ctx, jnp.int32))

    def map_rows(self, values: jnp.ndarray, inv_a, inv_b):
        v = values.astype(jnp.float32) * inv_a * inv_b
        # digit-major along the feature axis: [bin 0 | ... | bin NB-1]
        return jnp.concatenate(intac.bin_digits(v, bits=intac.BIN_BITS,
                                                num=intac.NUM_BINS), axis=1)

    def init(self, num_segments: int, d: int):
        # d is the (N, NB*D) domain width: the ovf counter is (S, D)
        return (jnp.zeros((num_segments, d), jnp.int32),
                jnp.zeros((num_segments, d // intac.NUM_BINS), jnp.int32))

    def update(self, carry, contrib):
        bins, ovf = carry
        nb, w = intac.wrap_add(bins, contrib)
        dd = ovf.shape[1]
        wb = jnp.zeros_like(ovf)
        # detlint: ok[DET002] int32 wrap-flag adds: associative, exact
        for k in range(intac.NUM_BINS):
            wb = wb + w[:, k * dd:(k + 1) * dd].astype(jnp.int32)
        return (nb, ovf + wb)

    def carry_status(self, carry):
        return jnp.any(carry[1] != 0)

    def finalize(self, carry, ctx) -> jnp.ndarray:
        c = carry[0]                                 # (S, NB*D) int32
        s, wd = c.shape
        bins = jnp.moveaxis(c.reshape(s, intac.NUM_BINS,
                                      wd // intac.NUM_BINS), 1, 0)
        return intac.bin_combine(bins, ctx)
