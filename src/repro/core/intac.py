"""INTAC on TPU: exact accumulation in an integer (carry-save-like) domain.

The circuit's insight — *accumulate in a redundant/exact representation with
a tiny per-step critical path, and pay for the expensive normalization only
once per set* — maps onto TPU as fixed-point accumulation:

  * per-element work: quantize fp32 -> int32 (cheap, VPU) and integer-add
    (exact, associative — the carry-save analogue);
  * the "final addition" (limb carry-resolve + dequantize back to float)
    happens once per segment / step / all-reduce, amortized exactly like the
    resource-shared final adder in Fig. 5.

Because integer addition is associative, the accumulation result is
**bitwise independent of reduction order** — blocks, devices, pods — which is
the TPU answer to the paper's FP non-associativity problem.  This module
holds the number formats; each integer tier's one implementation is its
``Policy`` in ``repro.reduce.policy``, which every face (``reduce``, the
``shard_map`` backend, ``collective_mean``, ``elastic_reduce_mean``) runs:

  * ``choose_scale`` / ``quantize`` / ``descale`` — the shared power-of-two
                              grid (the ``exact`` tier);
  * ``LimbState`` et al.    — two-limb int32 carry-save state (wider
                              dynamic range, deferred carries; the closest
                              software analogue of (sum, carry) feedback),
                              and ``intac_sum``, exact sum of an array;
  * ``bin_split/combine``   — exponent-indexed "procrastination" bins
                              (Liguori/Neal): per-element exact digit
                              split, all rounding deferred to one combine
                              (the ``procrastinate`` tier, and ``exact2``'s
                              residual digits);
  * ``limbs_resolve3_binned`` — ``exact2``'s once-per-set final addition;
  * ``compressed_psum_mean`` — int8/int16-quantized gradient all-reduce
                              with error feedback (the distributed-
                              optimization use of the same primitive).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

# int32 headroom: values quantized to <= 2^QBITS-1 in magnitude can be
# accumulated 2^(31-QBITS) times with no overflow.
_I32_BITS = 31


def two_sum(a: jnp.ndarray, b: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Knuth two-sum: s = fl(a+b) and the exact rounding error e.

    a + b == s + e exactly, with no magnitude precondition.  Every caller
    (the compensated policy, the bin-combine finalize) must execute these
    six ops in this order — the error term is the whole point, so the
    expression must never be algebraically simplified.
    """
    s = a + b
    bp = s - a
    e = (a - (s - bp)) + (b - bp)
    return s, e


def _ldexp2(x: jnp.ndarray, e) -> jnp.ndarray:
    """x * 2^e in two half-exponent ldexp steps.

    A single step materializes 2^e, which over/underflows f32 for |e| near
    the exponent-range edges even when the *product* is representable;
    halving keeps every intermediate factor finite.
    """
    e = jnp.asarray(e, jnp.int32)
    h = e // 2
    return jnp.ldexp(jnp.ldexp(x, h), e - h)


def ldexp2_factors(e) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The two half-exponent factors of ``_ldexp2(x, e)``: (2^h, 2^(e-h))
    with h = e // 2, so ``x * f1 * f2`` is ``_ldexp2(x, e)`` bit for bit
    at the exponents the integer tiers use (|e| <= 129; each step is an
    exact power-of-two multiply, flushed below the normal range as the
    hardware flushes it).  Computed once, outside the elementwise map, so
    a kernel body needs only the two multiplies."""
    e = jnp.asarray(e, jnp.int32)
    h = e // 2

    def pow2(k):            # from the exponent bits: exact on any backend
        k = jnp.clip(k, -126, 127)
        return jax.lax.bitcast_convert_type((k + 127) << 23, jnp.float32)

    return pow2(h), pow2(e - h)


def choose_scale(max_abs: jnp.ndarray, num_terms: int,
                 qbits: int = 30) -> jnp.ndarray:
    """Power-of-two scale s.t. n * |x|_max * scale < 2^qbits.

    A power of two makes quantization error-free for values already
    representable at the target precision, mirroring the paper's
    "specific accuracy range" argument for fixed point.
    """
    # Work in log space: forming 2^qbits / (n * max_abs) directly overflows
    # f32 to inf for tiny-magnitude streams, and the old 1e-30 floor made
    # their scale so coarse that every value quantized to 0.  Floor at the
    # smallest normal (values below it are flushed by the hardware anyway)
    # and clamp e to the f32 exponent range so the scale stays finite.
    max_abs = jnp.asarray(max_abs, jnp.float32)
    floored = jnp.maximum(max_abs, jnp.float32(2.0 ** -126))
    e = jnp.floor(jnp.float32(qbits) - jnp.log2(jnp.float32(num_terms))
                  - jnp.log2(floored)).astype(jnp.int32)
    # An all-zero (or all-padding) stream has max_abs == 0 — there is
    # nothing to represent, so any scale is "correct", but the clamped
    # near-2^127 scale the floor would produce is a footgun for any later
    # nonzero use (instant overflow) and NaN statistics would poison e
    # outright.  Pin the degenerate case to the benign unit scale.
    e = jnp.where(max_abs > 0, e, jnp.int32(0))
    # ldexp(1, e) is an exact power of two; exp2(float) is approximated on
    # some backends (observed 2^26 + 64 on XLA CPU) which breaks exactness.
    return jnp.ldexp(jnp.float32(1.0), jnp.clip(e, -126, 127))


def quantize(x: jnp.ndarray, scale) -> jnp.ndarray:
    return jnp.round(x * scale).astype(jnp.int32)


def wrap_add(a: jnp.ndarray, b: jnp.ndarray) -> Tuple[jnp.ndarray,
                                                      jnp.ndarray]:
    """int32 add plus an exact wraparound predicate: (a + b, wrapped).

    Two's-complement overflow happens iff both operands share a sign and
    the sum does not: ``((a ^ s) & (b ^ s)) < 0`` checks exactly that with
    three cheap bitwise ops — jittable, branch-free, and free to fuse into
    the accumulation it guards.  This is the guard-rail primitive of the
    integer tiers: every carry update that could saturate threads its
    wrap flags into an overflow counter, so a result whose canonical
    integer total wrapped is *detected* (``ReduceStatus.saturated``)
    instead of silently wrong.
    """
    a = a.astype(jnp.int32)
    b = b.astype(jnp.int32)
    s = a + b
    return s, ((a ^ s) & (b ^ s)) < 0


def descale(xf: jnp.ndarray, scale) -> jnp.ndarray:
    """Divide an f32 value by ``scale``; exact two-step ldexp for powers
    of two.

    In-repo scales all come from ``choose_scale`` (powers of two): for
    those, two half-exponent ldexp steps replace the division — XLA may
    lower x/s as x*(1/s), and for near-clamp scales (e≈127) the
    reciprocal (or a single-step 2^-e) is subnormal and flushes to zero
    on CPU; halving the exponent keeps every factor normal and exact.
    Arbitrary external scales fall back to plain division."""
    scale = jnp.asarray(scale, jnp.float32)
    xf = xf.astype(jnp.float32)
    e = jnp.round(jnp.log2(jnp.maximum(scale, jnp.float32(1e-45)))) \
        .astype(jnp.int32)
    exact = _ldexp2(xf, -e)
    is_pow2 = jnp.ldexp(jnp.float32(1.0), e) == scale
    return jnp.where(is_pow2, exact, xf / scale)


def dequantize(q: jnp.ndarray, scale) -> jnp.ndarray:
    return descale(q.astype(jnp.float32), scale)


@partial(jax.jit, static_argnames=("axis",))
def intac_sum(x: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Exact-within-quantization, order-independent sum along ``axis``.

    Two passes (max, then accumulate) — the first pass plays the role of the
    paper's a-priori bit-width parameterization.
    """
    n = x.shape[axis]
    max_abs = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = choose_scale(jnp.max(max_abs), n)
    q = quantize(x, scale)
    return dequantize(jnp.sum(q, axis=axis), scale)


class LimbState(NamedTuple):
    """Two-limb redundant accumulator — the (sum, carry) pair of Fig. 4.

    value represented = (hi * 2^15 + lo) / scale.  Each limb holds partial
    sums < 2^15 in magnitude per term, so 2^16 terms accumulate with no
    overflow and no cross-limb carries until ``finalize`` — deferred carry
    resolution, exactly the carry-save contract.
    """
    hi: jnp.ndarray   # int32
    lo: jnp.ndarray   # int32
    scale: jnp.ndarray


LIMB_SHIFT = 15


def limb_init(shape, scale) -> LimbState:
    z = jnp.zeros(shape, jnp.int32)
    return LimbState(z, z, jnp.asarray(scale, jnp.float32))


def limb_split(q: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Split an int32 value into (hi, lo) limbs with pure integer ops.

    q == hi * 2^LIMB_SHIFT + lo with lo in [0, 2^LIMB_SHIFT) — the
    arithmetic right shift floors, so the identity holds for negatives
    too.  Integer shift/mask, never float divide: a float-domain split
    would round for quantities above the 24-bit mantissa, silently
    breaking the exact-within-quantization contract.
    """
    q = q.astype(jnp.int32)
    hi = jnp.right_shift(q, LIMB_SHIFT)
    lo = jnp.bitwise_and(q, (1 << LIMB_SHIFT) - 1)
    return hi, lo


def limbs_canonical(hi: jnp.ndarray,
                    lo: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Canonicalize an (hi, lo) int32 limb pair in the integer domain.

    lo's bits above ``LIMB_SHIFT`` carry into hi, leaving the unique
    Euclidean pair with lo in [0, 2^LIMB_SHIFT).  The canonical pair is a
    pure function of the represented integer total ``hi * 2^15 + lo`` —
    *this* is the bitwise-invariant object of the limb tiers: raw carries
    depend on how the stream was blocked, the canonical pair does not.
    Tests and the shard_map guarantee compare limbs through here.
    """
    carry = jnp.right_shift(lo, LIMB_SHIFT)
    return hi + carry, jnp.bitwise_and(lo, (1 << LIMB_SHIFT) - 1)


def limb_add(state: LimbState, x: jnp.ndarray) -> LimbState:
    """Accumulate one fp32 operand (the 3:2 compressor step).

    Quantizes to int32 *first* and splits with integer shift/mask — the
    value must satisfy |x * scale| < 2^31 (the int32 contract).
    """
    hi, lo = limb_split(quantize(x, state.scale))
    return LimbState(state.hi + hi, state.lo + lo, state.scale)


def limbs_resolve(hi: jnp.ndarray, lo: jnp.ndarray, scale) -> jnp.ndarray:
    """Carry-resolve two int32 limbs and descale — the once-per-set final
    addition (resource-shared adder analogue).

    First canonicalizes in the integer domain (lo's bits above LIMB_SHIFT
    carry into hi, leaving the unique Euclidean pair with lo in
    [0, 2^LIMB_SHIFT)), so the f32 conversion of hi sees the same integer
    no matter how the stream was blocked — the result is bitwise
    independent of the limb decomposition.  The only floating-point
    rounding in the whole accumulation happens here.  ``lo`` must be
    non-negative (it is a sum of per-step remainders in [0, 2^15)).
    """
    hi, lo = limbs_canonical(hi, lo)
    total = jnp.ldexp(hi.astype(jnp.float32), LIMB_SHIFT) \
        + lo.astype(jnp.float32)
    return descale(total, scale)


def limb_finalize(state: LimbState) -> jnp.ndarray:
    return limbs_resolve(state.hi, state.lo, state.scale)


def limb_merge(a: LimbState, b: LimbState) -> LimbState:
    """Merging two redundant accumulators is itself exact/associative."""
    return LimbState(a.hi + b.hi, a.lo + b.lo, a.scale)


# ---------------------------------------------------------------------------
# Exponent-indexed bins ("procrastination" accumulation)
# ---------------------------------------------------------------------------
#
# Liguori's procrastination accumulators (arXiv 2406.05866) and Neal's
# small superaccumulators (arXiv 1505.05571), int32 edition: an f32 value
# is split — exactly, by Dekker-style extraction — into BIN_BITS-wide
# signed digits of a fixed-point window anchored at the stream's maximum
# exponent.  Each digit lands in its own int32 bin; bins add with pure
# (associative) integer arithmetic, so the accumulation is bitwise
# order-independent, and all rounding procrastinates to one carry-resolve
# + compensated combine in ``bin_combine``.
#
# Window: NUM_BINS * BIN_BITS = 48 fractional bits below the max
# exponent, so any value within 2^(48-24) = 2^24 of the maximum splits
# exactly (full f32 mantissa preserved); smaller values round once, per
# element, at the 2^-48 quantum — order-independent, and below 1 ulp of
# the sum whenever the sum itself stays within ~2^24 of the maximum.
# Under catastrophic cancellation the bound degrades to the absolute
# N * 2^-49-of-max truncation error, not a relative one.  Headroom:
# per-element
# digits are bounded by 2^BIN_BITS, so up to 2^(31-BIN_BITS-1) = 2^22
# terms accumulate per bin with no overflow, *independent of magnitude* —
# resolution no longer trades against stream length.

BIN_BITS = 8
NUM_BINS = 6
#: per-bin int32 headroom: max terms accumulated with no overflow
BIN_MAX_TERMS = 1 << (31 - BIN_BITS - 1)

#: the residual superaccumulator of the exact2 tier: the per-element
#: quantization residual (|r * scale| <= 1/2 — below one quantum) splits
#: into RES_NUM_BINS digits of RES_BIN_BITS bits anchored at the quantum
#: (e_ref = 0), a 49-bit window below the scale's grid.  Digits are <=
#: 2^(RES_BIN_BITS - 1) = 64 per element, so a 512-row block contributes
#: <= 2^15 per bin and 2^15 blocks stay within int32 — the same 2x-margin
#: headroom ledger as the integer limbs.  Truncation below the window is
#: <= 2^-50 of a quantum per element: with the exact2 scale (2^21 below
#: max|x|) that is max|x| * 2^-71 per element — far below 1 ulp of any
#: sum of up to 2^24 terms.
RES_BIN_BITS = 7
RES_NUM_BINS = 7


def bin_ref_exponent(max_abs) -> jnp.ndarray:
    """Window anchor: e with max_abs * 2^-e in [0.5, 1); 0 for all-zero.

    A pure function of the stream's maximum magnitude — permutation
    invariant, and shared across devices via a pmax for collectives.
    """
    m = jnp.maximum(jnp.asarray(max_abs, jnp.float32),
                    jnp.float32(2.0 ** -126))
    return jnp.frexp(m)[1].astype(jnp.int32)


def bin_split(x: jnp.ndarray, e_ref, *, bits: int = BIN_BITS,
              num: int = NUM_BINS) -> jnp.ndarray:
    """Split f32 values into (num, *x.shape) int32 exponent-bin digits.

    x == sum_k digits[k] * 2^(e_ref - (k+1)*bits) exactly for values
    within 2^24 of the window anchor; the residual below the window is
    dropped (see module comment).  Each extraction step is exact float
    arithmetic: s = v * 2^W is a power-of-two scaling, round(s) is an
    integer below 2^W, and s - round(s) is a multiple of ulp(s) — the
    classic Dekker split.  Defaults are the procrastinate tier's window;
    the exact2 residual superaccumulator uses ``bits=RES_BIN_BITS,
    num=RES_NUM_BINS`` anchored at its quantum.
    """
    v = _ldexp2(x.astype(jnp.float32), -jnp.asarray(e_ref, jnp.int32))
    return jnp.stack(bin_digits(v, bits=bits, num=num))


def bin_digits(v: jnp.ndarray, *, bits: int, num: int) -> list:
    """The digit extraction of ``bin_split`` on values already scaled to
    the window (``v = x * 2^-e_ref``): ``num`` int32 digit arrays, most
    significant first.  Multiplies by 2^bits, round-half-even, subtracts
    and converts only — what a kernel body lowers."""
    radix = jnp.float32(1 << bits)
    digits = []
    for _ in range(num):
        s = v * radix
        d = jnp.round(s)
        v = s - d                         # exact: both multiples of ulp(s)
        digits.append(d.astype(jnp.int32))
    return digits


def _bin_carry_resolve(bins: jnp.ndarray, bits: int) -> list:
    """Canonicalize (num, ...) int32 digit bins in the integer domain.

    Each bin's digit beyond +-2^(bits-1) carries into the next-more-
    significant bin, leaving a representation that is a pure function of
    the accumulated total — the bin analogue of ``limbs_canonical``, and
    the reason binned results are bitwise blocking/order-independent.
    """
    num = bins.shape[0]
    resolved = [bins[k] for k in range(num)]
    half = 1 << (bits - 1)
    for k in range(num - 1, 0, -1):
        c = jnp.right_shift(resolved[k] + half, bits)
        resolved[k] = resolved[k] - (c << bits)
        resolved[k - 1] = resolved[k - 1] + c
    return resolved


def bin_combine(bins: jnp.ndarray, e_ref, *,
                bits: int = BIN_BITS) -> jnp.ndarray:
    """The deferred final addition: (num, ...) int32 bins -> f32.

    Integer carry-resolve first (``_bin_carry_resolve``), which makes the
    representation a canonical function of the accumulated total — so the
    f32 result is bitwise independent of how the stream was blocked or
    ordered.  The float combine then runs least-significant-first through
    the compensated two-sum, so the one rounding that reaches the caller
    is the final one.
    """
    e_ref = jnp.asarray(e_ref, jnp.int32)
    num = bins.shape[0]
    resolved = _bin_carry_resolve(bins, bits)
    acc = jnp.zeros(bins.shape[1:], jnp.float32)
    comp = jnp.zeros(bins.shape[1:], jnp.float32)
    # detlint: ok[DET002] two_sum resolve chain: order pinned by data
    # dependence through acc; the final rounding is pinned by ulp tests
    for k in range(num - 1, -1, -1):
        term = _ldexp2(resolved[k].astype(jnp.float32),
                       e_ref - (k + 1) * bits)
        acc, e = two_sum(acc, term)
        comp = comp + e
    return acc + comp


def limbs_resolve3_binned(hi: jnp.ndarray, lo: jnp.ndarray,
                          rbins: jnp.ndarray, scale, *,
                          bits: int = RES_BIN_BITS) -> jnp.ndarray:
    """Resolve (hi, lo) integer limbs plus a binned residual
    superaccumulator — the all-integer three-limb final addition.

    ``rbins`` is (num, ...) int32: sums of per-element residual digits
    (``bin_split(r * scale, 0, bits=RES_BIN_BITS, num=RES_NUM_BINS)``),
    each digit worth ``2^(-(k+1)*bits) / scale``.  Everything entering
    the float combine is a canonical integer (``limbs_canonical`` for the
    limbs, ``_bin_carry_resolve`` for the bins) — a pure function of the
    accumulated integer totals — so the finalized float is **bitwise**
    independent of blocking, ordering, backend, shard count, and mesh
    shape, with no order-pinned float fold left anywhere.  The combine
    runs least-significant-first (residual bins, then lo, then the split
    hi) through compensated two-sums: one rounding reaches the caller.
    """
    hi, lo = limbs_canonical(hi, lo)
    num = rbins.shape[0]
    resolved = _bin_carry_resolve(rbins, bits)
    # hi may need up to 31 bits: split into two exactly-convertible pieces
    _HSPLIT = 14
    hih = jnp.right_shift(hi, _HSPLIT)               # |hih| <= 2^17
    hil = jnp.bitwise_and(hi, (1 << _HSPLIT) - 1)    # in [0, 2^14)
    acc = jnp.zeros(hi.shape, jnp.float32)
    cmp_ = jnp.zeros(hi.shape, jnp.float32)
    terms = [(resolved[k], -(k + 1) * bits) for k in range(num - 1, -1, -1)]
    terms += [(lo, 0), (hil, LIMB_SHIFT), (hih, LIMB_SHIFT + _HSPLIT)]
    # detlint: ok[DET002] two_sum resolve chain: order pinned by data
    # dependence through acc; the final rounding is pinned by ulp tests
    for quanta, shift in terms:
        term = descale(_ldexp2(quanta.astype(jnp.float32), shift), scale)
        acc, e = two_sum(acc, term)
        cmp_ = cmp_ + e
    return acc + cmp_


# ---------------------------------------------------------------------------
# Distributed reductions
# ---------------------------------------------------------------------------


def compressed_psum_mean(x: jnp.ndarray, residual: jnp.ndarray, axis_name,
                         *, bits: int = 8) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """INTAC-style compressed gradient all-reduce with error feedback.

    1. add the residual carried from the previous step (error feedback);
    2. agree on a shared power-of-two scale targeting ``bits``-bit payloads;
    3. quantize -> int, psum in the exact integer domain, dequantize once;
    4. the local quantization error becomes the next residual.

    Communication payload is ``bits``/32 of fp32 (int8 => 4x compression);
    the integer psum keeps the *reduction* exact and deterministic, so the
    only loss is the explicit, error-fed-back quantization.
    Returns (mean gradient, new residual).
    """
    xr = x + residual
    n = jax.lax.psum(1, axis_name)
    gmax = jax.lax.pmax(jnp.max(jnp.abs(xr)), axis_name)
    # payload must fit `bits` signed bits; headroom for the n-way sum lives
    # in the int32 accumulator, not the payload.
    scale = choose_scale(gmax, 1, qbits=bits - 1)
    q = quantize(xr, scale)
    new_residual = xr - dequantize(q, scale)
    total = jax.lax.psum(q, axis_name)          # int32 accumulate (exact)
    mean = dequantize(total, scale) / n
    return mean, new_residual
