"""Property tests for the production core: trees, segmented reduction,
INTAC fixed point, gradient juggler."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import intac, juggler, segmented, trees


# ---------------------------------------------------------------------------
# pairing trees
# ---------------------------------------------------------------------------


@settings(max_examples=30, deadline=None)
@given(st.integers(min_value=1, max_value=100))
def test_tree_sum_matches(n):
    x = jnp.asarray(np.random.RandomState(n).randn(n, 3).astype(np.float32))
    assert np.allclose(trees.pairwise_tree_sum(x, 0), np.asarray(x).sum(0),
                       atol=1e-4)


def test_tree_depth():
    assert trees.tree_depth(1) == 0
    assert trees.tree_depth(2) == 1
    assert trees.tree_depth(6) == 3
    assert trees.tree_depth(1024) == 10


def test_tree_error_growth_vs_serial():
    """The paper's numerical motivation: pairwise-tree error << serial
    error on large ill-conditioned sums (fp32)."""
    rng = np.random.RandomState(0)
    x = (rng.randn(1 << 16) * 10 ** rng.uniform(-4, 4, 1 << 16)) \
        .astype(np.float32)
    exact = np.sum(x.astype(np.float64))
    serial = np.float32(0.0)
    for v in x:
        serial += v
    tree = float(trees.pairwise_tree_sum(jnp.asarray(x), 0))
    err_serial = abs(float(serial) - exact)
    err_tree = abs(tree - exact)
    assert err_tree <= err_serial * 1.01


def test_tree_combine_nonpow2_order():
    """Fixed schedule: result independent of padding tricks, equals ref."""
    x = jnp.arange(11, dtype=jnp.float32)
    assert float(trees.pairwise_tree_sum(x, 0)) == 55.0


# ---------------------------------------------------------------------------
# segmented reduction (variable-length sets)
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(min_value=1, max_value=60), min_size=1,
                max_size=12),
       st.integers(min_value=1, max_value=8),
       st.sampled_from([64, 128, 257]))
def test_blocked_segment_sum(lengths, d, block):
    from repro import reduce as R
    total = sum(lengths)
    ids = segmented.segments_from_lengths(jnp.asarray(lengths), total)
    vals = jnp.asarray(
        np.random.RandomState(total).randn(total, d).astype(np.float32))
    ref = segmented.segment_sum_ref(vals, ids, len(lengths))
    out = R.reduce(vals, segment_ids=ids, num_segments=len(lengths),
                   backend="blocked", block_size=block)
    assert np.allclose(out, ref, atol=1e-4)


def test_segments_from_lengths():
    ids = segmented.segments_from_lengths(jnp.asarray([3, 1, 2]), 6)
    assert list(np.asarray(ids)) == [0, 0, 0, 1, 2, 2]


def test_segment_mean():
    vals = jnp.asarray([[1.0], [3.0], [10.0]])
    ids = jnp.asarray([0, 0, 1])
    out = segmented.segment_mean(vals, ids, 2)
    assert np.allclose(out[:, 0], [2.0, 10.0])


def test_flash_partial_combine_tree():
    """Combining flash partials with the fixed tree == full softmax."""
    rng = np.random.RandomState(1)
    nshards, g, d, s = 8, 4, 16, 32
    q = rng.randn(g, d).astype(np.float32)
    k = rng.randn(nshards, s, d).astype(np.float32)
    v = rng.randn(nshards, s, d).astype(np.float32)
    ms, ls, os_ = [], [], []
    for i in range(nshards):
        sc = q @ k[i].T
        m = sc.max(-1)
        p = np.exp(sc - m[:, None])
        ms.append(m)
        ls.append(p.sum(-1))
        os_.append(p @ v[i])
    m, l, o = segmented.combine_flash_partials_tree(
        jnp.asarray(np.stack(ms)), jnp.asarray(np.stack(ls)),
        jnp.asarray(np.stack(os_)), axis=0)
    out = np.asarray(o) / np.asarray(l)[:, None]
    # reference: softmax over the concatenated kv
    kk = k.reshape(-1, d)
    vv = v.reshape(-1, d)
    sc = q @ kk.T
    p = np.exp(sc - sc.max(-1, keepdims=True))
    ref = (p / p.sum(-1, keepdims=True)) @ vv
    assert np.allclose(out, ref, atol=1e-4)


# ---------------------------------------------------------------------------
# INTAC fixed point
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=1, max_value=400))
def test_intac_sum_order_independent(n):
    x = jnp.asarray(np.random.RandomState(n).randn(n).astype(np.float32))
    a = float(intac.intac_sum(x))
    b = float(intac.intac_sum(x[::-1]))
    assert a == b            # bitwise identical under reordering


def test_intac_sum_accuracy():
    x = jnp.asarray(np.random.RandomState(0).randn(1000).astype(np.float32))
    exact = float(np.sum(np.asarray(x, np.float64)))
    assert abs(float(intac.intac_sum(x)) - exact) < 1e-3


def test_choose_scale_no_overflow():
    for n, amax in [(10, 1.0), (65536, 100.0), (3, 1e-8)]:
        scale = float(intac.choose_scale(jnp.float32(amax), n))
        assert n * amax * scale < 2 ** 31
        # power of two
        assert float(np.log2(scale)) == int(np.log2(scale))


def test_limb_split_boundaries():
    """The limb split is pure integer shift/mask, so it reconstructs
    exactly at and beyond the f32 24-bit mantissa boundary (a float-domain
    split rounds there).  2^24 + 1 itself is not an f32, so the nearest
    representable neighbours bracket the boundary."""
    scale = jnp.float32(2.0 ** 16)
    for q in (2 ** 24 - 1, 2 ** 24, 2 ** 24 + 2, 2 ** 30,
              -(2 ** 24 - 1), -(2 ** 24 + 2), -(2 ** 30)):
        x = jnp.float32(q * 2.0 ** -16)          # quantizes to exactly q
        st_ = intac.limb_add(intac.limb_init((), scale), x)
        hi, lo = int(st_.hi), int(st_.lo)
        assert 0 <= lo < (1 << intac.LIMB_SHIFT)       # canonical split
        assert hi * (1 << intac.LIMB_SHIFT) + lo == q  # exact identity
        assert float(intac.limb_finalize(st_)) == float(x)


def test_limb_resolve_is_decomposition_independent():
    """limbs_resolve canonicalizes in the integer domain, so any (hi, lo)
    pair representing the same total resolves to the same bits."""
    scale = jnp.float32(1.0)
    a = intac.limbs_resolve(jnp.int32(1000), jnp.int32(2 ** 26 + 123), scale)
    hi2 = 1000 + ((2 ** 26 + 123) >> intac.LIMB_SHIFT)
    lo2 = (2 ** 26 + 123) & ((1 << intac.LIMB_SHIFT) - 1)
    b = intac.limbs_resolve(jnp.int32(hi2), jnp.int32(lo2), scale)
    assert float(a) == float(b)


def test_wrap_add_trips_exactly_at_the_int32_edge():
    """The wrap predicate is exact: carries within +/-1 of the int32
    boundary flag iff the two's-complement sum actually wrapped."""
    mx, mn = np.int32(2**31 - 1), np.int32(-(2**31))
    cases = [(mx - 1, 1, False), (mx, 0, False), (mx, 1, True),
             (mn + 1, -1, False), (mn, 0, False), (mn, -1, True),
             (mx, mn, False), (0, 0, False)]
    for a, b, wraps in cases:
        s, w = intac.wrap_add(jnp.int32(a), jnp.int32(b))
        assert bool(w) == wraps, (a, b)
        if not wraps:
            assert int(s) == int(a) + int(b)


def test_choose_scale_zero_and_nan_streams_are_benign():
    """max_abs == 0 (all-zero or all-padding stream) pins the unit scale
    instead of the degenerate near-2^127 clamp; a NaN statistic must not
    poison the scale either."""
    assert float(intac.choose_scale(jnp.float32(0.0), 1024)) == 1.0
    assert float(intac.choose_scale(jnp.float32(0.0), 1)) == 1.0
    s = float(intac.choose_scale(jnp.float32(np.nan), 16))
    assert np.isfinite(s) and s == 1.0
    # tiny-but-nonzero streams keep the clamped-scale behavior
    assert float(intac.choose_scale(jnp.float32(2e-38), 2)) == 2.0 ** 127


def test_bin_split_combine_exact_roundtrip():
    """Exponent-bin digits reconstruct arbitrary f32 exactly within the
    48-bit window, and the bin sums are bitwise permutation-invariant."""
    rng = np.random.RandomState(9)
    x = jnp.asarray((rng.randn(2000) * 10 ** rng.uniform(-4, 4, 2000))
                    .astype(np.float32))
    e_ref = intac.bin_ref_exponent(jnp.max(jnp.abs(x)))
    rec = intac.bin_combine(intac.bin_split(x, e_ref), e_ref)
    # per-element roundtrip is exact for values within 2^24 of the max
    big = np.abs(np.asarray(x)) >= float(jnp.max(jnp.abs(x))) * 2.0 ** -24
    assert np.array_equal(np.asarray(rec)[big], np.asarray(x)[big])
    perm = rng.permutation(2000)
    a = intac.bin_combine(jnp.sum(intac.bin_split(x, e_ref), axis=1), e_ref)
    b = intac.bin_combine(jnp.sum(intac.bin_split(x[perm], e_ref), axis=1),
                          e_ref)
    assert float(a) == float(b)


def test_limb_accumulator_exact_merge():
    rng = np.random.RandomState(3)
    xs = rng.randn(200, 8).astype(np.float32)
    scale = 2.0 ** 16
    st_a = intac.limb_init((8,), scale)
    for r in xs[:100]:
        st_a = intac.limb_add(st_a, jnp.asarray(r))
    st_b = intac.limb_init((8,), scale)
    for r in xs[100:]:
        st_b = intac.limb_add(st_b, jnp.asarray(r))
    merged = intac.limb_finalize(intac.limb_merge(st_a, st_b))
    direct = intac.limb_init((8,), scale)
    for r in xs:
        direct = intac.limb_add(direct, jnp.asarray(r))
    assert np.array_equal(np.asarray(merged),
                          np.asarray(intac.limb_finalize(direct)))


# ---------------------------------------------------------------------------
# gradient juggler (binary-counter pairing tree)
# ---------------------------------------------------------------------------


@settings(max_examples=20, deadline=None)
@given(st.integers(min_value=1, max_value=33))
def test_juggler_matches_sum(n):
    gs = [jnp.asarray(np.random.RandomState(i).randn(4).astype(np.float32))
          for i in range(n)]
    stt = juggler.juggler_init(gs[0], juggler.num_slots_for(n))
    for g in gs:
        stt = juggler.juggler_push(stt, g)
    tot = juggler.juggler_finalize(stt)
    assert np.allclose(tot, sum(np.asarray(g) for g in gs), atol=1e-4)
    assert int(stt.count) == n


def test_juggler_slot_bound():
    """Live-slot occupancy never exceeds ceil(log2 n)+1 — the PIS register
    bound translated to memory."""
    k = juggler.num_slots_for(19)
    stt = juggler.juggler_init(jnp.zeros((2,)), k)
    max_occ = 0
    for i in range(19):
        stt = juggler.juggler_push(stt, jnp.ones((2,)))
        max_occ = max(max_occ, int(jnp.sum(stt.occupancy)))
    assert max_occ <= k
    assert float(juggler.juggler_finalize(stt)[0]) == 19.0


def test_accumulate_microbatch_grads():
    from repro import reduce as R
    def grad_fn(p, mb):
        return jax.tree.map(lambda x: mb["x"].sum() * jnp.ones_like(x), p), \
            jnp.float32(0.0)
    params = {"w": jnp.zeros((3,))}
    mbs = {"x": jnp.arange(8, dtype=jnp.float32).reshape(4, 2)}
    g, _ = R.accumulate_microbatch_grads(
        grad_fn, params, mbs, num_microbatches=4, mean=True)
    assert np.allclose(g["w"], np.full(3, 28.0 / 4))
