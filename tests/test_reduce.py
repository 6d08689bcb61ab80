"""repro.reduce front-door tests.

The core contract under test: one call, and the (policy x backend) grid is
*consistent* — every backend executes the identical block schedule, so for
a given policy all backends agree bitwise; the exact policy additionally
agrees bitwise under input permutation.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import reduce as R
from repro.core import intac, segmented
from repro.kernels import ops

BACKENDS = ("ref", "blocked", "pallas")
POLICIES = ("fast", "compensated", "exact", "exact2", "procrastinate")
#: the tiers with integer accumulation domains (exact2 carries its
#: residual as exponent-indexed int32 digits, so its finalized float —
#: like its canonical limbs — is a pure function of the integer carry;
#: see test_exact2_limbs_invariant_result_1ulp)
INT_POLICIES = ("exact", "exact2", "procrastinate")
#: the tiers whose *finalized result* is bitwise order-independent
BITWISE_POLICIES = ("exact", "exact2", "procrastinate")


def _data(n, d, s, dtype, seed=0):
    rng = np.random.RandomState(seed)
    vals = jnp.asarray(rng.randn(n, d).astype(np.float32)).astype(dtype)
    ids = jnp.asarray(rng.randint(0, s, n))
    return vals, ids


def _scatter64(vals, ids, s):
    out = np.zeros((s,) + np.asarray(vals).shape[1:])
    np.add.at(out, np.asarray(ids), np.asarray(vals, np.float64))
    return out


# ---------------------------------------------------------------------------
# cross-backend equivalence: segmented/unsegmented x dtype x policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("policy", POLICIES)
def test_segmented_backends_bitwise_equal(policy, dtype):
    vals, ids = _data(700, 32, 9, dtype)
    outs = [np.asarray(R.reduce(vals, segment_ids=ids, num_segments=9,
                                policy=policy, backend=b, block_size=128))
            for b in BACKENDS]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)          # bitwise, not allclose
    tol = 1e-3 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        outs[0], _scatter64(vals.astype(jnp.float32), ids, 9),
        atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("policy", POLICIES)
def test_unsegmented_backends_bitwise_equal(policy, dtype):
    vals, _ = _data(500, 16, 1, dtype, seed=3)
    outs = [np.asarray(R.reduce(vals, policy=policy, backend=b,
                                block_size=128)) for b in BACKENDS]
    assert outs[0].shape == (16,)
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)


@pytest.mark.parametrize("policy", POLICIES)
def test_mean_op_matches_oracle(policy):
    vals, ids = _data(400, 8, 5, jnp.float32, seed=4)
    out = R.reduce(vals, segment_ids=ids, num_segments=5, op="mean",
                   policy=policy)
    s64 = _scatter64(vals, ids, 5)
    c64 = _scatter64(jnp.ones((400,)), ids, 5)[:, None]
    np.testing.assert_allclose(np.asarray(out), s64 / np.maximum(c64, 1),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("policy", BITWISE_POLICIES)
def test_integer_policies_permutation_and_blocksize_invariant(policy):
    x = jnp.asarray(np.random.RandomState(5).randn(4096).astype(np.float32))
    perm = np.random.RandomState(6).permutation(4096)
    a = float(R.reduce(x, policy=policy))
    b = float(R.reduce(x[perm], policy=policy))
    c = float(R.reduce(x, policy=policy, block_size=64))
    d = float(R.reduce(x[perm], policy=policy, backend="pallas",
                       block_size=256))
    assert a == b == c == d                        # bitwise


def test_exact2_limbs_invariant_result_1ulp():
    """exact2's split guarantee: the *canonical* int32 hi/lo limbs are
    bitwise identical under permutation, block size, and backend, while
    the finalized float (which folds the compensated residual limb, whose
    fold order follows the schedule) stays within 1 ulp of the f64
    reference in every configuration."""
    x = np.random.RandomState(5).randn(4096).astype(np.float32)
    perm = np.random.RandomState(6).permutation(4096)
    ref = float(np.sum(x.astype(np.float64)))
    pol = R.get_policy("exact2")
    ids = jnp.zeros(4096, jnp.int32)

    def canon_limbs(xv, backend, block_size):
        domain, ctx = pol.prepare(jnp.asarray(xv)[:, None], 4096)
        carry = R.get_backend(backend).run(domain, ids, 1, policy=pol,
                                           block_size=block_size)
        hi, lo = intac.limbs_canonical(carry[0], carry[1])
        return np.asarray(hi), np.asarray(lo)

    base = canon_limbs(x, "blocked", 512)
    for xv, bk, bs in ((x, "blocked", 64), (x[perm], "blocked", 512),
                       (x, "ref", 128), (x[perm], "pallas", 256)):
        hi, lo = canon_limbs(xv, bk, bs)
        assert np.array_equal(base[0], hi) and np.array_equal(base[1], lo)

    for xv, kw in ((x, {}), (x[perm], {}), (x, {"block_size": 64}),
                   (x[perm], {"backend": "pallas", "block_size": 256})):
        out = float(R.reduce(jnp.asarray(xv), policy="exact2", **kw))
        assert abs(out - ref) <= _ulp(ref)


def test_exact_policy_tiny_magnitude_stream():
    """Near-clamp scales (max|x| ~ 1e-38) must not collapse to zero: the
    scale clamps to 2^127 and the descale must avoid subnormal
    intermediates (reciprocal or single-step 2^-127 both flush on CPU)."""
    v = jnp.asarray([[2e-38], [2e-38]])
    for b in BACKENDS:
        out = float(R.reduce(v, policy="exact", backend=b)[0])
        assert abs(out - 4e-38) < 6e-39      # within one quantum of 2^-127


def _ulp(x: float) -> float:
    return float(np.spacing(np.abs(np.float32(x)), dtype=np.float32))


def test_large_n_exact2_and_procrastinate_keep_resolution():
    """The shrinking-scale defect, pinned: at N = 2^20 the single-limb
    ``exact`` scale has shrunk to ~2^-10 of max and visibly rounds, while
    ``exact2`` (fixed dyadic quantum) and ``procrastinate`` (per-exponent
    bins) stay within 1 ulp of the float64 oracle."""
    n = 1 << 20
    rng = np.random.RandomState(42)
    # dyadic-grid data (multiples of 2^-12): representable exactly by the
    # fixed ~2^-21-of-max quantum of exact2, far below the ~2^-10 quantum
    # the single-limb scale has shrunk to at this N
    x = (rng.randint(-4096, 4097, n) * 2.0 ** -12).astype(np.float32)
    ref = float(np.sum(x.astype(np.float64)))
    xj = jnp.asarray(x)
    errs = {p: abs(float(R.reduce(xj, policy=p, backend="blocked")) - ref)
            for p in INT_POLICIES}
    assert errs["exact"] > _ulp(ref)               # the defect
    assert errs["exact2"] <= _ulp(ref)
    assert errs["procrastinate"] <= _ulp(ref)

    # procrastinate — and, since the residual limb, exact2 — need no
    # grid: arbitrary f32 data, still <= 1 ulp
    y = rng.randn(n).astype(np.float32)
    refy = float(np.sum(y.astype(np.float64)))
    for p in ("procrastinate", "exact2"):
        erry = abs(float(R.reduce(jnp.asarray(y), policy=p,
                                  backend="blocked")) - refy)
        assert erry <= _ulp(refy), p
    assert abs(float(R.reduce(jnp.asarray(y), policy="exact",
                              backend="blocked")) - refy) > _ulp(refy)


def test_exact2_overflow_guards():
    """Stream length, block size, and block *count* beyond the two-limb
    headroom analysis are rejected eagerly rather than silently wrapping
    the int32 limbs."""
    with pytest.raises(ValueError, match="block"):
        R.reduce(jnp.ones(1024), policy="exact2", block_size=1024)
    # the lo limb accumulates one remainder per block: a small block size
    # shrinks the admissible row count proportionally
    with pytest.raises(ValueError, match="blocks"):
        R.reduce(jnp.ones((1 << 21) + 1), policy="exact2", block_size=64)
    assert float(R.reduce(jnp.ones(1 << 12), policy="exact2",
                          block_size=64)) == float(1 << 12)
    with pytest.raises(ValueError, match="headroom"):
        R.get_policy("exact2").prepare(jnp.ones(((1 << 24) + 1, 1)),
                                       (1 << 24) + 1)
    with pytest.raises(ValueError, match="headroom"):
        R.get_policy("procrastinate").prepare(jnp.ones(((1 << 22) + 1, 1)),
                                              (1 << 22) + 1)


@pytest.mark.parametrize("policy", POLICIES)
def test_all_zero_stream_is_benign(policy):
    """max_abs == 0 must yield a benign scale (``choose_scale`` pins the
    degenerate case to 1.0), not a near-2^127 one or NaN: an all-zero
    stream reduces to exact zeros on every backend, sums and means."""
    z = jnp.zeros((1024, 4))
    for b in BACKENDS:
        out = np.asarray(R.reduce(z, policy=policy, backend=b))
        assert np.array_equal(out, np.zeros(4)) and np.isfinite(out).all()
    m = np.asarray(R.reduce(jnp.zeros(512), policy=policy,
                            segment_ids=jnp.zeros(512, jnp.int32),
                            num_segments=2, op="mean"))
    assert np.array_equal(m, np.zeros(2))
    scale = float(intac.choose_scale(jnp.float32(0.0), 1024))
    assert scale == 1.0                      # pinned: benign, not 2^127


@pytest.mark.parametrize("policy", POLICIES)
def test_all_sentinel_block_is_benign(policy):
    """A stream that is 100% OUT_OF_RANGE_LABEL rows (every payload
    dropped and zeroed before ``prepare``) must reduce to finite zeros —
    the integer tiers' scale statistics see max_abs == 0."""
    vals = jnp.full((256, 3), 1e30)          # huge payloads, all dropped
    ids = jnp.full((256,), R.OUT_OF_RANGE_LABEL)
    for op in ("sum", "mean"):
        out = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=2,
                                  policy=policy, op=op))
        assert np.array_equal(out, np.zeros((2, 3)))
        assert np.isfinite(out).all()


def test_compensated_beats_fast_on_ill_conditioned():
    rng = np.random.RandomState(7)
    x = (rng.randn(1 << 15) * 10 ** rng.uniform(-4, 4, 1 << 15)) \
        .astype(np.float32)
    exact = float(np.sum(x.astype(np.float64)))
    e_fast = abs(float(R.reduce(jnp.asarray(x))) - exact)
    e_comp = abs(float(R.reduce(jnp.asarray(x), policy="compensated"))
                 - exact)
    assert e_comp <= e_fast * 1.0 + 1e-12


def test_1d_values_and_scalar_result():
    x = jnp.arange(11, dtype=jnp.float32)
    assert float(R.reduce(x)) == 55.0
    seg = R.reduce(x, segment_ids=jnp.asarray([0] * 5 + [1] * 6),
                   num_segments=2)
    assert seg.shape == (2,)
    np.testing.assert_allclose(np.asarray(seg), [10.0, 45.0])


# ---------------------------------------------------------------------------
# sentinel + mean masking
# ---------------------------------------------------------------------------


def test_out_of_range_label_drops_rows_everywhere():
    vals = jnp.asarray([[1.0], [2.0], [4.0], [8.0]])
    ids = jnp.asarray([0, R.OUT_OF_RANGE_LABEL, 1, 99])   # 99 also invalid
    for b in BACKENDS:
        out = R.reduce(vals, segment_ids=ids, num_segments=2, backend=b)
        np.testing.assert_allclose(np.asarray(out)[:, 0], [1.0, 4.0])
    # the scatter oracle follows the same convention (negatives must not
    # wrap into the last segment)
    ref = segmented.segment_sum_ref(vals, ids, 2)
    np.testing.assert_allclose(np.asarray(ref)[:, 0], [1.0, 4.0])


@pytest.mark.parametrize("policy", INT_POLICIES)
def test_dropped_rows_cannot_poison_integer_scales(policy):
    """A sentinel-labeled row's payload must not influence the integer
    tiers' quantization scale / window anchor for the rows that are kept."""
    out = R.reduce(jnp.asarray([[1.0], [1e30]]),
                   segment_ids=jnp.asarray([0, R.OUT_OF_RANGE_LABEL]),
                   num_segments=1, policy=policy)
    assert float(out[0, 0]) == 1.0


def test_mean_counts_only_in_range_rows():
    vals = jnp.asarray([2.0, 4.0, 100.0])
    ids = jnp.asarray([0, 0, R.OUT_OF_RANGE_LABEL])
    out = R.reduce(vals, segment_ids=ids, num_segments=1, op="mean")
    assert float(out[0]) == 3.0


def test_segment_mean_honors_impl_and_valid():
    vals = jnp.asarray([[1.0], [3.0], [10.0], [50.0]])
    ids = jnp.asarray([0, 0, 1, 1])
    valid = jnp.asarray([True, True, True, False])
    calls = []

    def impl(v, i, n):
        calls.append(v.shape)
        return R.reduce(v, segment_ids=i, num_segments=n, backend="blocked")

    out = segmented.segment_mean(vals, ids, 2, impl=impl, valid=valid)
    np.testing.assert_allclose(np.asarray(out)[:, 0], [2.0, 10.0])
    assert len(calls) == 2                 # sum AND count went through impl


# ---------------------------------------------------------------------------
# spec, registries, errors
# ---------------------------------------------------------------------------


def test_reduce_module_is_callable_front_door():
    x = jnp.arange(4, dtype=jnp.float32)
    assert float(repro.reduce(x)) == 6.0


def test_spec_reuse_and_replace():
    spec = R.ReduceSpec(op="mean", policy="compensated", backend="blocked")
    vals, ids = _data(64, 4, 3, jnp.float32, seed=9)
    a = R.reduce(vals, segment_ids=ids, num_segments=3, spec=spec)
    b = R.reduce(vals, segment_ids=ids, num_segments=3, op="mean",
                 policy="compensated", backend="blocked")
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert spec.replace(op="sum").op == "sum"
    assert hash(spec) == hash(R.ReduceSpec(op="mean", policy="compensated",
                                           backend="blocked"))


def test_registries_and_errors():
    assert set(BACKENDS) <= set(R.BACKENDS)
    assert set(POLICIES) <= set(R.POLICIES)
    with pytest.raises(ValueError):
        R.ReduceSpec(op="median")
    with pytest.raises(ValueError):
        R.ReduceSpec(policy="psychic")
    with pytest.raises(ValueError):
        R.ReduceSpec(backend="abacus")
    with pytest.raises(ValueError):
        R.reduce(jnp.ones((4,)), segment_ids=jnp.zeros((4,), jnp.int32))
    with pytest.raises(ValueError):
        R.reduce(jnp.ones((4,)), num_segments=2)   # ids missing
    # every backend reports wildcard/explicit capabilities correctly
    assert all(R.get_backend(b).supports(R.get_policy(p))
               for b in BACKENDS for p in POLICIES)


def test_empty_stream_is_identity_on_all_backends():
    for b in BACKENDS:
        out = R.reduce(jnp.zeros((0, 4)), backend=b)
        assert np.array_equal(np.asarray(out), np.zeros(4))
        m = R.reduce(jnp.zeros((0,)), segment_ids=jnp.zeros((0,), jnp.int32),
                     num_segments=3, op="mean", backend=b)
        assert np.array_equal(np.asarray(m), np.zeros(3))


def test_register_backend_extension_point():
    @R.register_backend("test_double", policies=("fast",),
                        description="test-only")
    def _run(values, ids, n, *, policy, block_size=512, interpret=None):
        carry = R.get_backend("blocked").run(
            values, ids, n, policy=policy, block_size=block_size)
        return tuple(2 * c for c in carry)
    try:
        x = jnp.arange(4, dtype=jnp.float32)
        assert float(R.reduce(x, backend="test_double")) == 12.0
    finally:
        del R.BACKENDS["test_double"]


# ---------------------------------------------------------------------------
# deprecation shims stay removed (CI also errors on repro DeprecationWarnings)
# ---------------------------------------------------------------------------


def test_deprecation_shims_are_gone():
    from repro.core import juggler
    assert not hasattr(segmented, "segment_sum_blocked")
    assert not hasattr(ops, "intac_sum_exact")
    assert not hasattr(juggler, "accumulate_microbatch_grads")


# ---------------------------------------------------------------------------
# Accumulator protocol
# ---------------------------------------------------------------------------


def test_protocol_instances_are_accumulators():
    for acc in (R.TreeAccumulator(4), R.FlashAccumulator(),
                R.CascadeAccumulator(2)):
        assert isinstance(acc, R.Accumulator)


def test_tree_accumulator_push_merge_finalize():
    rng = np.random.RandomState(13)
    gs = [jnp.asarray(rng.randn(6).astype(np.float32)) for _ in range(11)]
    acc = R.TreeAccumulator.for_count(11)
    st = acc.init(gs[0])
    for g in gs[:6]:
        st = acc.push(st, g)
    st2 = acc.init(gs[0])
    for g in gs[6:]:
        st2 = acc.push(st2, g)
    merged = acc.merge(st, st2)
    assert int(merged.count) == 11
    np.testing.assert_allclose(np.asarray(acc.finalize(merged)),
                               sum(np.asarray(g) for g in gs), atol=1e-5)


def test_flash_accumulator_streams_softmax():
    rng = np.random.RandomState(16)
    nshards, g, d, s = 6, 4, 16, 32
    q = rng.randn(g, d).astype(np.float32)
    k = rng.randn(nshards, s, d).astype(np.float32)
    v = rng.randn(nshards, s, d).astype(np.float32)
    acc = R.FlashAccumulator()
    state = acc.init((jnp.zeros((g,)), jnp.zeros((g,)),
                      jnp.zeros((g, d))))
    for i in range(nshards):
        sc = q @ k[i].T
        m = sc.max(-1)
        p = np.exp(sc - m[:, None])
        state = acc.push(state, (jnp.asarray(m), jnp.asarray(p.sum(-1)),
                                 jnp.asarray(p @ v[i])))
    out = np.asarray(acc.finalize(state))
    kk, vv = k.reshape(-1, d), v.reshape(-1, d)
    sc = q @ kk.T
    p = np.exp(sc - sc.max(-1, keepdims=True))
    ref = (p / p.sum(-1, keepdims=True)) @ vv
    np.testing.assert_allclose(out, ref, atol=1e-4)


def test_accumulate_microbatch_grads_front_door():
    def grad_fn(p, mb):
        return jax.tree.map(lambda x: mb["x"].sum() * jnp.ones_like(x), p), \
            jnp.float32(0.0)
    params = {"w": jnp.zeros((3,))}
    mbs = {"x": jnp.arange(8, dtype=jnp.float32).reshape(4, 2)}
    g, _ = R.accumulate_microbatch_grads(
        grad_fn, params, mbs, num_microbatches=4, mean=True)
    np.testing.assert_allclose(np.asarray(g["w"]), np.full(3, 28.0 / 4))


# ---------------------------------------------------------------------------
# collective policies (single-device mesh: policy plumbing + math parity)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", R.COLLECTIVE_POLICIES)
def test_collective_mean_policies_single_device(policy):
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    x = jnp.asarray(np.random.RandomState(17).randn(8).astype(np.float32))

    def f(v):
        m, r = R.collective_mean(v, ("data",), policy=policy, bits=8)
        return m, r

    m, r = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(x)
    tol = 0.05 if policy == "compensated" else 1e-5   # 8-bit payload
    np.testing.assert_allclose(np.asarray(m), np.asarray(x),
                               atol=tol * max(1.0, float(jnp.abs(x).max())))
    if policy == "compensated":
        # error feedback: residual holds exactly what quantization dropped
        np.testing.assert_allclose(np.asarray(m + r), np.asarray(x),
                                   atol=1e-6)
