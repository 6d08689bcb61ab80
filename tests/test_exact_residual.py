"""exact2's residual limb: "exact means exact" off the dyadic grid.

The old two-limb exact2 silently dropped the sub-quantum bits of any
input not on its ~2^-21-of-max dyadic grid; these tests pin adversarial
non-dyadic streams where that defect *provably* exceeds 1 ulp vs the f64
reference, and assert the three-limb tier closes it on every backend —
ref / blocked / pallas in-process, shard_map at 1/2/8 simulated devices
in a subprocess — while the canonical int32 hi/lo limbs stay bitwise
identical across backends, block sizes, and shard counts.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import reduce as R
from repro.core import intac
from repro.kernels import ops

REPO = Path(__file__).resolve().parent.parent
N = 1 << 20


def _ulp(x: float) -> float:
    return float(np.spacing(np.abs(np.float32(x)), dtype=np.float32))


def third_stream(n=N) -> np.ndarray:
    """1/3 + ulp-scale noise: every value sits ~1/3 of a quantum off the
    exact2 grid with a shared bias, so the old tier's per-element drop
    accumulates linearly (~3 ulp of the sum at N=2^20)."""
    rng = np.random.RandomState(7)
    return (1 / 3 + rng.randn(n) * 1e-9).astype(np.float32)


def cancellation_stream(n=N) -> np.ndarray:
    """Catastrophic-cancellation pairs (+/- up-to-1000 values that cancel
    exactly) interleaved with an off-grid 1/3 payload: the huge max|x|
    coarsens the old tier's quantum to ~2^-11, shredding the payload
    (~11 ulp of the surviving sum at N=2^20)."""
    rng = np.random.RandomState(11)
    big = rng.uniform(100.0, 1000.0, n // 2).astype(np.float32)
    x = np.empty(n, np.float32)
    x[0::4] = big[0::2]
    x[1::4] = -big[0::2]
    x[2::4] = big[1::2] + np.float32(1 / 3)
    x[3::4] = -big[1::2]
    return x


def _old_exact2(x: np.ndarray) -> float:
    """The pre-fix behavior: run the schedule, finalize the *integer
    limbs only* (what the two-limb tier returned)."""
    pol = R.get_policy("exact2")
    xj = jnp.asarray(x)[:, None]
    domain, scale = pol.prepare(xj, len(x))
    carry = R.get_backend("blocked").run(
        domain, jnp.zeros(len(x), jnp.int32), 1, policy=pol, block_size=512)
    return float(intac.limbs_resolve(carry[0], carry[1], scale)[0, 0])


@pytest.mark.parametrize("stream", [third_stream, cancellation_stream])
def test_pinned_streams_defeat_the_old_tier(stream):
    """Regression pin: on these streams the integer limbs alone — the
    whole of the old exact2 — exceed 1 ulp vs f64.  If this ever stops
    holding, the adversarial fixtures have gone stale."""
    x = stream()
    ref = float(np.sum(x.astype(np.float64)))
    assert abs(_old_exact2(x) - ref) > _ulp(ref)


@pytest.mark.parametrize("stream", [third_stream, cancellation_stream])
def test_residual_limb_within_1ulp_on_local_backends(stream):
    """The fix, end to end: <= 1 ulp vs f64 at N=2^20 on blocked/pallas
    (and on ref at 2^16 — the unrolled oracle is too slow to jit 2048
    blocks), with bitwise-equal results across backends at a fixed
    schedule and bitwise-equal canonical limbs across block sizes."""
    x = stream()
    ref = float(np.sum(x.astype(np.float64)))
    outs = {b: float(R.reduce(jnp.asarray(x), policy="exact2", backend=b))
            for b in ("blocked", "pallas")}
    for b, out in outs.items():
        assert abs(out - ref) <= _ulp(ref), (b, out, ref)
    assert outs["blocked"] == outs["pallas"]          # same schedule: bits

    xs = x[: 1 << 16]
    refs = float(np.sum(xs.astype(np.float64)))
    out_ref = float(R.reduce(jnp.asarray(xs), policy="exact2",
                             backend="ref"))
    assert abs(out_ref - refs) <= _ulp(refs)

    # canonical integer limbs: bitwise across block sizes and backends
    pol = R.get_policy("exact2")
    domain, _ = pol.prepare(jnp.asarray(x)[:, None], len(x))
    ids = jnp.zeros(len(x), jnp.int32)
    limbs = []
    for bk, bs in (("blocked", 512), ("blocked", 128), ("pallas", 512)):
        c = R.get_backend(bk).run(domain, ids, 1, policy=pol, block_size=bs)
        limbs.append([np.asarray(v)
                      for v in intac.limbs_canonical(c[0], c[1])])
    for other in limbs[1:]:
        assert all(np.array_equal(a, b) for a, b in zip(limbs[0], other))


def _with_dropped_rows(x: np.ndarray, s: int, seed: int = 3):
    """Labels in [0, s) for the rows of ``x``, with 60 rows dropped (30 on
    the sentinel, 30 past ``s``) whose payloads carry NaN, +-Inf and
    1e38.  Returns (values, labels, number of kept rows)."""
    rng = np.random.RandomState(seed)
    x = x.copy()
    ids = rng.randint(0, s, len(x)).astype(np.int32)
    drop = rng.choice(len(x), 60, replace=False)
    ids[drop[:30]] = R.OUT_OF_RANGE_LABEL
    ids[drop[30:]] = s + 2
    for k, bad in enumerate((np.nan, np.inf, -np.inf, 1e38)):
        x[drop[k::4]] = bad
    return x, ids, len(x) - 60


@pytest.mark.parametrize("backend", ("ref", "blocked", "pallas"))
@pytest.mark.parametrize("policy", ("exact", "exact2", "procrastinate",
                                    "fast"))
@pytest.mark.parametrize("stream", [third_stream, cancellation_stream])
def test_block_domain_map_matches_whole_stream_prepare(stream, policy,
                                                       backend,
                                                       monkeypatch):
    """The integer tiers map each schedule block into their domain as it
    folds (in VMEM, on pallas): against the whole-stream
    ``policy.prepare`` the schedule then folds, the carry, the result and
    the status flags are the same bits — with dropped rows carrying NaN,
    Inf and 1e38, N no multiple of the block, and S over one label tile
    of the pallas carry budget.  (``fast``, whose front door maps the
    stream, runs the per-block map here only: its float domain is where
    a dropped row's NaN would show if the block step did not zero it.)"""
    n, d, s, bs = 1037, 4, 9, 128
    x, ids, kept_rows = _with_dropped_rows(stream(n * d).reshape(n, d), s)
    pol = R.get_policy(policy)
    # about 4 labels per pallas label tile, so S=9 takes three
    monkeypatch.setattr(ops, "_SEGSUM_ACC_BUDGET",
                        4 * pol.domain_width(d) * pol.carry_len)
    vals = jnp.asarray(x)
    mids = R.mask_out_of_range(jnp.asarray(ids), s)
    bk = R.get_backend(backend)
    domain, ctx = pol.prepare(
        jnp.where((mids >= 0)[:, None], vals, 0.0), n)
    whole = bk.run(domain, mids, s, policy=pol, block_size=bs)
    block = bk.run(vals, mids, s, policy=pol, block_size=bs,
                   to_domain=pol.map_rows, prep_state=pol.domain_args(ctx))
    for a, b in zip(whole, block):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    if not pol.needs_max_stat:
        return                     # the front door maps fast's stream

    out, st = R.reduce(vals, segment_ids=jnp.asarray(ids), num_segments=s,
                       policy=policy, backend=backend, block_size=bs,
                       with_status=True)
    assert np.array_equal(np.asarray(out),
                          np.asarray(pol.finalize(whole, ctx)))
    assert (bool(st.nonfinite), bool(st.saturated), int(st.kept_rows)) == \
        (False, False, kept_rows)

    # a NaN in a kept row trips the flag, and the result keeps the bits
    # of the whole-stream path (the NaN's segment included)
    x[np.flatnonzero(ids == 0)[0], 1] = np.nan
    out, st = R.reduce(jnp.asarray(x), segment_ids=jnp.asarray(ids),
                       num_segments=s, policy=policy, backend=backend,
                       block_size=bs, with_status=True)
    masked = jnp.where((mids >= 0)[:, None], jnp.asarray(x), 0.0)
    domain, ctx = pol.prepare(masked, n)
    want = pol.finalize(bk.run(domain, mids, s, policy=pol, block_size=bs),
                        ctx)
    assert np.array_equal(np.asarray(out).view(np.int32),
                          np.asarray(want).view(np.int32))
    assert bool(st.nonfinite) and int(st.kept_rows) == kept_rows


def _ldexp_domain(policy: str, v, ctx):
    """The integer tiers' domain maps written with ``ldexp``
    (``dequantize``, ``bin_split``), as the whole stream was mapped
    before the map moved into the block schedule."""
    if policy == "exact2":
        q = intac.quantize(v, ctx)
        res = v - intac.dequantize(q, ctx)
        digits = intac.bin_split(res * ctx, 0, bits=intac.RES_BIN_BITS,
                                 num=intac.RES_NUM_BINS)
        return jnp.concatenate([q.astype(jnp.float32)]
                               + [dg.astype(jnp.float32) for dg in digits],
                               axis=1)
    return jnp.concatenate(list(intac.bin_split(v, ctx)), axis=1)


def _exponent_sweep():
    """One 64-row stream per largest binade, subnormal to the top, with
    values spread 40 binades below it; then the edges: +-Inf, NaN, the
    largest finite value and subnormals."""
    rng = np.random.RandomState(0)
    for emax in range(-149, 128):
        e = emax - rng.randint(0, 40, size=64)
        x = np.sign(rng.randn(64)) * np.ldexp(rng.uniform(1, 2, 64), e)
        x[:3] = (np.ldexp(1.5, emax), 0.0, -0.0)
        yield x.astype(np.float32)
    big = np.finfo(np.float32).max
    for x in ([np.inf, 1.0], [-np.inf, 1.0, np.nan], [big, -big, 1.0, 3e-30],
              [2.0 ** -127, 2.0 ** -149, 3 * 2.0 ** -127]):
        yield np.asarray(x, np.float32)


@pytest.mark.parametrize("policy", ("exact2", "procrastinate"))
def test_map_rows_matches_the_ldexp_formulation(policy):
    """``map_rows`` multiplies by exact power-of-two factors computed
    once (``domain_args``) where the map used ``ldexp`` per element, so
    the kernel body can run it: the same domain bits, at every binade,
    through overflow, Inf and NaN."""
    pol = R.get_policy(policy)

    @jax.jit
    def both(x):
        v = x[:, None]
        ctx = pol.prepare_ctx(jnp.max(jnp.abs(v)), x.shape[0])
        return pol.to_domain(v, ctx), _ldexp_domain(policy, v, ctx)

    for x in _exponent_sweep():
        got, want = both(jnp.asarray(x))
        assert np.array_equal(np.asarray(got).view(np.int32),
                              np.asarray(want).view(np.int32)), x


SHARD_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro import reduce as R
from repro.core import intac
import sys
sys.path.insert(0, "@TESTDIR@")
from test_exact_residual import third_stream, cancellation_stream, _ulp

for name, stream in (("third", third_stream), ("cancel",
                                               cancellation_stream)):
    x = stream()
    ref = float(np.sum(x.astype(np.float64)))
    xj = jnp.asarray(x)
    pol = R.get_policy("exact2")
    domain, _ = pol.prepare(xj[:, None], len(x))
    ids = jnp.zeros(len(x), jnp.int32)
    base = R.get_backend("blocked").run(domain, ids, 1, policy=pol,
                                        block_size=512)
    lbase = [np.asarray(v)
             for v in intac.limbs_canonical(base[0], base[1])]
    for ndev in (1, 2, 8):
        mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("shards",))
        out = float(R.reduce(xj, policy="exact2", backend="shard_map",
                             mesh=mesh))
        csh = R.get_backend("shard_map").run(domain, ids, 1, policy=pol,
                                             block_size=512, mesh=mesh)
        lsh = intac.limbs_canonical(csh[0], csh[1])
        limbs_ok = all(np.array_equal(a, np.asarray(b))
                       for a, b in zip(lbase, lsh))
        ok = abs(out - ref) <= _ulp(ref)
        print(f"SHARD {name} {ndev} {int(ok)} {int(limbs_ok)}")
"""


def test_residual_limb_within_1ulp_through_shard_map():
    """The fix across the mesh: <= 1 ulp vs f64 at N=2^20 through the
    shard_map backend at 1/2/8 simulated devices, with the canonical
    integer limbs bitwise identical to the single-device schedule."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    snippet = SHARD_SNIPPET.replace("@TESTDIR@", str(REPO / "tests"))
    r = subprocess.run([sys.executable, "-c", snippet],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    rows = [ln.split() for ln in r.stdout.strip().splitlines()
            if ln.startswith("SHARD")]
    assert len(rows) == 6
    for _, name, ndev, ok, limbs_ok in rows:
        assert ok == "1", (name, ndev)
        assert limbs_ok == "1", (name, ndev)
