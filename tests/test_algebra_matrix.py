"""Full-matrix coverage for the reduction-algebra ops (ISSUE 9).

The algebra's ``pre`` hook runs once, above every backend and policy, so
each new op must inherit the whole determinism contract for free:

  * backend invariance — ref / blocked / pallas produce *bitwise*
    identical results for every op x policy cell (mirroring
    test_reduce.test_segmented_backends_bitwise_equal);
  * block-size invariance — the integer tiers are bitwise across the
    block-size sweep for every op;
  * shard invariance — the integer tiers are bitwise at 1 / 2 / 8
    simulated devices (subprocess, test_shard_backend pattern);
  * the in-model dogfood knobs default to off (bitwise-legacy) and are
    deterministic when on;
  * the front door validates op arguments loudly.
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

REPO = Path(__file__).resolve().parent.parent
BACKENDS = ("ref", "blocked", "pallas")
POLICIES = ("fast", "compensated", "exact", "exact2", "procrastinate")
INT_POLICIES = ("exact", "exact2", "procrastinate")
NEW_OPS = ("weighted_sum", "sumsq", "moments", "poly")


def _data(n=420, d=6, s=5, seed=0):
    rng = np.random.RandomState(seed)
    vals = jnp.asarray(rng.randn(n, d).astype(np.float32))
    ids = jnp.asarray(rng.randint(-1, s, n))        # sentinel rows included
    w = jnp.asarray(rng.uniform(-2, 2, n).astype(np.float32))
    return vals, ids, w


def _kwargs(op, w):
    if op == "weighted_sum":
        return {"weights": w}
    if op == "poly":
        return {"coeffs": (1.0, 0.5, -0.25)}
    return {}


# ---------------------------------------------------------------------------
# backend x op x policy: bitwise across executors
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("op", NEW_OPS)
def test_op_backends_bitwise_equal(op, policy):
    vals, ids, w = _data()
    outs = [np.asarray(R.reduce(vals, segment_ids=ids, num_segments=5,
                                op=op, policy=policy, backend=b,
                                block_size=64, **_kwargs(op, w)))
            for b in BACKENDS]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o), (op, policy)
    if op == "moments":
        assert outs[0].shape == (5, 2, 6)


@pytest.mark.parametrize("policy", INT_POLICIES)
@pytest.mark.parametrize("op", NEW_OPS)
def test_op_block_size_sweep_bitwise(op, policy):
    vals, ids, w = _data(seed=3)
    outs = [np.asarray(R.reduce(vals, segment_ids=ids, num_segments=5,
                                op=op, policy=policy, backend="blocked",
                                block_size=bs, **_kwargs(op, w)))
            for bs in (32, 64, 256)]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o), (op, policy)


@pytest.mark.parametrize("op", NEW_OPS)
def test_op_oracle_f64(op):
    """Every cell of the matrix tracks the f64 oracle (exact2 shown;
    the cross-backend tests pin the other tiers to this one)."""
    vals, ids, w = _data(seed=5)
    out = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=5,
                              op=op, policy="exact2", backend="blocked",
                              block_size=64, **_kwargs(op, w)))
    v = np.asarray(vals, np.float64)
    i = np.asarray(ids)
    keep = i >= 0
    if op == "weighted_sum":
        v = v * np.asarray(w, np.float64)[:, None]
    elif op == "sumsq":
        v = v * v
    elif op == "poly":
        c = _kwargs(op, w)["coeffs"]
        t = np.arange(len(v), dtype=np.float64)
        v = v * sum(cc * t ** p for p, cc in enumerate(c))[:, None]
    if op == "moments":
        ref = np.zeros((5, 2, v.shape[1]))
        for seg in range(5):
            rows = v[keep & (i == seg)]
            if len(rows):
                ref[seg, 0] = rows.mean(0)
                ref[seg, 1] = rows.var(0)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-5)
    else:
        ref = np.zeros((5, v.shape[1]))
        np.add.at(ref, i[keep], v[keep])
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# shard_map: 1 / 2 / 8 simulated devices, bitwise for the integer tiers
# ---------------------------------------------------------------------------

MULTIDEV_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro import reduce as R

rng = np.random.RandomState(0)
n, d, s, bs = 900, 8, 5, 128              # uneven: 900 % (8*128) != 0
vals = jnp.asarray(rng.randn(n, d).astype(np.float32))
ids = jnp.asarray(rng.randint(-1, s, n))
w = jnp.asarray(rng.uniform(-2, 2, n).astype(np.float32))

def kwargs(op):
    if op == "weighted_sum":
        return {"weights": w}
    if op == "poly":
        return {"coeffs": (1.0, 0.5)}
    return {}

for op in ("weighted_sum", "sumsq", "moments", "poly"):
    for pol in ("fast", "compensated", "exact", "exact2", "procrastinate"):
        base = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                                   op=op, policy=pol, backend="blocked",
                                   block_size=bs, **kwargs(op)))
        scale = max(float(np.abs(base).max()), 1e-30)
        for ndev in (1, 2, 8):
            mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("shards",))
            out = np.asarray(R.reduce(vals, segment_ids=ids,
                                      num_segments=s, op=op, policy=pol,
                                      backend="shard_map", mesh=mesh,
                                      block_size=bs, **kwargs(op)))
            bit = int(np.array_equal(base, out))
            rel = float(np.abs(base - out).max()) / scale
            print(f"GRID {op} {pol} {ndev} {bit} {rel:.3e}")

# collective companions of the new ops
from jax.sharding import PartitionSpec as P
mesh8 = Mesh(np.asarray(jax.devices()), ("data",))
x8 = jnp.asarray(rng.randn(8, 16).astype(np.float32))
w8 = jnp.asarray(rng.uniform(0.1, 2.0, (8, 16)).astype(np.float32))

def wmean(xs, ws):
    return R.collective_weighted_mean(xs, ws, ("data",), policy="exact2")
got = np.asarray(jax.shard_map(wmean, mesh=mesh8,
                               in_specs=(P("data"), P("data")), out_specs=P(),
                               check_vma=False)(x8, w8))[0]
xf = np.asarray(x8, np.float64)
wf = np.asarray(w8, np.float64)
ref = (xf * wf).sum(0) / wf.sum(0)        # per-element, over the device axis
print(f"WMEAN {int(np.allclose(got, ref, rtol=1e-4, atol=1e-5))}")

def moms(xs):
    return R.collective_moments(xs, ("data",), policy="exact2")
m1, var = jax.shard_map(moms, mesh=mesh8, in_specs=P("data"),
                        out_specs=(P(), P()), check_vma=False)(x8)
ok = (np.allclose(np.asarray(m1)[0], xf.mean(0), rtol=1e-4, atol=1e-5)
      and np.allclose(np.asarray(var)[0], xf.var(0), rtol=1e-3, atol=1e-4)
      and (np.asarray(var) >= 0.0).all())
print(f"CMOMS {int(ok)}")
"""


def test_multidevice_op_invariance():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SNIPPET],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln.split() for ln in r.stdout.strip().splitlines()]
    grid = {(op, p, int(nd)): (int(bit), float(rel))
            for _, op, p, nd, bit, rel in
            (ln for ln in lines if ln[0] == "GRID")}
    assert len(grid) == len(NEW_OPS) * len(POLICIES) * 3
    for (op, pol, ndev), (bit, rel) in grid.items():
        if pol in INT_POLICIES or ndev == 1:
            assert bit == 1, (op, pol, ndev)    # bitwise at any shard count
        else:
            assert rel < 1e-5, (op, pol, ndev, rel)
    tags = [(ln[0], ln[1]) for ln in lines]
    assert ("WMEAN", "1") in tags
    assert ("CMOMS", "1") in tags


# ---------------------------------------------------------------------------
# dogfood: the in-model call sites and their knobs
# ---------------------------------------------------------------------------


def test_dogfood_knobs_default_off():
    """Stock configs must keep every algebra knob at None, so mainline
    serving/training output is bitwise the pre-algebra path."""
    from repro.configs import all_configs
    for arch, cfg in all_configs().items():
        assert cfg.norm_reduce_policy is None, arch
        if cfg.moe is not None:
            assert cfg.moe.router_norm_policy is None, arch


def test_rmsnorm_knob_off_is_bitwise_legacy():
    from repro.models.layers import rmsnorm
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 5, 32).astype(np.float32))
    g = jnp.asarray(rng.randn(32).astype(np.float32))
    got = np.asarray(rmsnorm(g, x))
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    ref = (xf * jax.lax.rsqrt(var + 1e-5)).astype(x.dtype) * g
    assert np.array_equal(got, np.asarray(ref))


@pytest.mark.parametrize("policy", ("fast", "exact2"))
def test_rmsnorm_knob_on_close_and_deterministic(policy):
    from repro.models.layers import rmsnorm
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 7, 64).astype(np.float32))
    g = jnp.asarray(rng.randn(64).astype(np.float32))
    a = np.asarray(rmsnorm(g, x, policy=policy))
    b = np.asarray(rmsnorm(g, x, policy=policy))
    assert np.array_equal(a, b)
    jitted = np.asarray(jax.jit(
        lambda gg, xx: rmsnorm(gg, xx, policy=policy))(g, x))
    assert np.array_equal(a, jitted)
    np.testing.assert_allclose(a, np.asarray(rmsnorm(g, x)),
                               rtol=1e-4, atol=1e-5)


def test_global_norm_policy_matches_legacy():
    from repro.optim import adamw
    rng = np.random.RandomState(2)
    tree = {"a": jnp.asarray(rng.randn(37, 5).astype(np.float32)),
            "b": [jnp.asarray(rng.randn(2049).astype(np.float32)),
                  jnp.asarray(rng.randn(3).astype(np.float32)
                              ).astype(jnp.bfloat16)]}
    legacy = float(adamw.global_norm(tree))
    for pol in ("fast", "exact2"):
        got = float(adamw.global_norm(tree, policy=pol))
        assert got == pytest.approx(legacy, rel=1e-5), pol
        jitted = float(jax.jit(
            lambda t: adamw.global_norm(t, policy=pol))(tree))
        assert jitted == pytest.approx(got, rel=0, abs=0)


def test_router_norm_policy_matches_legacy():
    from repro.models.config import MoECfg
    from repro.models.moe import router_topk
    import dataclasses
    rng = np.random.RandomState(3)
    m = MoECfg(num_experts=8, top_k=2, d_ff_expert=16,
               router_norm_topk=True)
    router_w = jnp.asarray(rng.randn(32, 8).astype(np.float32))
    x = jnp.asarray(rng.randn(24, 32).astype(np.float32))
    w0, i0, a0 = router_topk(router_w, x, m)
    mp = dataclasses.replace(m, router_norm_policy="exact2")
    w1, i1, a1 = router_topk(router_w, x, mp)
    assert np.array_equal(np.asarray(i0), np.asarray(i1))
    assert float(a0) == float(a1)
    np.testing.assert_allclose(np.asarray(w0), np.asarray(w1),
                               rtol=1e-5, atol=1e-7)
    row_sums = np.asarray(w1).sum(-1)
    np.testing.assert_allclose(row_sums, 1.0, rtol=1e-4)


def test_model_forward_with_knobs_on_deterministic_and_close():
    from repro.configs import get_smoke_config
    from repro.models import forward, init_params
    import dataclasses
    cfg = get_smoke_config("deepseek-7b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                cfg.vocab)
    base, _, _, _ = forward(params, cfg, tokens=tokens, mode="train")
    cfg_on = dataclasses.replace(cfg, norm_reduce_policy="exact2")
    on1, _, _, _ = forward(params, cfg_on, tokens=tokens, mode="train")
    on2, _, _, _ = forward(params, cfg_on, tokens=tokens, mode="train")
    assert np.array_equal(np.asarray(on1, np.float32),
                          np.asarray(on2, np.float32))
    np.testing.assert_allclose(np.asarray(on1, np.float32),
                               np.asarray(base, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_train_step_norm_policy_runs():
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.optim import adamw
    from repro.train.steps import make_train_step
    cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                          0, cfg.vocab)}
    kw = dict(lr_fn=adamw.cosine_schedule(1e-3, 2, 20), remat=False,
              moe_impl="dense")
    p0, _, m0 = jax.jit(make_train_step(cfg, **kw))(params, opt, batch)
    p1, _, m1 = jax.jit(make_train_step(cfg, norm_policy="exact2",
                                        **kw))(params, opt, batch)
    assert float(m1["grad_norm"]) == pytest.approx(float(m0["grad_norm"]),
                                                   rel=1e-5)
    num = sum(float(jnp.sum((jnp.asarray(a, jnp.float32)
                             - jnp.asarray(b, jnp.float32)) ** 2))
              for a, b in zip(jax.tree.leaves(p0), jax.tree.leaves(p1)))
    den = sum(float(jnp.sum(jnp.asarray(a, jnp.float32) ** 2))
              for a in jax.tree.leaves(p0))
    assert num / max(den, 1e-30) < 1e-8


# ---------------------------------------------------------------------------
# front-door validation
# ---------------------------------------------------------------------------


def test_unknown_op_rejected_with_registry_listing():
    with pytest.raises(ValueError, match="weighted_sum"):
        R.reduce(jnp.ones(4), op="median")


def test_weighted_sum_requires_weights():
    with pytest.raises(ValueError, match="weights"):
        R.reduce(jnp.ones(4), op="weighted_sum")


def test_poly_requires_coeffs():
    with pytest.raises(ValueError, match="coeffs"):
        R.reduce(jnp.ones(4), op="poly")


def test_weights_on_weightless_op_rejected():
    with pytest.raises(ValueError, match="weights"):
        R.reduce(jnp.ones(4), op="sum", weights=jnp.ones(4))


def test_coeffs_on_coeffless_op_rejected():
    with pytest.raises(ValueError, match="coeffs"):
        R.reduce(jnp.ones(4), op="sum", coeffs=(1.0, 2.0))


def test_weights_shape_validated():
    with pytest.raises(ValueError, match="weights"):
        R.reduce(jnp.ones((4, 2)), op="weighted_sum", weights=jnp.ones(3))
    with pytest.raises(ValueError, match="weights"):
        R.reduce(jnp.ones((4, 2)), op="weighted_sum",
                 weights=jnp.ones((4, 2)))
