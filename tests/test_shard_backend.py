"""shard_map backend tests: the multi-device face of repro.reduce.

The tentpole contract: the shard_map backend runs the identical block
schedule, so the integer tiers (exact / exact2 / procrastinate) are
bitwise identical to the single-device ``blocked`` schedule at any shard
count, for uneven N, and under permutation of shards; the float tiers
hold documented tolerance.  Multi-device cases run in a subprocess with
8 simulated CPU devices (XLA_FLAGS must be set before jax initializes);
everything else runs in-process on whatever devices exist.
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

REPO = Path(__file__).resolve().parent.parent
POLICIES = ("fast", "compensated", "exact", "exact2", "procrastinate")
INT_POLICIES = ("exact", "exact2", "procrastinate")
#: tiers whose *finalized float* is bitwise at any shard count — every
#: integer tier: all carry state (exact's int32 sum, exact2's limbs +
#: binned residual digits, procrastinate's bins) adds associatively and
#: finalizes canonically
BITWISE_POLICIES = ("exact", "exact2", "procrastinate")


def _data(n=700, d=8, s=5, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, d).astype(np.float32)),
            jnp.asarray(rng.randint(0, s, n)))


# ---------------------------------------------------------------------------
# in-process: registry, plumbing, and the 1-shard degenerate case
# ---------------------------------------------------------------------------


def test_backend_registered_with_capabilities():
    bk = R.get_backend("shard_map")
    assert bk.distributed
    assert all(bk.supports(R.get_policy(p)) for p in POLICIES)
    # single-device backends reject the mesh plumbing
    assert not R.get_backend("blocked").distributed


@pytest.mark.parametrize("policy", POLICIES)
def test_one_shard_is_bitwise_the_blocked_schedule(policy):
    """With one shard the carry merge is an identity, so even the float
    tiers must reproduce the blocked backend exactly."""
    vals, ids = _data()
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    a = R.reduce(vals, segment_ids=ids, num_segments=5, policy=policy,
                 backend="shard_map", mesh=mesh, block_size=128)
    b = R.reduce(vals, segment_ids=ids, num_segments=5, policy=policy,
                 backend="blocked", block_size=128)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_mean_and_sentinel_through_shard_map():
    vals = jnp.asarray([2.0, 4.0, 100.0])
    ids = jnp.asarray([0, 0, R.OUT_OF_RANGE_LABEL])
    out = R.reduce(vals, segment_ids=ids, num_segments=1, op="mean",
                   backend="shard_map")
    assert float(out[0]) == 3.0


def test_mesh_kwarg_validation():
    with pytest.raises(ValueError, match="single-device"):
        R.reduce(jnp.ones(4), backend="blocked", mesh=R.default_mesh())
    with pytest.raises(ValueError, match="axis_names"):
        R.reduce(jnp.ones(4), backend="shard_map", mesh=R.default_mesh(),
                 axis_names=("nonexistent",))
    # distributed intent stated via axis_names must never silently fall
    # back to a single-device reduction under auto-selection
    if len(jax.devices()) == 1:
        with pytest.raises(ValueError, match="axis_names"):
            R.reduce(jnp.ones(4), axis_names=("shards",))


def test_ambient_mesh_detection():
    assert R.ambient_mesh() is None
    with jax.set_mesh(R.default_mesh()):
        amb = R.ambient_mesh()
        assert amb is not None and tuple(amb.axis_names) == ("shards",)
    assert R.ambient_mesh() is None


AMBIENT_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro import reduce as R

pol = R.get_policy("exact2")
mesh = R.default_mesh()
print("OUTSIDE", R.select_backend(pol).name)
with jax.set_mesh(mesh):
    print("AMBIENT", R.select_backend(pol).name, mesh.size)
    print("TRACED", R.select_backend(pol, traced=True).name)
    x = jnp.arange(4096.0).reshape(512, 8)
    auto = np.asarray(R.reduce(x, policy="exact2"))
base = np.asarray(R.reduce(x, policy="exact2", backend="blocked"))
print("BITS", int(np.array_equal(auto, base)))
"""


def test_select_backend_picks_shard_map_under_ambient_mesh():
    """``with jax.set_mesh(mesh):`` over 8 virtual CPU devices steers
    auto-selection to shard_map for concrete arrays (never for traced
    values), and the sharded result keeps exact2's bits."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", AMBIENT_SNIPPET],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    got = dict(ln.split(None, 1) for ln in r.stdout.strip().splitlines())
    assert got["OUTSIDE"] == "blocked"
    assert got["AMBIENT"] == "shard_map 8"
    assert got["TRACED"] == "blocked"
    assert got["BITS"] == "1"


@pytest.mark.parametrize("policy", POLICIES)
def test_policy_merge_is_the_schedule_split(policy):
    """``merge(fold(blocks[:k]), fold(blocks[k:]))`` equals
    ``fold(blocks)`` — bitwise for the integer tiers (their carries add
    associatively), tolerance for the float tiers.  This is the local
    statement of the combiner contract the shard_map backend relies on."""
    pol = R.get_policy(policy)
    vals, ids = _data(n=512, d=4, s=3, seed=2)
    ids = R.mask_out_of_range(ids, 3)
    domain, ctx = pol.prepare(vals, 512)
    bk = R.get_backend("blocked")
    full = bk.run(domain, ids, 3, policy=pol, block_size=64)
    ca = bk.run(domain[:256], ids[:256], 3, policy=pol, block_size=64)
    cb = bk.run(domain[256:], ids[256:], 3, policy=pol, block_size=64)
    merged = pol.merge(ca, cb)
    out_full = np.asarray(pol.finalize(full, ctx))
    out_merged = np.asarray(pol.finalize(merged, ctx))
    if policy in BITWISE_POLICIES:
        assert np.array_equal(out_full, out_merged)
        if policy == "exact2":
            # the canonical integer limbs are bitwise equal too
            for a, b in zip(intac.limbs_canonical(full[0], full[1]),
                            intac.limbs_canonical(merged[0], merged[1])):
                assert np.array_equal(np.asarray(a), np.asarray(b))
    else:
        np.testing.assert_allclose(out_merged, out_full, rtol=1e-6,
                                   atol=1e-6)
    assert pol.merge_is_add == (policy != "compensated")


def test_merge_across_accumulator_single_device():
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("shards",))
    from jax.sharding import PartitionSpec as P
    acc = R.CascadeAccumulator(1)
    x = jnp.asarray([1.5, 2.5])

    def f(v):
        st = acc.push(acc.init(v), v)
        return acc.finalize(R.merge_across(acc, st, mesh.axis_names))[0]

    out = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                        check_vma=False)(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_train_step_grad_reduce_routes_through_front_door():
    """``make_train_step(..., grad_reduce="exact2")`` reduces the stacked
    microbatch gradients through repro.reduce: the step must be
    call-to-call deterministic and track the pairing-tree step closely."""
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.optim import adamw
    from repro.train.steps import make_train_step

    cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    opt = adamw.init(params)
    lr_fn = adamw.cosine_schedule(1e-3, 2, 20)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                          0, cfg.vocab)}
    kw = dict(lr_fn=lr_fn, remat=False, moe_impl="dense",
              num_microbatches=2)
    s_tree = jax.jit(make_train_step(cfg, **kw))
    s_exact = jax.jit(make_train_step(cfg, grad_reduce="exact2", **kw))
    p1, _, m1 = s_tree(params, opt, batch)
    p2, _, m2 = s_exact(params, opt, batch)
    p2b, _, _ = s_exact(params, opt, batch)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(p2), jax.tree.leaves(p2b)))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    num = sum(float(jnp.sum((a - b) ** 2)) for a, b in
              zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    den = sum(float(jnp.sum((a - b) ** 2)) for a, b in
              zip(jax.tree.leaves(params), jax.tree.leaves(p1)))
    assert num / max(den, 1e-30) < 1e-3


# ---------------------------------------------------------------------------
# multi-device: 1/2/8 simulated devices in a subprocess
# ---------------------------------------------------------------------------

MULTIDEV_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro import reduce as R
from repro.core import intac

rng = np.random.RandomState(0)
n, d, s, bs = 1000, 16, 7, 128            # uneven: 1000 % (8*128) != 0
vals = jnp.asarray(rng.randn(n, d).astype(np.float32))
ids = jnp.asarray(rng.randint(0, s, n))

for pol in ("fast", "compensated", "exact", "exact2", "procrastinate"):
    base = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                               policy=pol, backend="blocked",
                               block_size=bs))
    scale = float(np.abs(base).max())
    for ndev in (1, 2, 8):
        mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("shards",))
        out = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                                  policy=pol, backend="shard_map",
                                  mesh=mesh, block_size=bs))
        bit = int(np.array_equal(base, out))
        rel = float(np.abs(base - out).max()) / scale
        print(f"GRID {pol} {ndev} {bit} {rel:.3e}")

# exact2's integer-limb half of the split guarantee: the canonical hi/lo
# limbs out of the shard_map backend are bitwise identical to the blocked
# schedule at every shard count
pol2 = R.get_policy("exact2")
mids = R.mask_out_of_range(ids, s)
mvals = jnp.where((mids >= 0)[:, None], vals, 0.0)
domain, ctx = pol2.prepare(mvals, n)
cbase = R.get_backend("blocked").run(domain, mids, s, policy=pol2,
                                     block_size=bs)
lbase = [np.asarray(c) for c in intac.limbs_canonical(cbase[0], cbase[1])]
for ndev in (1, 2, 8):
    mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("shards",))
    csh = R.get_backend("shard_map").run(domain, mids, s, policy=pol2,
                                         block_size=bs, mesh=mesh)
    lsh = intac.limbs_canonical(csh[0], csh[1])
    ok = all(np.array_equal(a, np.asarray(b)) for a, b in zip(lbase, lsh))
    print(f"LIMBS {ndev} {int(ok)}")

# ProcrastinatePolicy's carry merges by addition: merge_across must take
# the fused psum and match one device's pass over all 8 rows bit for bit
from jax.sharding import PartitionSpec as P
meshA = Mesh(np.asarray(jax.devices()), ("data",))
xa = jnp.asarray((np.arange(8 * 4).reshape(8, 4) % 7 - 3) * 0.25,
                 dtype=jnp.float32)
polP = R.get_policy("procrastinate")
ctxP = polP.prepare_ctx(jnp.float32(8.0), 8)
def carry_of(rows):
    dom = polP.to_domain(rows, ctxP)
    return R.get_backend("blocked").run(dom, jnp.zeros(rows.shape[0],
                                                       jnp.int32), 1,
                                        policy=polP, block_size=8)
def accf(shard):
    return polP.finalize(polP.merge_across(carry_of(shard), ("data",)),
                         ctxP)
got = np.asarray(jax.shard_map(accf, mesh=meshA, in_specs=P("data", None),
                               out_specs=P(), check_vma=False)(xa))
direct = np.asarray(polP.finalize(carry_of(xa), ctxP))
print(f"BINACC {int(np.array_equal(got, direct))}")

# permutation of shards: swap whole shard-sized row chunks; the bitwise
# tiers must not notice (associative + commutative integer carries);
# exact2's finalized float re-folds its residual limb in the new order —
# ulp-level tolerance, with bitwise-equal canonical integer limbs
mesh8 = Mesh(np.asarray(jax.devices()), ("shards",))
npad = 1024                                # 8 shards x 1 block of 128
vp = jnp.asarray(rng.randn(npad, d).astype(np.float32))
ip = jnp.asarray(rng.randint(0, s, npad))
perm = rng.permutation(8)
chunks = np.arange(npad).reshape(8, -1)[perm].reshape(-1)
for pol in ("exact", "exact2", "procrastinate"):
    a = np.asarray(R.reduce(vp, segment_ids=ip, num_segments=s,
                            policy=pol, backend="shard_map", mesh=mesh8,
                            block_size=bs))
    b = np.asarray(R.reduce(vp[chunks], segment_ids=ip[chunks],
                            num_segments=s, policy=pol,
                            backend="shard_map", mesh=mesh8,
                            block_size=bs))
    rel = float(np.abs(a - b).max()) / max(float(np.abs(a).max()), 1e-30)
    print(f"PERM {pol} {int(np.array_equal(a, b))} {rel:.3e}")

# the staged program's lane-parallel contrib through shard_map: forcing
# contrib="lanes" swaps the gather form on every shard, and for the
# integer tiers that must not change a single bit vs the blocked dot
# schedule, at any shard count
for pol in ("exact", "exact2", "procrastinate"):
    base = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                               policy=pol, backend="blocked",
                               block_size=bs))
    for ndev in (1, 2, 8):
        mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("shards",))
        out = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                                  policy=pol, backend="shard_map",
                                  mesh=mesh, block_size=bs,
                                  contrib="lanes"))
        print(f"LANES {pol} {ndev} {int(np.array_equal(base, out))}")

# block-size sweep at 8 shards: the bitwise tiers may not notice the
# schedule's block granularity either
for pol in ("exact", "exact2", "procrastinate"):
    outs = [np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                                policy=pol, backend="shard_map",
                                mesh=mesh8, block_size=b2))
            for b2 in (64, 128, 256)]
    ok = all(np.array_equal(outs[0], o) for o in outs[1:])
    print(f"BSWEEP {pol} {int(ok)}")

# auto-selection under an ambient multi-device mesh, bitwise vs blocked
with jax.set_mesh(mesh8):
    assert R.select_backend(R.get_policy("exact")).name == "shard_map"
    auto = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                               policy="exact", block_size=bs))
base = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                           policy="exact", backend="blocked",
                           block_size=bs))
print(f"AUTO {int(np.array_equal(auto, base))}")

# a 2D mesh, sharding over both axes jointly
mesh2d = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("dp", "mp"))
out2d = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                            policy="procrastinate", backend="shard_map",
                            mesh=mesh2d, block_size=bs))
base2d = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=s,
                             policy="procrastinate", backend="blocked",
                             block_size=bs))
print(f"MESH2D {int(np.array_equal(out2d, base2d))}")

# the training route: make_train_step(grad_reduce="exact2",
# grad_reduce_mesh=<8-dev mesh>) routes the microbatch-gradient mean
# through shard_map; the integer limbs are executor-invariant and the
# residual limb holds ulp-level tolerance, so the mesh-built step must
# track the local-executor build to float tolerance through a whole step
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.optim import adamw
from repro.train.steps import make_train_step
cfg = get_smoke_config("stablelm-1.6b")
params = init_params(jax.random.PRNGKey(0), cfg)
opt = adamw.init(params)
kw = dict(lr_fn=adamw.cosine_schedule(1e-3, 2, 20), remat=False,
          moe_impl="dense", num_microbatches=2, grad_reduce="exact2")
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 32),
                                      0, cfg.vocab)}
p1, _, _ = jax.jit(make_train_step(cfg, grad_reduce_mesh=mesh8,
                                   **kw))(params, opt, batch)
p0, _, _ = jax.jit(make_train_step(cfg, **kw))(params, opt, batch)
close = all(np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                        rtol=1e-5, atol=1e-6)
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p0)))
print(f"TRAINSTEP {int(close)}")
"""


def test_multidevice_bitwise_invariance():
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", MULTIDEV_SNIPPET],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = [ln.split() for ln in r.stdout.strip().splitlines()]
    grid = {(p, int(nd)): (int(bit), float(rel))
            for _, p, nd, bit, rel in
            (ln for ln in lines if ln[0] == "GRID")}
    assert len(grid) == 15
    for (pol, ndev), (bit, rel) in grid.items():
        if pol in BITWISE_POLICIES or ndev == 1:
            assert bit == 1, (pol, ndev)        # bitwise, any shard count
        elif pol == "exact2":
            # residual limb folds in device order: ulp-level, not bitwise
            # (the integer limbs are checked bitwise by LIMBS below)
            assert rel < 1e-6, (pol, ndev, rel)
        else:
            assert rel < 1e-5, (pol, ndev, rel)   # documented tolerance
    limbs = {int(nd): int(ok) for tag, nd, ok in
             (ln for ln in lines if ln[0] == "LIMBS")}
    assert limbs == {1: 1, 2: 1, 8: 1}
    perms = {p: (int(bit), float(rel)) for tag, p, bit, rel in
             (ln for ln in lines if ln[0] == "PERM")}
    for p in BITWISE_POLICIES:
        assert perms[p][0] == 1, p
    assert perms["exact2"][1] < 1e-6
    lanes = {(p, int(nd)): int(ok) for tag, p, nd, ok in
             (ln for ln in lines if ln[0] == "LANES")}
    assert len(lanes) == 9
    assert all(ok == 1 for ok in lanes.values()), lanes
    bsweep = {p: int(ok) for tag, p, ok in
              (ln for ln in lines if ln[0] == "BSWEEP")}
    assert bsweep == {p: 1 for p in BITWISE_POLICIES}
    tags = [(ln[0], ln[1]) for ln in lines]
    assert ("AUTO", "1") in tags
    assert ("MESH2D", "1") in tags
    assert ("TRAINSTEP", "1") in tags
    assert ("BINACC", "1") in tags
