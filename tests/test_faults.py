"""Fault-injection suite: every injected failure is detected, degraded,
or recovered — never silent corruption.

The injectors live in ``repro.testing.faults``; the failure modes and the
contracts asserted here are documented in docs/robustness.md:

  * NaN/Inf payload bursts — sentinel-dropped rows provably never poison
    any tier (bitwise-equal to the clean run); kept-row bursts trip
    ``ReduceStatus.nonfinite``.
  * Overflow guard rails — ``on_overflow="degrade"`` chunks over-bound
    streams and escalates saturated tiers; a saturated tier with no
    escalation raises instead of returning garbage.
  * Checkpoint bit flips / truncation — caught by the CRC sidecars as a
    structured ``CheckpointError``; ``restore_latest_valid`` falls back
    to the newest verifying step.
  * Kill-mid-save — a real subprocess dies at the atomic-rename point;
    the orphaned ``.tmp`` directory is never restored from.
  * Shard dropout — a lost carry in ``merge_carry_across`` degrades to
    exactly the reduction over the surviving shards (bitwise).
  * Elastic resume — train on 2 emulated devices, checkpoint, resume on
    8: bit-identical params and losses vs the uninterrupted run.
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
from repro.ckpt import checkpoint as ckpt
from repro.testing import faults

REPO = Path(__file__).resolve().parent.parent

pytestmark = pytest.mark.faults

POLICIES = ("fast", "compensated", "exact", "exact2", "procrastinate")


# ---------------------------------------------------------------------------
# NaN/Inf payload bursts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("kind", ("nan", "inf", "both"))
def test_nonfinite_in_dropped_rows_never_poisons(policy, kind):
    """The guarantee is bitwise: a reduction whose *dropped* rows carry
    NaN/Inf payloads returns the exact bits of the clean run, on every
    tier — the sentinel zeroing happens before any policy sees the
    payloads — and does not trip the nonfinite flag."""
    rng = np.random.RandomState(0)
    x = rng.randn(256, 4).astype(np.float32)
    ids = rng.randint(0, 5, 256).astype(np.int32)
    burst = np.arange(0, 256, 7)
    ids[burst] = R.OUT_OF_RANGE_LABEL
    clean = R.reduce(jnp.asarray(x), segment_ids=jnp.asarray(ids),
                     num_segments=5, policy=policy)
    poisoned = faults.inject_nonfinite(x, rows=burst, kind=kind)
    out, st = R.reduce(jnp.asarray(poisoned), segment_ids=jnp.asarray(ids),
                       num_segments=5, policy=policy, with_status=True)
    assert np.array_equal(np.asarray(clean), np.asarray(out))
    assert np.isfinite(np.asarray(out)).all()
    assert not bool(st.nonfinite)
    assert int(st.kept_rows) == int((ids >= 0).sum())


def test_nonfinite_in_kept_rows_trips_the_flag():
    x = faults.inject_nonfinite(np.ones((8, 2), np.float32), rows=[3],
                                kind="nan")
    out, st = R.reduce(jnp.asarray(x), segment_ids=jnp.zeros(8, np.int32),
                       num_segments=1, policy="fast", with_status=True)
    assert bool(st.nonfinite)
    assert int(st.kept_rows) == 8


def test_with_status_is_jittable_and_free_flags_are_false():
    out, st = jax.jit(
        lambda v: R.reduce(v, policy="exact2", with_status=True))(
            jnp.arange(8.0))
    assert float(out) == 28.0
    assert not bool(st.nonfinite) and not bool(st.saturated)
    assert not bool(st.degraded) and int(st.kept_rows) == 8


# ---------------------------------------------------------------------------
# overflow guard rails: degrade instead of garbage
# ---------------------------------------------------------------------------


def test_degrade_chunks_over_bound_streams():
    """A stream past the block-count headroom bound raises under the
    default, and under ``degrade`` splits into bound-sized chunks folded
    with a compensated accumulator — correct result, flagged."""
    n = (1 << 21) + 3
    x = jnp.ones(n)
    with pytest.raises(ValueError, match="blocks"):
        R.reduce(x, policy="exact2", block_size=64)
    out, st = R.reduce(x, policy="exact2", block_size=64,
                       on_overflow="degrade", with_status=True)
    assert float(out) == float(n)
    assert bool(st.degraded) and not bool(st.saturated)
    assert int(st.kept_rows) == n


def test_saturation_escalates_to_the_next_tier():
    """A tier reporting carry saturation re-runs through its declared
    ``escalation`` tier; the result is the stronger tier's bits and
    ``ReduceStatus.degraded`` records the swap."""
    ExactCls = type(R.get_policy("exact"))

    @R.register_policy
    class _AlwaysSaturated(ExactCls):
        name = "always_saturated"
        escalation = "exact2"

        def carry_status(self, carry):
            return jnp.asarray(True)

    try:
        x = jnp.asarray(np.random.RandomState(2).randn(64)
                        .astype(np.float32))
        ref = float(R.reduce(x, policy="exact2"))
        out, st = R.reduce(x, policy="always_saturated",
                           on_overflow="degrade", with_status=True)
        assert float(out) == ref
        assert bool(st.degraded)
    finally:
        R.POLICIES.pop("always_saturated", None)


def test_saturation_with_no_escalation_raises():
    ExactCls = type(R.get_policy("exact"))

    @R.register_policy
    class _DeadEnd(ExactCls):
        name = "dead_end_saturated"
        escalation = None

        def carry_status(self, carry):
            return jnp.asarray(True)

    try:
        with pytest.raises(OverflowError, match="no stronger tier"):
            R.reduce(jnp.ones(16), policy="dead_end_saturated",
                     on_overflow="degrade")
    finally:
        R.POLICIES.pop("dead_end_saturated", None)


def test_degrade_is_eager_only():
    with pytest.raises(ValueError, match="eager-only"):
        jax.jit(lambda v: R.reduce(v, on_overflow="degrade"))(jnp.ones(4))


# ---------------------------------------------------------------------------
# reduction-algebra ops under the same guard rails (ISSUE 9)
# ---------------------------------------------------------------------------


def test_nonfinite_weight_in_kept_rows_trips_the_flag():
    """The algebra's ``pre`` multiplies before any policy sees the rows,
    so a NaN *weight* on a kept row poisons the transformed stream the
    same way a NaN value would — and the status flag must say so."""
    w = np.ones(8, np.float32)
    w[3] = np.nan
    out, st = R.reduce(jnp.ones((8, 2)), segment_ids=jnp.zeros(8, jnp.int32),
                       num_segments=1, op="weighted_sum",
                       weights=jnp.asarray(w), policy="fast",
                       with_status=True)
    assert bool(st.nonfinite)
    assert int(st.kept_rows) == 8


@pytest.mark.parametrize("op", ("weighted_sum", "moments"))
@pytest.mark.parametrize("policy", POLICIES)
def test_nonfinite_in_dropped_rows_never_poisons_algebra_ops(op, policy):
    """Sentinel zeroing runs downstream of ``pre``, so NaN/Inf payloads
    in dropped rows — in the values *or* the weights — leave the clean
    run's exact bits, for every op x tier."""
    rng = np.random.RandomState(1)
    x = rng.randn(192, 3).astype(np.float32)
    w = rng.uniform(0.5, 2.0, 192).astype(np.float32)
    ids = rng.randint(0, 4, 192).astype(np.int32)
    burst = np.arange(0, 192, 5)
    ids[burst] = R.OUT_OF_RANGE_LABEL
    kw = {"weights": jnp.asarray(w)} if op == "weighted_sum" else {}
    clean = R.reduce(jnp.asarray(x), segment_ids=jnp.asarray(ids),
                     num_segments=4, op=op, policy=policy, **kw)
    xp = faults.inject_nonfinite(x, rows=burst, kind="both")
    if op == "weighted_sum":
        wp = w.copy()
        wp[burst] = np.nan
        kw = {"weights": jnp.asarray(wp)}
    out, st = R.reduce(jnp.asarray(xp), segment_ids=jnp.asarray(ids),
                       num_segments=4, op=op, policy=policy,
                       with_status=True, **kw)
    assert np.array_equal(np.asarray(clean), np.asarray(out)), (op, policy)
    assert np.isfinite(np.asarray(out)).all()
    assert not bool(st.nonfinite)


@pytest.mark.parametrize("op", ("weighted_sum", "moments"))
def test_degrade_chunks_over_bound_streams_algebra_ops(op):
    """The degrade fallback folds the op-transformed stream and applies
    ``post`` once at the end — over-bound weighted/moment reductions
    stay correct and flagged, like plain sums."""
    n = (1 << 21) + 3
    x = jnp.ones(n)
    kw = {"weights": jnp.full((n,), 2.0)} if op == "weighted_sum" else {}
    with pytest.raises(ValueError, match="blocks"):
        R.reduce(x, op=op, policy="exact2", block_size=64, **kw)
    out, st = R.reduce(x, op=op, policy="exact2", block_size=64,
                       on_overflow="degrade", with_status=True, **kw)
    if op == "weighted_sum":
        assert float(out) == float(2.0 * n)
    else:
        assert float(out[0]) == 1.0 and float(out[1]) == 0.0
    assert bool(st.degraded) and not bool(st.saturated)
    assert int(st.kept_rows) == n


# ---------------------------------------------------------------------------
# checkpoint storage faults
# ---------------------------------------------------------------------------


def _tree(shift=0.0):
    return {"w": jnp.arange(12.0).reshape(3, 4) + shift,
            "b": jnp.ones(4) * (1.0 + shift)}


def test_bitflip_is_detected_and_falls_back(tmp_path):
    ckpt.save(tmp_path, 1, _tree(0.0), extra={"next_step": 2})
    ckpt.save(tmp_path, 2, _tree(1.0), extra={"next_step": 3})
    faults.corrupt_checkpoint(tmp_path, 2, mode="bitflip")
    with pytest.raises(ckpt.CheckpointError, match="CRC32"):
        ckpt.restore(tmp_path, 2, _tree())
    tree, manifest, step = ckpt.restore_latest_valid(tmp_path, _tree())
    assert step == 1 and manifest["extra"]["next_step"] == 2
    assert np.array_equal(np.asarray(tree["w"]),
                          np.asarray(_tree(0.0)["w"]))


def test_truncation_is_detected_and_falls_back(tmp_path):
    ckpt.save(tmp_path, 1, _tree(0.0))
    ckpt.save(tmp_path, 2, _tree(1.0))
    faults.corrupt_checkpoint(tmp_path, 2, mode="truncate")
    with pytest.raises(ckpt.CheckpointError):
        ckpt.restore(tmp_path, 2, _tree())
    _, _, step = ckpt.restore_latest_valid(tmp_path, _tree())
    assert step == 1


def test_every_checkpoint_corrupt_raises_structured(tmp_path):
    ckpt.save(tmp_path, 1, _tree())
    faults.corrupt_checkpoint(tmp_path, 1, mode="bitflip")
    with pytest.raises(ckpt.CheckpointError, match="no valid checkpoint"):
        ckpt.restore_latest_valid(tmp_path, _tree())


def test_kill_mid_save_orphan_is_never_restored(tmp_path):
    """A real process death between shard write and rename: the ``.tmp``
    directory stays behind, ``latest_step`` ignores it, and recovery
    resumes from the previous verified step."""
    tree = jax.tree.map(jnp.asarray, faults._demo_tree())
    ckpt.save(tmp_path, 1, tree, extra={"next_step": 2})
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-m", "repro.testing.faults",
                        "kill-mid-save", str(tmp_path), "2"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == faults.KILL_EXIT_CODE, (r.returncode, r.stderr)
    assert (tmp_path / "step_00000002.tmp").exists()
    assert not (tmp_path / "step_00000002").exists()
    assert ckpt.latest_step(tmp_path) == 1
    restored, manifest, step = ckpt.restore_latest_valid(tmp_path, tree)
    assert step == 1
    assert all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(restored),
                               jax.tree.leaves(tree)))


# ---------------------------------------------------------------------------
# shard dropout in merge_carry_across
# ---------------------------------------------------------------------------

DROPOUT_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro import reduce as R
from repro.testing.faults import drop_shard_carry

rng = np.random.RandomState(0)
n, d, s, bs, nshards = 1024, 4, 3, 128, 8
vals = jnp.asarray(rng.randn(n, d).astype(np.float32))
ids = jnp.asarray(rng.randint(0, s, n).astype(np.int32))
pol = R.get_policy("exact2")
mids = R.mask_out_of_range(ids, s)
mvals = jnp.where((mids >= 0)[:, None], vals, 0.0)
domain, ctx = pol.prepare(mvals, n)
mesh = Mesh(np.asarray(jax.devices()), ("shards",))
DROP = 3

def body(v, i):
    carry = R.get_backend("blocked").run(v, i, s, policy=pol, block_size=bs)
    carry = drop_shard_carry(carry, "shards", DROP)
    return R.merge_carry_across(pol, carry, ("shards",))

carry = jax.shard_map(body, mesh=mesh,
                      in_specs=(P("shards", None), P("shards")),
                      out_specs=P(), check_vma=False)(domain, mids)
dropped = np.asarray(pol.finalize(carry, ctx))

# ground truth: the identical schedule with shard DROP's rows deleted
# (same prepared domain and ctx, so the quantization grid is unchanged)
rows = np.ones(n, bool)
per = n // nshards
rows[DROP * per:(DROP + 1) * per] = False
csur = R.get_backend("blocked").run(domain[rows], mids[rows], s,
                                    policy=pol, block_size=bs)
survive = np.asarray(pol.finalize(csur, ctx))
print("DROPOUT", int(np.array_equal(dropped, survive)))
"""


def test_shard_dropout_degrades_to_surviving_rows():
    """Zeroing one shard's carry before ``merge_carry_across`` must yield
    *exactly* (bitwise) the reduction over the surviving shards' rows —
    graceful degradation, not corruption."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    r = subprocess.run([sys.executable, "-c", DROPOUT_SNIPPET],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "DROPOUT 1" in r.stdout


# ---------------------------------------------------------------------------
# the acceptance test: bitwise elastic resume, 2 devices -> 8
# ---------------------------------------------------------------------------

ELASTIC_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.optim import adamw
from repro.distributed.collectives import make_elastic_train_step
from repro.ckpt import checkpoint as ckpt

ckpt_dir = r"@CKPT@"
cfg = get_smoke_config("xlstm-125m")
params0 = init_params(jax.random.PRNGKey(0), cfg)
opt0 = adamw.init(params0)
lr_fn = adamw.cosine_schedule(1e-3, 2, 20)

def make_batch(step):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(100 + step),
                                         (8, 16), 0, cfg.vocab)}

def run(ndev, params, opt, steps, start=0):
    mesh = Mesh(np.asarray(jax.devices()[:ndev]), ("data",))
    fn = jax.jit(make_elastic_train_step(cfg, mesh, lr_fn=lr_fn,
                                         microbatch_size=1))
    losses = []
    for s in range(start, start + steps):
        params, opt, m = fn(params, opt, make_batch(s))
        losses.append(float(m["loss"]))
    return params, opt, losses

# the uninterrupted reference: 4 steps on 2 devices
pA, oA, lA = run(2, params0, opt0, 4)

# the elastic run: 2 steps on 2 devices, checkpoint, restore, 2 on 8
p1, o1, l1 = run(2, params0, opt0, 2)
ckpt.save(ckpt_dir, 2, {"params": p1, "opt": o1}, extra={"next_step": 2})
state, manifest, step = ckpt.restore_latest_valid(
    ckpt_dir, {"params": p1, "opt": o1})
assert step == 2 and manifest["extra"]["next_step"] == 2
pB, oB, l2 = run(8, state["params"], state["opt"], 2, start=2)

ok_params = all(np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(jax.tree.leaves(pA), jax.tree.leaves(pB)))
ok_loss = (l1 + l2) == lA
print("ELASTIC", int(ok_params), int(ok_loss))
"""


@pytest.mark.slow
def test_elastic_resume_is_bitwise_2_to_8_devices(tmp_path):
    """Train 2 steps on 2 emulated devices with the elastic (exact2)
    step, checkpoint, restore, finish on 8 devices: params and every
    per-step loss match the uninterrupted 2-device run bit for bit."""
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    snippet = ELASTIC_SNIPPET.replace("@CKPT@", str(tmp_path / "ck"))
    r = subprocess.run([sys.executable, "-c", snippet],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC 1 1" in r.stdout


# ---------------------------------------------------------------------------
# serving faults: a request killed mid-decode
# ---------------------------------------------------------------------------


def test_cancel_mid_decode_frees_pages_and_isolates_survivors():
    """Kill a request mid-decode: its KV pages return to the pool at the
    moment of cancellation (not at drain), a 'cancelled' result still
    arrives in submission order, and the survivors' greedy outputs are
    bitwise identical to a run where the victim never existed (per-slot
    isolation: a dying batchmate cannot perturb anyone's stream)."""
    from repro.configs import get_smoke_config
    from repro.models import init_params
    from repro.serve import Engine, Request

    cfg = get_smoke_config("stablelm-1.6b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, max_len=96, seed=0, max_batch=4)

    survivors = [Request(prompt=[5, 6, 7], max_new_tokens=6),
                 Request(prompt=[9, 10], max_new_tokens=8),
                 Request(prompt=[2, 3, 4, 5], max_new_tokens=5)]
    victim = Request(prompt=[30, 31, 32], max_new_tokens=20)

    rids = [eng.submit(r) for r in survivors + [victim]]
    victim_rid = rids[-1]
    victim_pages = eng.pool.pages_for(
        min(len(victim.prompt) + victim.max_new_tokens, eng.max_len))
    seen = {}

    def kill(engine, step):
        if step == 3:      # victim is mid-decode (admitted at step 0)
            assert engine.scheduler.tracked(victim_rid).state == "decode"
            before = engine.pool.free_pages
            assert engine.cancel(victim_rid)
            seen["freed"] = engine.pool.free_pages - before
            seen["tokens"] = len(engine.scheduler.tracked(victim_rid).out)

    results = eng.run(on_step=kill)
    assert seen["freed"] == victim_pages          # pages back immediately
    assert [r.rid for r in results] == rids       # in-order incl. victim
    vres = results[-1]
    assert vres.finish_reason == "cancelled"
    assert len(vres.tokens) == seen["tokens"]     # partial output kept
    assert eng.pool.free_pages == eng.pool.num_pages

    clean = eng.generate(survivors)               # victim never existed
    for got, ref_ in zip(results, clean):
        assert got.tokens == ref_.tokens, \
            "cancellation perturbed a surviving request's stream"
        assert got.finish_reason == ref_.finish_reason
