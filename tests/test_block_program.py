"""Staged block-program tests: the planner, the two contrib forms, and
the double-buffered kernel grid.

The contracts under test:

  * ``plan_program`` picks the lane-parallel contrib only for
    integer-domain policies at large label counts ("auto" is a pure
    performance decision);
  * the lane form is **bitwise** the one-hot dot for integer-domain
    tiers, on every backend (associative int32 addition — same multiset
    of adds per segment), and tolerance-close for the float tiers;
  * the pallas supertile depth (``blocks_per_step``) never changes a
    result bit, for any policy — the double buffering moves tiles, not
    the fold order;
  * the staged prepare split (``prepare_ctx`` + row-local ``to_domain``)
    reproduces the whole-stream ``prepare`` bit for bit, which is what
    lets the shard_map backend digitize in-shard.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import reduce as R
from repro.kernels import ops
from repro.kernels.jugglepac_segsum import (blocks_per_step_for,
                                            segsum_policy_pallas)
from repro.reduce.program import (LANE_MIN_SEGMENTS, BlockProgram,
                                  block_contrib, plan_program)

POLICIES = ("fast", "compensated", "exact", "exact2", "procrastinate")
INT_POLICIES = ("exact", "exact2", "procrastinate")
FLOAT_POLICIES = ("fast", "compensated")
BACKENDS = ("ref", "blocked", "pallas")


def _data(n, d, s, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(n, d).astype(np.float32)),
            jnp.asarray(rng.randint(0, s, n)))


# ---------------------------------------------------------------------------
# the planner
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", POLICIES)
def test_plan_auto_contrib_selection(policy):
    pol = R.get_policy(policy)
    small = plan_program(pol, num_segments=LANE_MIN_SEGMENTS - 1,
                         domain_width=pol.domain_width(8))
    large = plan_program(pol, num_segments=LANE_MIN_SEGMENTS,
                         domain_width=pol.domain_width(8))
    assert small.contrib == "dot"       # below crossover: always the dot
    if policy in INT_POLICIES:
        assert large.contrib == "lanes"
    else:
        # float tiers never switch under auto (rounding-order consent)
        assert large.contrib == "dot"


def test_plan_program_declares_both_stages_with_bounds():
    prog = plan_program("exact2", num_segments=64, domain_width=128)
    assert isinstance(prog, BlockProgram)
    assert prog.stage("contrib").bound == "memory"
    assert prog.stage("update").bound == "compute"
    assert prog.stage("contrib").bytes > 0
    assert prog.stage("update").flops > 0
    with pytest.raises(KeyError, match="no stage"):
        prog.stage("gather")
    # hashable: rides through jit static args like ReduceSpec
    assert hash(prog) == hash(plan_program("exact2", num_segments=64,
                                           domain_width=128))


def test_dot_flops_grow_with_segments_lanes_flops_do_not():
    pol = R.get_policy("exact2")
    dot_small = pol.stage_costs(512, 128, 16, contrib="dot")
    dot_large = pol.stage_costs(512, 128, 1024, contrib="dot")
    lane_small = pol.stage_costs(512, 128, 16, contrib="lanes")
    lane_large = pol.stage_costs(512, 128, 1024, contrib="lanes")
    assert dot_large["contrib"]["flops"] > dot_small["contrib"]["flops"]
    assert lane_large["contrib"]["flops"] == lane_small["contrib"]["flops"]


def test_reduce_rejects_unknown_contrib():
    with pytest.raises(ValueError, match="contrib"):
        R.reduce(jnp.ones(8), contrib="scatter")


@pytest.mark.parametrize("policy", INT_POLICIES)
def test_pallas_plans_the_dot_in_and_out_of_interpret_mode(policy):
    """The compiled kernel has no scatter-add, so auto planning gives the
    pallas executor the dot form past the crossover too — one behaviour
    with or without interpret mode — and an explicit lane form sent to
    the compiled kernel is refused by name."""
    pol = R.get_policy(policy)
    pallas = R.get_backend("pallas")
    prog = plan_program(pol, num_segments=LANE_MIN_SEGMENTS,
                        domain_width=pol.domain_width(8),
                        plans_lanes=pallas.plans_lanes)
    assert prog.contrib == "dot"
    assert R.get_backend("blocked").plans_lanes
    with pytest.raises(ValueError, match="one-hot dot"):
        R.reduce(jnp.ones((512, 8)), segment_ids=jnp.zeros(512, jnp.int32),
                 num_segments=64, policy=policy, backend="pallas",
                 contrib="lanes", interpret=False)


@pytest.mark.parametrize("policy", POLICIES)
def test_tpu_auto_selection_is_the_compiled_kernel(policy, monkeypatch):
    """On a TPU every tier auto-selects the pallas kernel, compiled —
    no tier is routed to another executor or to interpret mode."""
    from repro.reduce import backends
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert R.select_backend(R.get_policy(policy)).name == "pallas"
    assert backends.interpret_default() is False


# ---------------------------------------------------------------------------
# lanes vs dot, per backend
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("policy", INT_POLICIES)
def test_lanes_bitwise_equals_dot_for_integer_tiers(policy, backend):
    """The planner's crossover is bitwise-invisible where it applies."""
    vals, ids = _data(600, 8, 40, seed=1)        # S > LANE_MIN_SEGMENTS
    kw = dict(segment_ids=ids, num_segments=40, policy=policy,
              backend=backend, block_size=128)
    a = np.asarray(R.reduce(vals, contrib="dot", **kw))
    b = np.asarray(R.reduce(vals, contrib="lanes", **kw))
    c = np.asarray(R.reduce(vals, contrib="auto", **kw))
    assert np.array_equal(a, b)                  # zero bits changed
    assert np.array_equal(a, c)


@pytest.mark.parametrize("policy", FLOAT_POLICIES)
def test_lanes_opt_in_close_for_float_tiers(policy):
    vals, ids = _data(600, 8, 40, seed=2)
    kw = dict(segment_ids=ids, num_segments=40, policy=policy,
              backend="blocked", block_size=128)
    a = np.asarray(R.reduce(vals, contrib="dot", **kw))
    b = np.asarray(R.reduce(vals, contrib="lanes", **kw))
    # auto == dot for float tiers (no silent rounding-order change) ...
    assert np.array_equal(a, np.asarray(R.reduce(vals, contrib="auto",
                                                 **kw)))
    # ... and the opt-in lane fold is the same sum, different order
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_block_contrib_is_the_shared_gather():
    """ref/blocked/pallas all call this helper; check both forms against
    a scatter oracle on one block."""
    pol = R.get_policy("exact")
    rng = np.random.RandomState(3)
    vals = jnp.asarray(rng.randint(-50, 50, (128, 4)).astype(np.int32))
    ids = jnp.asarray(rng.randint(0, 6, 128).astype(np.int32))
    oracle = np.zeros((6, 4), np.int32)
    np.add.at(oracle, np.asarray(ids), np.asarray(vals))
    dot = block_contrib(vals, ids, 6, pol)
    prog = plan_program(pol, num_segments=6, domain_width=4,
                        contrib="lanes")
    lanes = block_contrib(vals, ids, 6, pol, prog)
    assert np.array_equal(np.asarray(dot), oracle)
    assert np.array_equal(np.asarray(lanes), oracle)


# ---------------------------------------------------------------------------
# the double-buffered pallas grid
# ---------------------------------------------------------------------------


def test_blocks_per_step_sizing():
    assert blocks_per_step_for(512, 16) == 8     # tiny rows: cap at 8
    assert blocks_per_step_for(512, 4096) == 1   # huge rows: no stacking
    # monotone non-increasing in width
    widths = [16, 64, 256, 1024, 4096]
    depths = [blocks_per_step_for(512, w) for w in widths]
    assert depths == sorted(depths, reverse=True)


@pytest.mark.parametrize("mapped", [False, True], ids=["domain", "raw"])
@pytest.mark.parametrize("policy", POLICIES)
def test_pallas_supertile_depth_is_bitwise_invisible(policy, mapped):
    """blocks_per_step ∈ {1, 2, 4, 8} — including depths that force
    whole-sentinel-block padding — changes zero bits for every tier; so
    does handing the kernel raw rows to map per block (``to_domain``), at
    an N that leaves the last block partial."""
    pol = R.get_policy(policy)
    n = 700 if mapped else 768                   # 6 blocks of 128
    vals, ids = _data(n, 8, 5, seed=4)
    ids = R.mask_out_of_range(ids, 5)
    domain, ctx = pol.prepare(vals, n)
    kw = ({"to_domain": pol.map_rows, "prep_state": pol.domain_args(ctx)}
          if mapped else {})
    outs = []
    for bps in (1, 2, 4, 8):                     # 6 % 4 != 0: pads
        carry = segsum_policy_pallas(vals if mapped else domain, ids, 5,
                                     policy=pol, block_rows=128,
                                     interpret=True, blocks_per_step=bps,
                                     **kw)
        outs.append(np.asarray(pol.finalize(carry, ctx)))
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)
    if mapped:
        prepared = R.get_backend("pallas").run(domain, ids, 5, policy=pol,
                                               block_size=128)
        assert np.array_equal(outs[0],
                              np.asarray(pol.finalize(prepared, ctx)))


def test_ops_segment_sum_bps_bitwise():
    vals, ids = _data(1024, 4, 8, seed=5)
    base = np.asarray(ops.segment_sum(vals, ids, 8))
    for bps in (1, 2, 4):
        out = np.asarray(ops.segment_sum(vals, ids, 8,
                                         blocks_per_step=bps))
        assert np.array_equal(base, out)


# ---------------------------------------------------------------------------
# the staged prepare split
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", POLICIES)
def test_prepare_ctx_to_domain_equals_prepare(policy):
    """The split the shard_map backend runs in-shard: global stat →
    ctx, then row-local to_domain — must equal whole-stream prepare
    bitwise, row subsets included."""
    pol = R.get_policy(policy)
    vals, _ = _data(500, 8, 1, seed=6)
    v32 = vals.astype(jnp.float32)
    domain, ctx = pol.prepare(vals, 500)
    m = jnp.max(jnp.abs(v32)) if pol.needs_max_stat else None
    ctx2 = pol.prepare_ctx(m, 500)
    split = pol.to_domain(v32, ctx2)
    assert np.array_equal(np.asarray(domain), np.asarray(split))
    # row-locality: a shard's slice maps identically under the shared ctx
    half = pol.to_domain(v32[:250], ctx2)
    assert np.array_equal(np.asarray(domain)[:250], np.asarray(half))
    if ctx is not None:
        assert np.asarray(ctx) == np.asarray(ctx2)


@pytest.mark.parametrize("policy", POLICIES)
def test_front_door_auto_program_matches_explicit(policy):
    """reduce() plans the program itself; pinning the same program via
    ReduceSpec(contrib=...) must reproduce it bitwise."""
    vals, ids = _data(400, 4, 64, seed=7)        # S past the crossover
    out_auto = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=64,
                                   policy=policy, backend="blocked"))
    forced = "lanes" if policy in INT_POLICIES else "dot"
    spec = R.ReduceSpec(policy=policy, backend="blocked", contrib=forced)
    out_spec = np.asarray(R.reduce(vals, segment_ids=ids, num_segments=64,
                                   spec=spec))
    assert np.array_equal(out_auto, out_spec)
