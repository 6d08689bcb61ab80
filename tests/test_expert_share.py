"""One chip's share of expert parallelism (``MoECfg.held``), DeepSeek-V2's
latent attention with YaRN, and the engine's held-expert path, against
the plain float32 reference (``repro.testing.deepseek_ref``), at CPU size
on seeded random weights."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import attention, init_params
from repro.models import moe as M
from repro.models.config import MoECfg
from repro.serve.engine import Engine, Request
from repro.testing import deepseek_ref as ref

SMOKE = get_smoke_config("deepseek-v2-lite-16b")


def _moe_cfg(**moe):
    base = dict(num_experts=8, top_k=3, d_ff_expert=32, num_shared=2,
                d_ff_shared=16)
    return SMOKE.scaled(moe=MoECfg(**{**base, **moe}))


def _share_params(full, first, held):
    return {**full, **{k: full[k][first:first + held]
                       for k in ("wi", "wg", "wo")}}


def _np_routed(p, x, moe, keep):
    """(held,) count of the (token, choice) pairs on the held experts, by
    NumPy from the router's logits, over the tokens ``keep`` keeps."""
    lg = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    top = np.argsort(-lg, axis=-1, kind="stable")[:, :moe.top_k]
    top = top[keep]
    return np.array([(top == moe.held_first + j).sum()
                     for j in range(moe.n_held)])


def test_shares_sum_to_the_layer():
    """Four shares of 2 of 8 experts: their held parts, with the shared
    experts counted once, give the uncut layer."""
    cfg = _moe_cfg()
    full = M.moe_init(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, cfg.d_model))
    shared = ref._swiglu(full["shared"], x.reshape(-1, cfg.d_model))
    total = -3 * shared
    routed = 0
    for s in range(4):
        cs = cfg.scaled(moe=dataclasses.replace(cfg.moe, held_first=2 * s,
                                                held=2))
        y, r = M.moe_apply_held(_share_params(full, 2 * s, 2), x, cs)
        total = total + y.reshape(-1, cfg.d_model)
        routed += int(r.sum())
    want = ref.moe_layer(full, x.reshape(-1, cfg.d_model), cfg.moe)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert routed == 2 * 24 * cfg.moe.top_k


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4), (6, 2)])
def test_held_path_matches_reference_share(first, held):
    """The held path (ragged grouped matmul, segmented combine) gives the
    reference's held part plus the shared experts: no pair is dropped."""
    cfg = _moe_cfg(held_first=first, held=held)
    full = M.moe_init(jax.random.PRNGKey(5), _moe_cfg(), jnp.float32)
    p = _share_params(full, first, held)
    x = jax.random.normal(jax.random.PRNGKey(6), (3, 16, cfg.d_model))
    y, routed = M.moe_apply_held(p, x, cfg)
    want = ref.moe_layer(p, x.reshape(-1, cfg.d_model), cfg.moe)
    np.testing.assert_allclose(np.asarray(y).reshape(-1, cfg.d_model),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    keep = np.ones(48, bool)
    np.testing.assert_array_equal(
        np.asarray(routed), _np_routed(p, x.reshape(-1, cfg.d_model),
                                       cfg.moe, keep))


def test_masked_tokens_route_nothing():
    """A token the mask drops computes only the shared experts, and its
    pairs are not counted."""
    cfg = _moe_cfg(held_first=2, held=4)
    p = _share_params(M.moe_init(jax.random.PRNGKey(7), _moe_cfg(),
                                 jnp.float32), 2, 4)
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 1, cfg.d_model))
    mask = jnp.asarray([[True], [False], [True], [False]])
    y, routed = M.moe_apply_held(p, x, cfg, token_mask=mask)
    xt = x.reshape(-1, cfg.d_model)
    shared = ref._swiglu(p["shared"], xt)
    want = ref.moe_layer(p, xt, cfg.moe)
    y = np.asarray(y).reshape(-1, cfg.d_model)
    np.testing.assert_allclose(y[[1, 3]], np.asarray(shared)[[1, 3]],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y[[0, 2]], np.asarray(want)[[0, 2]],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(routed),
        _np_routed(p, xt, cfg.moe, np.asarray(mask).reshape(-1)))


def test_dense_path_on_a_share_runs_the_held_experts():
    cfg = _moe_cfg(held_first=4, held=4)
    p = _share_params(M.moe_init(jax.random.PRNGKey(9), _moe_cfg(),
                                 jnp.float32), 4, 4)
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 8, cfg.d_model))
    yd, _ = M.moe_apply_dense(p, x, cfg)
    yh, _ = M.moe_apply_held(p, x, cfg)
    np.testing.assert_allclose(np.asarray(yd), np.asarray(yh), rtol=2e-5,
                               atol=2e-5)
    with pytest.raises(ValueError, match="no expert share"):
        M.moe_apply_capacity(p, x, cfg)


def test_yarn_matches_published_formulas():
    """At DeepSeek-V2-Lite's constants: inverse frequencies, the cos/sin
    scale and the softmax scale as the published modelling code gives
    them."""
    cfg = get_config("deepseek-v2-lite-16b")
    y = cfg.rope_scaling
    got = attention.yarn_inv_freq(64, cfg.rope_theta, y)
    want = ref.yarn_inv_freq(64, 1e4, 40, 4096, 32, 1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    # correction dims: floor(10.47) = 10 and ceil(22.51) = 23
    np.testing.assert_allclose(got[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((got[11:23] < base[11:23]) & (got[11:23] > base[11:23] / 40))
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m - 1.2608) < 1e-4
    freqs, rscale, sm = attention.mla_rope(cfg)
    assert rscale == 1.0
    assert sm == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    np.testing.assert_allclose(np.asarray(freqs), want, rtol=1e-6)


def test_published_preset():
    cfg = get_config("deepseek-v2-lite-16b")
    assert (cfg.n_layers, cfg.first_dense, cfg.n_periods) == (27, 1, 26)
    assert (cfg.d_ff, cfg.moe.d_ff_expert, cfg.moe.num_experts,
            cfg.moe.top_k, cfg.moe.num_shared) == (10944, 1408, 64, 6, 2)
    assert not cfg.moe.router_norm_topk
    assert cfg.norm_eps == 1e-6 and cfg.rope_scaling.factor == 40
    # 15.7B parameters published; the padded vocab adds none here
    assert abs(cfg.param_counts()["total"] / 1e9 - 15.7) < 0.05


def test_smoke_forward_matches_reference():
    p = init_params(jax.random.PRNGKey(11), SMOKE)
    toks = jax.random.randint(jax.random.PRNGKey(12), (1, 20), 0,
                              SMOKE.vocab)
    from repro.models import forward
    lg, _, _, routed = forward(p, SMOKE, tokens=toks, moe_impl="held")
    want = ref.logits(p, SMOKE, toks[0])
    np.testing.assert_allclose(np.asarray(lg[0, :, :SMOKE.vocab]),
                               np.asarray(want), rtol=1e-4, atol=1e-4)
    assert routed.shape == (SMOKE.n_periods, SMOKE.moe.n_held)


def test_engine_chunks_and_decode_match_reference():
    """Chunked prefill (a prompt across chunk boundaries, slots at
    different lengths) and decode through the latent cache, with an idle
    slot riding along, give the reference's full-forward logits; the
    routing counts are the NumPy count of the reference's top-k over the
    real tokens only."""
    cfg = SMOKE
    p = init_params(jax.random.PRNGKey(13), cfg)
    eng = Engine(cfg, p, max_len=32, max_batch=3, prefill_chunk=4)
    rng = np.random.default_rng(0)
    prompts = {0: rng.integers(1, cfg.vocab, 10), 2: rng.integers(
        1, cfg.vocab, 3)}
    steps = 3
    seqs = {s: np.concatenate([pr, rng.integers(1, cfg.vocab, steps)])
            for s, pr in prompts.items()}
    want = {s: np.asarray(ref.logits(p, cfg, seq)) for s, seq in seqs.items()}
    caches = eng._caches
    prefill_pairs = 0
    for s, pr in prompts.items():
        for start in range(0, len(pr), 4):
            piece = pr[start:start + 4]
            toks = np.zeros((1, 4), np.int32)
            toks[0, :len(piece)] = piece
            last, caches, routed = eng._prefill_chunk(
                p, caches, jnp.int32(s), jnp.asarray(toks),
                jnp.int32(start), jnp.int32(len(piece)))
            prefill_pairs += int(np.asarray(routed).sum())
        np.testing.assert_allclose(np.asarray(last)[0, 0, :cfg.vocab],
                                   want[s][len(pr) - 1], rtol=1e-4,
                                   atol=1e-4)
    active = np.array([True, False, True])
    for i in range(steps):
        tok = np.zeros((3, 1), np.int32)
        pos = np.zeros(3, np.int32)
        for s, pr in prompts.items():
            tok[s, 0] = seqs[s][len(pr) + i]
            pos[s] = len(pr) + i
        logits, caches, routed = eng._decode(p, jnp.asarray(tok), caches,
                                             jnp.asarray(pos),
                                             jnp.asarray(active))
        for s, pr in prompts.items():
            np.testing.assert_allclose(
                np.asarray(logits)[s, 0, :cfg.vocab],
                want[s][len(pr) + i], rtol=1e-4, atol=1e-4)
        assert int(np.asarray(routed).sum()) <= 2 * cfg.moe.top_k \
            * cfg.n_periods
    # the idle slot's cache never moved
    assert int(np.asarray(caches[1]["core"].length)[:, 1].max()) == 0
    assert prefill_pairs <= 13 * cfg.moe.top_k * cfg.n_periods


def test_engine_routing_counts_match_numpy():
    """The counts the engine keeps per step equal a NumPy count over each
    MoE layer's router input, which the reference recomputes."""
    cfg = SMOKE
    p = init_params(jax.random.PRNGKey(14), cfg)
    eng = Engine(cfg, p, max_len=32, max_batch=2, prefill_chunk=4)
    routing = []
    eng.on_routing = lambda *read: routing.append(read)
    prompt = [5, 9, 2, 7, 1, 3]
    res = eng.generate([Request(prompt=prompt, max_new_tokens=3)])[0]
    phases = [ph for _, ph, _ in routing]
    assert phases == ["prefill", "prefill", "decode", "decode"]
    # replay the sequence through the reference, capturing each MoE
    # layer's input, and count its top-k over the held experts
    seq = jnp.asarray(res.tokens)
    hidden = _moe_inputs(p, cfg, seq)
    counts = [np.stack([_np_routed(
        jax.tree.map(lambda a: a[i], p["blocks"][0]["mlp"]), h[lo:hi],
        cfg.moe, np.ones(hi - lo, bool)) for i, h in enumerate(hidden)])
        for lo, hi in ((0, 4), (4, 6), (6, 7), (7, 8))]
    for (_, _, got), want in zip(routing, counts):
        np.testing.assert_array_equal(got, want)


def _moe_inputs(params, cfg, tokens):
    """Each MoE layer's normed input h (T, D) in the reference forward."""
    captured = []
    real = ref.moe_layer

    def spy(p, h, moe):
        captured.append(h)
        return real(p, h, moe)

    ref.moe_layer = spy
    try:
        ref.logits(params, cfg, tokens)
    finally:
        ref.moe_layer = real
    return captured


def test_dense_configs_return_nothing_extra():
    cfg = get_smoke_config("stablelm-1.6b")
    eng = Engine(cfg, init_params(jax.random.PRNGKey(0), cfg), max_len=32,
                 max_batch=2)
    routing = []
    eng.on_routing = lambda *read: routing.append(read)
    eng.generate([Request(prompt=[1, 2, 3], max_new_tokens=2)])
    assert routing == []
    out = eng._decode(eng.params, jnp.zeros((2, 1), jnp.int32), eng._caches,
                      jnp.zeros(2, jnp.int32), jnp.zeros(2, bool))
    assert out[2] is None and len(jax.tree.leaves(out)) == 1 + len(
        jax.tree.leaves(eng._caches))


def test_share_programs_update_caches_in_place():
    """Every configuration's decode takes the caches donated (one copy of
    them in a step, not two); a share configuration's prefill chunk does
    too, and a dense configuration's chunk leaves its input caches
    alive."""
    i32 = jnp.int32
    for cfg, chunk_donates in ((SMOKE, True),
                               (get_smoke_config("stablelm-1.6b"), False)):
        eng = Engine(cfg, init_params(jax.random.PRNGKey(0), cfg),
                     max_len=32, max_batch=2, prefill_chunk=4)
        old = eng._caches
        _, new, _ = eng._prefill_chunk(eng.params, old, i32(0),
                                       jnp.ones((1, 4), i32), i32(0), i32(4))
        _, newer, _ = eng._decode(eng.params, jnp.ones((2, 1), i32), new,
                                  jnp.array([4, 0], i32),
                                  jnp.array([True, False]))
        assert all(leaf.is_deleted() == chunk_donates
                   for leaf in jax.tree.leaves(old))
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(new))
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(newer))
