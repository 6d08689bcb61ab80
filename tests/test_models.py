"""Per-architecture smoke tests + model-level invariants.

For each of the 10 assigned architectures: instantiate the reduced
same-family SMOKE config, run one forward/loss and one train step on CPU,
assert output shapes and finiteness.  Plus: decode-vs-train parity, MoE
capacity-vs-dense equivalence, chunked-attention equivalence, SSM
chunk-invariance — the invariants the production paths rely on.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke_config
from repro.models import (decode_step, encode, forward, init_caches,
                          init_params, loss_fn, pad_caches_to)
from repro.models.config import SHAPES, SHAPES_BY_NAME
from repro.optim import adamw
from repro.train.steps import make_train_step

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, b=2, s=32):
    batch = {}
    if cfg.embed_inputs and not cfg.is_encdec:
        batch["embeds"] = jax.random.normal(KEY, (b, s, cfg.d_model),
                                            jnp.float32)
        batch["labels"] = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
        if cfg.mrope:
            batch["positions"] = jnp.broadcast_to(
                jnp.arange(s)[None, :, None], (b, s, 3)).astype(jnp.int32)
    else:
        batch["tokens"] = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    if cfg.is_encdec:
        batch["enc_embeds"] = jax.random.normal(KEY, (b, s, cfg.d_model),
                                                jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    params = init_params(KEY, cfg)
    batch = _batch_for(cfg)

    loss, metrics = loss_fn(params, cfg, batch, moe_impl="dense")
    assert np.isfinite(float(loss)), arch
    assert float(metrics["tokens"]) > 0

    lr_fn = adamw.cosine_schedule(1e-3, 2, 10)
    step = make_train_step(cfg, lr_fn=lr_fn, remat=False, moe_impl="dense")
    opt = adamw.init(params)
    p2, o2, m2 = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(m2["loss"])), arch
    assert int(o2.count) == 1
    # params actually changed
    delta = sum(float(jnp.abs(a - b).sum())
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(p2)))
    assert delta > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_full_config_consistency(arch):
    """The FULL config (exercised via dry-run) is structurally valid."""
    cfg = get_config(arch)
    assert cfg.n_layers % len(cfg.period) == 0
    assert cfg.padded_vocab >= cfg.vocab
    assert cfg.padded_vocab % 256 == 0
    pc = cfg.param_counts()
    assert pc["active"] <= pc["total"]
    if cfg.moe:
        assert pc["active"] < pc["total"]


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if not get_smoke_config(a).embed_inputs])
def test_decode_matches_train(arch):
    cfg = get_smoke_config(arch)
    params = init_params(jax.random.PRNGKey(1), cfg)
    B, S = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S + 1), 0,
                              cfg.vocab)
    enc_out = None
    if cfg.is_encdec:
        enc = jax.random.normal(KEY, (B, 16, cfg.d_model), jnp.float32)
        enc_out = encode(params, cfg, enc)
    full, _, _, _ = forward(params, cfg, tokens=toks, mode="train",
                            enc_out=enc_out, moe_impl="dense")
    _, caches, _, _ = forward(params, cfg, tokens=toks[:, :S],
                              mode="prefill", enc_out=enc_out,
                              moe_impl="dense")
    caches = pad_caches_to(cfg, caches, 32)
    dec, _ = decode_step(params, cfg, toks[:, S:S + 1], caches, S,
                         enc_out=enc_out, moe_impl="dense")
    rel = (float(jnp.abs(dec[:, 0] - full[:, S]).max())
           / float(jnp.abs(full[:, S]).max()))
    assert rel < 2e-2, (arch, rel)


def test_vlm_decode_with_tokens():
    """qwen2-vl: embeds prefill (patch stubs) then token decode."""
    cfg = get_smoke_config("qwen2-vl-7b")
    params = init_params(KEY, cfg)
    B, S = 2, 16
    embeds = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None, :, None],
                           (B, S, 3)).astype(jnp.int32)
    _, caches, _, _ = forward(params, cfg, embeds=embeds, positions=pos,
                              mode="prefill", moe_impl="dense")
    caches = pad_caches_to(cfg, caches, 32)
    tok = jax.random.randint(KEY, (B, 1), 0, cfg.vocab)
    logits, caches2 = decode_step(params, cfg, tok, caches, S,
                                  moe_impl="dense")
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all()


def test_moe_capacity_matches_dense():
    from repro.models import moe as M
    cfg = get_smoke_config("mixtral-8x22b").scaled(
        moe=get_smoke_config("mixtral-8x22b").moe.__class__(
            num_experts=4, top_k=2, d_ff_expert=64, capacity_factor=8.0))
    p = M.moe_init(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (2, 40, cfg.d_model))
    yc, auxc = M.moe_apply_capacity(p, x, cfg, group_size=16)
    yd, auxd = M.moe_apply_dense(p, x, cfg)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(yd), atol=1e-4)
    assert np.allclose(float(auxc), float(auxd))


def test_moe_capacity_drops_under_tight_capacity():
    from repro.models import moe as M
    cfg = get_smoke_config("mixtral-8x22b")
    p = M.moe_init(KEY, cfg, jnp.float32)
    x = jax.random.normal(KEY, (1, 64, cfg.d_model))
    y_tight, _ = M.moe_apply_capacity(p, x, cfg, capacity=1, group_size=64)
    y_loose, _ = M.moe_apply_capacity(p, x, cfg, capacity=64, group_size=64)
    assert not np.allclose(np.asarray(y_tight), np.asarray(y_loose))
    assert np.isfinite(np.asarray(y_tight)).all()


def test_chunked_attention_matches_full():
    for arch in ("stablelm-1.6b", "deepseek-v2-lite-16b"):
        cfg = get_smoke_config(arch)
        p = init_params(KEY, cfg)
        toks = jax.random.randint(KEY, (2, 64), 0, cfg.vocab)
        l1, _, _, _ = forward(p, cfg.scaled(attn_qchunk=4096),
                              tokens=toks, moe_impl="dense")
        l2, _, _, _ = forward(p, cfg.scaled(attn_qchunk=8), tokens=toks,
                              moe_impl="dense")
        np.testing.assert_allclose(np.asarray(l1), np.asarray(l2),
                                   atol=2e-3)


def test_swa_ring_cache_long_decode():
    """Mixtral-style SWA: decode far past the window; ring cache stays
    O(window) and matches a full-cache windowed reference."""
    cfg = get_smoke_config("mixtral-8x22b").scaled(window=8, n_layers=2)
    p = init_params(KEY, cfg)
    B, S = 1, 24
    toks = jax.random.randint(jax.random.PRNGKey(3), (B, S + 4), 0,
                              cfg.vocab)
    # reference: full forward logits at each position
    full, _, _, _ = forward(p, cfg, tokens=toks, mode="train",
                            moe_impl="dense")
    _, caches, _, _ = forward(p, cfg, tokens=toks[:, :S], mode="prefill",
                              moe_impl="dense")
    assert caches[0]["core"].k.shape[2] == cfg.window      # ring-sized
    pos = S
    for i in range(4):
        lg, caches = decode_step(p, cfg, toks[:, S + i:S + i + 1], caches,
                                 pos, moe_impl="dense")
        rel = (float(jnp.abs(lg[:, 0] - full[:, S + i]).max())
               / float(jnp.abs(full[:, S + i]).max()))
        assert rel < 2e-2, (i, rel)
        pos += 1


def test_ssm_chunk_invariance():
    from repro.models import ssm
    from repro.models.config import MambaCfg
    m = MambaCfg(d_state=4)
    p = ssm.mamba_init(KEY, 16, m, jnp.float32)
    x = jax.random.normal(KEY, (2, 33, 16))
    y1, _ = ssm.mamba_apply(p, x, m, chunk=8)
    y2, _ = ssm.mamba_apply(p, x, m, chunk=64)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_loss_chunk_invariance():
    cfg = get_smoke_config("minitron-8b")
    p = init_params(KEY, cfg)
    batch = {"tokens": jax.random.randint(KEY, (2, 32), 0, cfg.vocab)}
    l1, _ = loss_fn(p, cfg.scaled(loss_chunk=8), batch, moe_impl="dense")
    l2, _ = loss_fn(p, cfg.scaled(loss_chunk=4096), batch, moe_impl="dense")
    assert abs(float(l1) - float(l2)) < 1e-5


def test_mrope_text_equals_rope():
    """M-RoPE with equal position streams == standard RoPE."""
    from repro.models.layers import apply_mrope, apply_rope
    x = jax.random.normal(KEY, (2, 16, 4, 128))
    pos = jnp.broadcast_to(jnp.arange(16)[None, :], (2, 16))
    pos3 = jnp.broadcast_to(pos[..., None], (2, 16, 3))
    a = apply_rope(x, pos, 1e4)
    b = apply_mrope(x, pos3, 1e4, (16, 24, 24))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_virtual_experts_exact_equivalence():
    """moe_virtual_split=2: splitting each expert's FFN into column shards
    is mathematically exact (y = sum_v (x @ wi_v) @ wo_v)."""
    import dataclasses
    from repro.models import moe as M
    from repro.models.config import BlockSpec, ModelConfig, MoECfg
    base = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                       n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
                       period=(BlockSpec("attn", "moe"),),
                       moe=MoECfg(num_experts=4, top_k=2, d_ff_expert=64,
                                  capacity_factor=8.0))
    cfg2 = base.scaled(moe_virtual_split=2)
    p1 = M.moe_init(KEY, base, jnp.float32)
    e, d, f = p1["wi"].shape
    p2 = {"router": p1["router"],
          "wi": p1["wi"].reshape(e, d, 2, f // 2).transpose(0, 2, 1, 3)
                        .reshape(2 * e, d, f // 2),
          "wg": p1["wg"].reshape(e, d, 2, f // 2).transpose(0, 2, 1, 3)
                        .reshape(2 * e, d, f // 2),
          "wo": p1["wo"].reshape(e, 2, f // 2, d).reshape(2 * e, f // 2, d)}
    x = jax.random.normal(KEY, (2, 40, 32))
    y1, _ = M.moe_apply_capacity(p1, x, base, group_size=16)
    y2, _ = M.moe_apply_capacity(p2, x, cfg2, group_size=16)
    y2d, _ = M.moe_apply_dense(p2, x, cfg2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y2d), atol=1e-4)
