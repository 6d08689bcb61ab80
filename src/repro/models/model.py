"""The unified LM: init / train forward / prefill / decode for every
assigned architecture.

Layer stacking is scan-over-periods: parameters for each position in the
period pattern are stacked with a leading ``n_periods`` axis and consumed by
``lax.scan``, so HLO size is O(period), not O(depth) — essential for the
512-device dry-run compiles.  Heterogeneous stacks (Jamba 1:7, xLSTM m/s
mix, MoE-every-k) fall out of the period pattern.  Decode reads the
per-layer caches through the same scan and writes each attention
layer's new rows into them after it.  Leading dense layers
(``cfg.first_dense``, deepseek's ``first_k_dense_replace``) are a stack of
their own, run before the periods, and their caches come first in the
cache list (``cfg.cache_pattern``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from . import attention as attn
from . import moe as moe_mod
from . import ssm
from .config import BlockSpec, MambaCfg, ModelConfig, XLSTMCfg
from .layers import (dense, dense_init, embed_init, embed_lookup, gelu_mlp,
                     gelu_mlp_init, rmsnorm, rmsnorm_init, swiglu, swiglu_init)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _block_init(key, spec: BlockSpec, cfg: ModelConfig, dtype, *,
                cross: bool = False):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": rmsnorm_init(cfg.d_model, dtype)}
    if spec.kind == "attn":
        if cfg.attn_type == "mla":
            p["core"] = attn.mla_init(ks[0], cfg, dtype)
        else:
            p["core"] = attn.gqa_init(ks[0], cfg, dtype)
    elif spec.kind == "mamba":
        p["core"] = ssm.mamba_init(ks[0], cfg.d_model,
                                   cfg.mamba or MambaCfg(), dtype)
    elif spec.kind == "mlstm":
        p["core"] = ssm.mlstm_init(ks[0], cfg.d_model,
                                   cfg.xlstm or XLSTMCfg(), dtype)
    elif spec.kind == "slstm":
        p["core"] = ssm.slstm_init(ks[0], cfg.d_model,
                                   cfg.xlstm or XLSTMCfg(), dtype)
    else:
        raise ValueError(spec.kind)
    if cross:
        p["norm_x"] = rmsnorm_init(cfg.d_model, dtype)
        p["cross"] = attn.gqa_init(ks[1], cfg, dtype)
    if spec.mlp != "none":
        p["norm2"] = rmsnorm_init(cfg.d_model, dtype)
        if spec.mlp == "moe":
            p["mlp"] = moe_mod.moe_init(ks[2], cfg, dtype)
        elif spec.mlp == "swiglu":
            p["mlp"] = swiglu_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
        else:
            p["mlp"] = gelu_mlp_init(ks[2], cfg.d_model, cfg.d_ff, dtype)
    return p


def _stacked_block_init(key, spec, cfg, dtype, n, **kw):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: _block_init(k, spec, cfg, dtype, **kw))(keys)


def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8 + len(cfg.period))
    params: Dict[str, Any] = {}
    # the embed table always exists: embed_inputs archs (vlm/audio) consume
    # precomputed embeddings at prefill but decode with text tokens
    params["embed"] = embed_init(keys[0], cfg.padded_vocab,
                                 cfg.d_model, dtype)
    params["blocks"] = [
        _stacked_block_init(keys[1 + j], spec, cfg, dtype, cfg.n_periods,
                            cross=cfg.is_encdec)
        for j, spec in enumerate(cfg.period)]
    if cfg.first_dense:
        params["lead_blocks"] = _stacked_block_init(
            keys[7], cfg.lead_spec, cfg, dtype, cfg.first_dense)
    params["final_norm"] = rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(
            keys[5], cfg.d_model, cfg.padded_vocab, dtype)
    if cfg.is_encdec:
        enc_spec = BlockSpec("attn", "gelu")
        params["encoder"] = {
            "blocks": _stacked_block_init(keys[6], enc_spec, cfg, dtype,
                                          cfg.encoder_layers),
            "final_norm": rmsnorm_init(cfg.d_model, dtype),
        }
    return params


# ---------------------------------------------------------------------------
# blocks
# ---------------------------------------------------------------------------


def _constrain_act(x, cfg: ModelConfig):
    """Pin the batch axis of an activation to the dp mesh axes.  Without
    this GSPMD may all-gather the batch to exploit the FSDP (data)-sharded
    contracting dim of a weight — a 16x activation-memory blowup."""
    if cfg.act_dp_axes:
        dp = cfg.act_dp_axes if len(cfg.act_dp_axes) > 1 \
            else cfg.act_dp_axes[0]
        sp = cfg.act_sp_axis
        if sp is not None and x.ndim >= 3 and x.shape[1] > 1:
            return jax.lax.with_sharding_constraint(
                x, P(*((dp, sp) + (None,) * (x.ndim - 2))))
        return jax.lax.with_sharding_constraint(
            x, P(*((dp,) + (None,) * (x.ndim - 1))))
    return x


def _apply_block(bp, spec: BlockSpec, x, cfg: ModelConfig, *, positions,
                 mode, cache, enc_out, moe_impl, is_causal=True,
                 token_mask=None):
    """(x, new cache, aux loss, routed): ``routed`` is the held-expert
    path's per-expert pair count, else None."""
    aux = jnp.float32(0.0)
    routed = None
    h = rmsnorm(bp["norm1"], x, cfg.norm_eps,
                policy=cfg.norm_reduce_policy)
    new_cache = {}
    core_cache = None if cache is None else cache.get("core")

    if spec.kind == "attn":
        if cfg.attn_type == "mla":
            out, c2 = attn.mla_apply(bp["core"], h, cfg, positions=positions,
                                     mode=mode, cache=core_cache,
                                     token_mask=token_mask)
        else:
            out, c2 = attn.gqa_apply(bp["core"], h, cfg, positions=positions,
                                     mode=mode, cache=core_cache,
                                     causal=is_causal, token_mask=token_mask)
        new_cache["core"] = c2
    elif spec.kind == "mamba":
        out, c2 = ssm.mamba_apply(bp["core"], h, cfg.mamba or MambaCfg(),
                                  mode=mode, state=core_cache,
                                  chunk=cfg.scan_chunk, cfg=cfg)
        new_cache["core"] = c2
    elif spec.kind == "mlstm":
        out, c2 = ssm.mlstm_apply(bp["core"], h, cfg.xlstm or XLSTMCfg(),
                                  mode=mode, state=core_cache,
                                  chunk=cfg.scan_chunk)
        new_cache["core"] = c2
    elif spec.kind == "slstm":
        out, c2 = ssm.slstm_apply(bp["core"], h, cfg.xlstm or XLSTMCfg(),
                                  mode=mode, state=core_cache)
        new_cache["core"] = c2
    else:
        raise ValueError(spec.kind)
    x = x + out

    if "cross" in bp and enc_out is not None:
        # Cross-attention KV is recomputed from the encoder memory each call
        # (cheap relative to self-attention; avoids cache-structure drift
        # between prefill and decode).
        hx = rmsnorm(bp["norm_x"], x, cfg.norm_eps,
                     policy=cfg.norm_reduce_policy)
        k = dense(bp["cross"]["wk"], enc_out)
        v = dense(bp["cross"]["wv"], enc_out)
        hd = cfg.hdim
        k = k.reshape(k.shape[:-1] + (cfg.n_kv_heads, hd))
        v = v.reshape(v.shape[:-1] + (cfg.n_kv_heads, hd))
        out, _ = attn.gqa_apply(bp["cross"], hx, cfg, positions=positions,
                                mode="train", kv_override=(k, v), cross=True)
        x = x + out

    if spec.mlp != "none":
        h2 = rmsnorm(bp["norm2"], x, cfg.norm_eps,
                     policy=cfg.norm_reduce_policy)
        if spec.mlp == "moe" and moe_impl == "held":
            out, routed = moe_mod.moe_apply_held(bp["mlp"], h2, cfg,
                                                 token_mask)
        elif spec.mlp == "moe":
            out, a = moe_mod.moe_apply(bp["mlp"], h2, cfg, impl=moe_impl)
            aux = aux + a
        elif spec.mlp == "swiglu":
            out = swiglu(bp["mlp"], h2)
        else:
            out = gelu_mlp(bp["mlp"], h2)
        x = x + out
    return x, new_cache, aux, routed


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def _run_stack(params_blocks, cfg: ModelConfig, x, *, positions, mode,
               caches, enc_out, moe_impl, remat: bool = False,
               is_causal=True, pattern=None, token_mask=None):
    """Scan over periods. ``caches``: list per pattern position of stacked
    cache pytrees (leading axis n_periods) or None.  Returns (x, caches,
    aux loss, routed): ``routed`` (MoE layers, held experts) int32 on the
    held-expert path, else None.  In decode the scan reads the caches
    and returns only what each attention layer appends; the rows are
    written into the stacked caches after it, so no layer's cache is
    copied to take one row."""
    pattern = pattern or cfg.period

    def period_body(xc, scanned):
        bps, cs = scanned
        aux = jnp.float32(0.0)
        new_cs = []
        routed = []
        xc = _constrain_act(xc, cfg)
        # detlint: ok[DET002] aux-loss scalar chain across unrolled
        # blocks: legacy bits pinned by tests; front-door routing is the
        # knob-gated follow-up (docs/algebra.md)
        for j, spec in enumerate(pattern):
            c_j = None if cs is None else cs[j]
            xc, nc, a, r = _apply_block(bps[j], spec, xc, cfg,
                                        positions=positions, mode=mode,
                                        cache=c_j, enc_out=enc_out,
                                        moe_impl=moe_impl,
                                        is_causal=is_causal,
                                        token_mask=token_mask)
            xc = _constrain_act(xc, cfg)
            new_cs.append(nc)
            aux = aux + a
            if r is not None:
                routed.append(r)
        return xc, (tuple(new_cs), aux, tuple(routed))

    body = period_body
    if remat:
        body = jax.checkpoint(period_body, prevent_cse=False)

    def scan_fn(xc, scanned):
        return body(xc, scanned)

    cs_stacked = None if caches is None else tuple(caches)
    nper = jax.tree.leaves(params_blocks[0])[0].shape[0]
    if nper <= 2:
        # Unrolled: dry-run depth-1/2 cost variants need the period body in
        # the top-level HLO (XLA cost_analysis counts while bodies ONCE,
        # independent of trip count, so scanned variants measure nothing).
        ys = []
        for i in range(nper):
            sl = jax.tree.map(lambda t: t[i],
                              (tuple(params_blocks), cs_stacked))
            x, y = scan_fn(x, sl)
            ys.append(y)
        new_caches, auxs, routed = jax.tree.map(
            lambda *t: jnp.stack(t), *ys) if ys \
            else ((), jnp.zeros((0,)), ())
    else:
        x, (new_caches, auxs, routed) = jax.lax.scan(
            scan_fn, x, (tuple(params_blocks), cs_stacked))
    # (periods, held) per MoE position -> (MoE layers, held), layer order
    routed = jnp.stack(routed, axis=1).reshape(-1, routed[0].shape[-1]) \
        if routed else None
    new_caches = [_write_appends(cfg, c, n) for c, n in
                  zip(caches, new_caches, strict=True)] \
        if caches is not None else list(new_caches)
    return x, new_caches, jnp.sum(auxs), routed  # detlint: ok[DET001] L aux scalars


def _write_appends(cfg: ModelConfig, cache, new):
    """A decode step's stacked cache: an attention stack's appended rows
    written into its cache (``attn.write_appended``, in place where the
    cache is donated), a recurrent state as the scan returned it."""
    if isinstance(new["core"], attn.CacheAppend):
        return {**new, "core": attn.write_appended(cfg, cache["core"],
                                                   new["core"])}
    return new


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _default_positions(cfg: ModelConfig, bsz, s, offset=0):
    """``offset`` is a scalar (shared position) or a (B,) array — serving
    slots in a continuous batch sit at per-request positions."""
    off = jnp.asarray(offset, jnp.int32)
    pos = jnp.arange(s, dtype=jnp.int32)[None, :]
    pos = pos + (off[:, None] if off.ndim == 1 else off)
    pos = jnp.broadcast_to(pos, (bsz, s))
    if cfg.mrope:
        pos = jnp.broadcast_to(pos[..., None], (bsz, s, 3))
    return pos


def encode(params, cfg: ModelConfig, enc_embeds, *, remat=False):
    """Encoder stack (enc-dec only); enc_embeds (B, S, D) from the stub
    modality frontend."""
    bsz, s, _ = enc_embeds.shape
    positions = _default_positions(cfg, bsz, s)
    enc_cfg_pattern = (BlockSpec("attn", "gelu"),)
    x, _, _, _ = _run_stack([params["encoder"]["blocks"]], cfg, enc_embeds,
                         positions=positions, mode="train", caches=None,
                         enc_out=None, moe_impl="capacity", remat=remat,
                         is_causal=False, pattern=enc_cfg_pattern)
    return rmsnorm(params["encoder"]["final_norm"], x, cfg.norm_eps,
                   policy=cfg.norm_reduce_policy)


def forward_hidden(params, cfg: ModelConfig, *, tokens=None, embeds=None,
                   positions=None, mode: str = "train", caches=None,
                   enc_out=None, moe_impl: str = "capacity",
                   remat: bool = False, position_offset=0,
                   token_mask=None):
    """Backbone only: returns (final-norm hidden states, caches, aux,
    routed); see ``forward``."""
    if embeds is not None:
        x = embeds
    else:
        x = embed_lookup(params["embed"], tokens)
    bsz, s = x.shape[0], x.shape[1]
    if positions is None:
        positions = _default_positions(cfg, bsz, s, position_offset)
    kw = dict(positions=positions, mode=mode, enc_out=enc_out,
              moe_impl=moe_impl, remat=remat, token_mask=token_mask)
    lead_caches = []
    if cfg.first_dense:
        x, lead_caches, _, _ = _run_stack(
            [params["lead_blocks"]], cfg, x, pattern=(cfg.lead_spec,),
            caches=None if caches is None else caches[:1], **kw)
        caches = None if caches is None else caches[1:]

    x, new_caches, aux, routed = _run_stack(params["blocks"], cfg, x,
                                            caches=caches, **kw)

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps,
                policy=cfg.norm_reduce_policy)
    return x, lead_caches + new_caches, aux, routed


def _lm_head(params, cfg: ModelConfig):
    return params["embed"].T if cfg.tie_embeddings else params["lm_head"]


def forward(params, cfg: ModelConfig, *, tokens=None, embeds=None,
            positions=None, mode: str = "train", caches=None,
            enc_out=None, moe_impl: str = "capacity", remat: bool = False,
            position_offset=0, logits_pspec=None, token_mask=None):
    """Returns (logits, new_caches, aux, routed).  ``aux`` is the MoE
    load-balance loss.  ``routed`` is None except under
    ``moe_impl="held"`` (one chip's share of the experts), where it is
    the (MoE layers, held experts) int32 count of the (token, choice)
    pairs each held expert computed; ``token_mask`` (B, S) then keeps
    the tokens that may route (padding and idle serving slots route
    nothing)."""
    x, new_caches, aux, routed = forward_hidden(
        params, cfg, tokens=tokens, embeds=embeds, positions=positions,
        mode=mode, caches=caches, enc_out=enc_out, moe_impl=moe_impl,
        remat=remat, position_offset=position_offset, token_mask=token_mask)
    logits = jnp.einsum("bsd,dv->bsv", x, _lm_head(params, cfg),
                        preferred_element_type=jnp.float32)
    if logits_pspec is not None:
        # keep the vocab axis sharded through the loss (26 GB/device if not)
        logits = jax.lax.with_sharding_constraint(logits, logits_pspec)
    return logits, new_caches, aux, routed


def loss_fn(params, cfg: ModelConfig, batch, *, moe_impl="capacity",
            remat=False, aux_weight: float = 0.01, logits_pspec=None):
    """batch: tokens (B,S) [+ optional embeds/enc_embeds/positions];
    next-token xent in f32 with an MoE load-balance aux term."""
    tokens = batch.get("tokens")
    embeds = batch.get("embeds")
    enc_out = None
    if cfg.is_encdec:
        enc_out = encode(params, cfg, batch["enc_embeds"], remat=remat)
    hidden, _, aux, _ = forward_hidden(
        params, cfg, tokens=tokens, embeds=embeds,
        positions=batch.get("positions"), mode="train",
        enc_out=enc_out, moe_impl=moe_impl, remat=remat)
    labels = batch.get("labels")
    if labels is None:
        labels = tokens[:, 1:]
        hidden = hidden[:, :-1]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    else:
        mask = mask.astype(jnp.float32)[:, :labels.shape[1]]

    # Sequence-chunked fused head + xent.  Two disciplines at work:
    #  * gather-free: take_along_axis over the model-sharded vocab axis
    #    would make GSPMD all-gather the logits; instead lse reduces over
    #    the sharded axis (an all-reduce of (B, chunk)) and the label logit
    #    is a masked reduction;
    #  * chunked: only one (B, chunk, V) logits block is live at a time —
    #    256k-vocab archs would otherwise spend >10 GB/device here.  The
    #    chunk loop is a JugglePAC stream: per-chunk partial (nll, count)
    #    accumulate in the carry; the normalization happens once at the end.
    head = _lm_head(params, cfg)
    s = labels.shape[1]
    chunk = cfg.loss_chunk if (s % cfg.loss_chunk == 0) else s

    @jax.checkpoint
    def chunk_nll(h_c, lab_c, m_c):
        lg = jnp.einsum("bsd,dv->bsv", h_c, head,
                        preferred_element_type=jnp.float32)
        if logits_pspec is not None:
            lg = jax.lax.with_sharding_constraint(lg, logits_pspec)
        lse = jax.nn.logsumexp(lg, axis=-1)
        iota = jnp.arange(lg.shape[-1], dtype=jnp.int32)
        # detlint: ok[DET001] per-chunk xent math (label gather + masked
        # loss): legacy bits pinned by tests
        lab_logit = jnp.sum(
            jnp.where(iota[None, None, :] == lab_c[..., None], lg, 0.0),
            axis=-1)
        # detlint: ok[DET001] same xent chunk reduction as above
        return jnp.sum((lse - lab_logit) * m_c)

    if chunk == s:
        nll = chunk_nll(hidden, labels, mask)
    else:
        nb = s // chunk
        resh = lambda t: t.reshape(t.shape[0], nb, chunk, *t.shape[2:]) \
                          .swapaxes(0, 1)

        def body(acc, args):
            h_c, lab_c, m_c = args
            return acc + chunk_nll(h_c, lab_c, m_c), None

        nll, _ = jax.lax.scan(
            body, jnp.float32(0.0),
            (resh(hidden), resh(labels), resh(mask)))
    xent = nll / jnp.maximum(mask.sum(), 1.0)  # detlint: ok[DET001] token count, B*S well under 2^24
    loss = xent + aux_weight * aux
    return loss, {"xent": xent, "aux": aux,
                  "tokens": mask.sum()}  # detlint: ok[DET001] logging metric


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, bsz: int, max_len: int,
                dtype=None) -> list:
    """Stacked (layers-leading) cache pytrees per ``cfg.cache_pattern``
    entry: the leading dense layers' stack, then each period position."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    caches = []
    for i, spec in enumerate(cfg.cache_pattern):
        n = cfg.first_dense if cfg.first_dense and i == 0 else cfg.n_periods
        if spec.kind == "attn":
            if cfg.attn_type == "mla":
                c = attn.MLACache(
                    c_kv=jnp.zeros((n, bsz, max_len, cfg.kv_lora_rank), dtype),
                    k_rope=jnp.zeros((n, bsz, max_len, cfg.qk_rope_dim), dtype),
                    length=jnp.zeros((n, bsz), jnp.int32))
            else:
                s_alloc = (cfg.window if cfg.window is not None else max_len)
                c = attn.KVCache(
                    k=jnp.zeros((n, bsz, s_alloc, cfg.n_kv_heads, cfg.hdim),
                                dtype),
                    v=jnp.zeros((n, bsz, s_alloc, cfg.n_kv_heads, cfg.hdim),
                                dtype),
                    length=jnp.zeros((n, bsz), jnp.int32))
            caches.append({"core": c})
        elif spec.kind == "mamba":
            m = cfg.mamba or MambaCfg()
            di = m.expand * cfg.d_model
            caches.append({"core": ssm.MambaState(
                h=jnp.zeros((n, bsz, di, m.d_state), jnp.float32),
                conv=jnp.zeros((n, bsz, m.d_conv - 1, di), dtype))})
        elif spec.kind == "mlstm":
            xc = cfg.xlstm or XLSTMCfg()
            di = int(xc.proj_factor_m * cfg.d_model)
            hd = di // xc.num_heads
            caches.append({"core": ssm.MLSTMState(
                c=jnp.zeros((n, bsz, xc.num_heads, hd, hd), jnp.float32),
                n=jnp.zeros((n, bsz, xc.num_heads, hd), jnp.float32),
                m=jnp.zeros((n, bsz, xc.num_heads), jnp.float32),
                conv=jnp.zeros((n, bsz, xc.conv_kernel - 1, di), dtype))})
        elif spec.kind == "slstm":
            d = cfg.d_model
            caches.append({"core": ssm.SLSTMState(
                c=jnp.zeros((n, bsz, d), jnp.float32),
                n=jnp.ones((n, bsz, d), jnp.float32),
                h=jnp.zeros((n, bsz, d), dtype),
                m=jnp.zeros((n, bsz, d), jnp.float32))})
        else:
            raise ValueError(spec.kind)
    return caches


def pad_caches_to(cfg: ModelConfig, caches, max_len: int):
    """Grow prefill-shaped KV caches (seq axis == prefill length) to
    ``max_len`` so decode can append.  Ring / SSM caches are O(1) already."""
    def pad_block(c, spec: BlockSpec):
        core = c.get("core")
        if core is None:
            return c
        if isinstance(core, attn.KVCache) and cfg.window is None:
            s_now = core.k.shape[2]       # (n, B, S, K, hd)
            padn = max_len - s_now
            if padn > 0:
                padk = jnp.pad(core.k, ((0, 0), (0, 0), (0, padn),
                                        (0, 0), (0, 0)))
                padv = jnp.pad(core.v, ((0, 0), (0, 0), (0, padn),
                                        (0, 0), (0, 0)))
                return {**c, "core": attn.KVCache(padk, padv, core.length)}
        if isinstance(core, attn.MLACache):
            s_now = core.c_kv.shape[2]
            padn = max_len - s_now
            if padn > 0:
                pc = jnp.pad(core.c_kv, ((0, 0), (0, 0), (0, padn), (0, 0)))
                pr = jnp.pad(core.k_rope, ((0, 0), (0, 0), (0, padn), (0, 0)))
                return {**c, "core": attn.MLACache(pc, pr, core.length)}
        return c

    return [pad_block(c, spec) for c, spec in zip(caches, cfg.cache_pattern,
                                                   strict=True)]


def decode_step(params, cfg: ModelConfig, token, caches, position, *,
                enc_out=None, moe_impl: str = "capacity"):
    """One serving step: token (B, 1) -> (logits (B,1,V), new caches).

    ``position`` may be a scalar (lock-step batch) or a (B,) array of
    per-request positions (continuous batching: each slot appends at its
    own cache length).  ``token`` with s > 1 columns is a chunked-prefill
    extend for attention caches (SSM states remain one-token-at-a-time).
    """
    bsz, s = token.shape[0], token.shape[1]
    positions = _default_positions(cfg, bsz, s, position)
    logits, new_caches, _, _ = forward(params, cfg, tokens=token,
                                       positions=positions, mode="decode",
                                       caches=caches, enc_out=enc_out,
                                       moe_impl=moe_impl)
    return logits, new_caches
