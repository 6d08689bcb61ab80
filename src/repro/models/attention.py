"""Attention blocks: GQA (+ sliding window), MLA (DeepSeek-V2), cross-attn.

Three execution modes share one set of weights:
  * mode="train"/"prefill": full-sequence causal attention (optionally
    windowed).  Prefill additionally returns the KV cache.
  * mode="decode": one new token against a cache.  GQA decode can run via
    the Pallas flash_decode kernel (use_pallas=True) or the jnp reference —
    identical math; the jnp path is what the multi-pod dry-run lowers (the
    HLO roofline terms are equivalent).

Caches:
  * full cache   k,v (B, S, K, hd) + length (B,)
  * ring cache   k,v (B, W, K, hd) + absolute position — sliding-window
    (mixtral) long-context decode in O(W) memory: the sub-quadratic path.
  * MLA latent   c_kv (B, S, r) + k_rope (B, S, rd): decode works entirely
    in the r-dim latent space (absorbed projections), the paper-exact trick
    from DeepSeek-V2 — per-token cache is r+rd instead of 2*K*hd.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig, YarnCfg
from .layers import apply_mrope, apply_rope, causal_mask, dense, dense_init


class KVCache(NamedTuple):
    k: jnp.ndarray          # (B, S, K, hd) — or (B, W, K, hd) ring buffer
    v: jnp.ndarray
    length: jnp.ndarray     # (B,) int32 — tokens currently valid
    # NB: ring (sliding-window) addressing is a *static* property derived
    # from cfg.window, never stored here — it must not be traced.


class MLACache(NamedTuple):
    c_kv: jnp.ndarray       # (B, S, r)
    k_rope: jnp.ndarray     # (B, S, rd)
    length: jnp.ndarray


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(key, cfg: ModelConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hdim
    ks = jax.random.split(key, 4)
    return {"wq": dense_init(ks[0], d, h * hd, dtype),
            "wk": dense_init(ks[1], d, kv * hd, dtype),
            "wv": dense_init(ks[2], d, kv * hd, dtype),
            "wo": dense_init(ks[3], h * hd, d, dtype)}


def _split_heads(x, n, hd):
    return x.reshape(x.shape[:-1] + (n, hd))


def _rope_or_mrope(x, positions, cfg: ModelConfig):
    if cfg.mrope:
        # positions (B, S, 3); hd/2 partitioned per qwen2-vl
        # ([16,24,24] at hd=128, scaled proportionally otherwise).
        half = x.shape[-1] // 2
        s0 = max(1, round(half * 16 / 64))
        s1 = (half - s0) // 2
        s2 = half - s0 - s1
        return apply_mrope(x, positions, cfg.rope_theta, (s0, s1, s2))
    return apply_rope(x, positions, cfg.rope_theta)


def _sdpa_chunked(q, k, v, cfg: ModelConfig, sm_scale, *, causal: bool,
                  qchunk: int):
    """Memory-bounded causal attention: scan over query blocks, each block
    attending to full K/V — scores are (B, H, qc, S), never (S, S).

    This is the streaming-accumulation discipline again: the query stream is
    processed block-by-block against a resident K/V, exactly how the Pallas
    flash kernel tiles, expressed at the jnp level so it shards under pjit.
    """
    b, s, h, hd = q.shape
    nblk = s // qchunk
    qb = q.reshape(b, nblk, qchunk, h, hd).transpose(1, 0, 2, 3, 4)

    @jax.checkpoint
    def blk_body(i, qblk):
        # checkpointed so the scan VJP saves only (i, qblk), never the
        # (B, H, qc, S) score blocks — flash-attention memory discipline
        if causal:
            mask = causal_mask(qchunk, s, offset=i * qchunk,
                               window=cfg.window)
        else:
            mask = jnp.zeros((qchunk, s), jnp.float32)
        return _sdpa(qblk, k, v, mask, sm_scale)

    def blk(carry, args):
        i, qblk = args
        return carry, blk_body(i, qblk)

    _, outs = jax.lax.scan(blk, (), (jnp.arange(nblk), qb))
    return outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, hd)


def _sdpa(q, k, v, mask, sm_scale):
    """q (B,S,H,hd), k/v (B,T,K,hd) grouped; mask (B,1,S,T) or (S,T)."""
    b, s, h, hd = q.shape
    kheads = k.shape[2]
    g = h // kheads
    qg = q.reshape(b, s, kheads, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * sm_scale
    if mask.ndim == 2:
        mask = mask[None, None, None]
    else:
        mask = mask[:, None, :, :][:, :, None]   # (B,1,1,S,T)
    scores = scores + mask
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, hd)


class CacheAppend(NamedTuple):
    """What one decode step of an attention layer adds to its cache:
    the new rows of each cache array (B, s, ...), in the cache's field
    order; the sequence index of each (B, s), past the cache for a token
    that enters nothing; and the new length (B,).  ``write_appended``
    puts a stack of them into the stacked cache."""
    rows: Tuple[jnp.ndarray, ...]
    at: jnp.ndarray
    length: jnp.ndarray


def _with_rows(old, new, at):
    """``old`` (N, T, ...) with ``new[:, q]`` (N, s, ...) at sequence
    index ``at[:, q]`` (N, s).  An index outside [0, T) puts nothing in;
    a later q wins a repeated index.  A select over the sequence axis,
    which the compiler fuses into whatever reads the result."""
    t = jnp.arange(old.shape[1], dtype=at.dtype)
    tail = (1,) * (old.ndim - 2)
    for q in range(at.shape[1]):
        hit = (at[:, q, None] == t).reshape(at.shape[:1] + t.shape + tail)
        old = jnp.where(hit, new[:, q:q + 1], old)
    return old


def _append(arrays, rows, at, token_mask, length):
    """This step's cache as the layer's attention reads it, and what the
    step appends.  ``rows[i]`` (B, s, ...) go into ``arrays[i]``
    (B, T, ...) at the sequence indices ``at`` (B, s).  A token that
    ``token_mask`` (B, s) leaves out — an idle serving slot, a chunk's
    padding — gets index T, past the cache, so neither its row nor the
    slot's length moves.  The mask leaves out a suffix of each row."""
    b, s = at.shape
    grown = s
    if token_mask is not None:
        at = jnp.where(token_mask, at, arrays[0].shape[1])
        grown = jnp.count_nonzero(token_mask, axis=1).astype(at.dtype)
    read = tuple(_with_rows(a, r, at) for a, r in zip(arrays, rows))
    return read, CacheAppend(tuple(rows), at, length + grown)


def write_appended(cfg: ModelConfig, cache, app: CacheAppend):
    """A stack's cache (L, B, T, ...) with its layers' appends (L, B,
    s, ...) written in, and their lengths.  Each batch row takes one
    read-modify-write of the 128-aligned window of the sequence axis
    that holds its new rows, across every layer, in a loop over the
    rows; a row that appends nothing writes its window back unchanged.  The TPU keeps a cache whose rows are narrower than its 128
    lanes with the sequence axis minor-most, and a scatter or a one-row
    update into it makes XLA relayout the whole cache first; a window of
    whole lanes is updated where it lies, so a donated cache is written
    in place.  The layers of a stack hold the same tokens, so the window
    comes from the first layer's indices.  An extend (s > 1) of the ring
    cache can wrap round it, so it takes the whole ring."""
    arrays = tuple(cache)[:-1]
    t = arrays[0].shape[2]
    s = app.at.shape[-1]
    w = t if (cfg.window is not None and s > 1) \
        else min(t, -(-(s + 127) // 128) * 128)
    first = jnp.minimum(app.at[0, :, 0], t - 1)
    starts = jnp.minimum(first // 128 * 128, t - w)

    def row(arrays, args):
        b, st = args
        idx = jax.lax.dynamic_index_in_dim(app.at, b, 1,
                                           keepdims=False) - st
        out = []
        for a, r in zip(arrays, app.rows):
            corner = (0, b, st) + (0,) * (a.ndim - 3)
            win = jax.lax.dynamic_slice(a, corner,
                                        (a.shape[0], 1, w) + a.shape[3:])
            new = jax.lax.dynamic_index_in_dim(r, b, 1, keepdims=False)
            win = _with_rows(win[:, 0], new, idx)[:, None]
            out.append(jax.lax.dynamic_update_slice(a, win, corner))
        return tuple(out), None

    arrays, _ = jax.lax.scan(
        row, arrays, (jnp.arange(arrays[0].shape[1]), starts))
    return type(cache)(*arrays, app.length)


def gqa_apply(params, x, cfg: ModelConfig, *, positions, mode: str = "train",
              cache: Optional[KVCache] = None,
              kv_override: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
              cross: bool = False, causal: bool = True, token_mask=None):
    """Returns (out, new_cache).  In decode, ``new_cache`` is the
    step's ``CacheAppend``, and ``token_mask`` (B, s) bool marks the
    tokens that enter the cache (see ``_append``)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hdim
    sm_scale = hd ** -0.5
    is_ring = cfg.window is not None          # static

    q = _split_heads(dense(params["wq"], x), h, hd)
    if kv_override is not None:                  # cross-attention memory
        k, v = kv_override
    else:
        k = _split_heads(dense(params["wk"], x), kvh, hd)
        v = _split_heads(dense(params["wv"], x), kvh, hd)

    if not cross:
        q = _rope_or_mrope(q, positions, cfg)
        if kv_override is None:
            k = _rope_or_mrope(k, positions, cfg)

    new_cache = cache
    if mode in ("train", "prefill"):
        qchunk = cfg.attn_qchunk
        if s > qchunk and s % qchunk == 0:
            out = _sdpa_chunked(q, k, v, cfg, sm_scale,
                                causal=(causal and not cross), qchunk=qchunk)
        else:
            if cross or not causal:
                t = k.shape[1]
                mask = jnp.zeros((s, t), jnp.float32)
            else:
                mask = causal_mask(s, s, window=cfg.window)
            out = _sdpa(q, k, v, mask, sm_scale)
        if mode == "prefill" and not cross:
            from .layers import shard_hint
            # cache layout: head_dim on 'model' — matches the natural
            # projection sharding, so no cross-layout reshard of the cache
            # (GSPMD's replicate-fallback costs ~17 GB/layer otherwise)
            k = shard_hint(k, cfg, ("dp", None, None, "model"))
            v = shard_hint(v, cfg, ("dp", None, None, "model"))
            if is_ring:
                # Pack the last W positions into ring order: slot j holds
                # the latest p <= s-1 with p % W == j.  Slots with p < 0
                # (when s < W) hold garbage but are masked at decode.
                w = cfg.window
                j = jnp.arange(w)
                p = (s - 1) - ((s - 1 - j) % w)
                p_safe = jnp.clip(p, 0, s - 1)
                new_cache = KVCache(k=k[:, p_safe], v=v[:, p_safe],
                                    length=jnp.full((b,), s, jnp.int32))
            else:
                new_cache = KVCache(k=k, v=v,
                                    length=jnp.full((b,), s, jnp.int32))
    elif mode == "decode":
        # Decode/extend against a cache.  Each batch row appends its ``s``
        # new tokens at its OWN ``length[row]`` (continuous-batching slots
        # hold requests at heterogeneous positions): attention reads the
        # cache with them selected in, and the layer returns them as a
        # ``CacheAppend`` that the stack writes after its layer scan
        # (``write_appended``).  s == 1 is the classic decode step; s > 1
        # is a chunked-prefill extend: the chunk attends causally to
        # [0, length + qi] per chunk-local query qi.  Out-of-bounds
        # positions (an idle serving slot past max_len) are dropped
        # rather than clamped, and so are masked tokens.
        if cache is None:
            raise ValueError("gqa_apply: mode='decode' needs a cache")
        length = cache.length                    # (B,) tokens already cached
        qi = jnp.arange(s, dtype=length.dtype)   # chunk-local query offsets
        newpos = length[:, None] + qi[None, :]   # (B, s) absolute positions
        if is_ring:
            # Ring (sliding-window) cache: slot j holds the latest absolute
            # position p <= L with p % W == j  =>  p = L - ((L - j) % W).
            w = cache.k.shape[1]
            (ck, cv), new_cache = _append((cache.k, cache.v), (k, v),
                                          newpos % w, token_mask, length)
            j = jnp.arange(w)[None, :]
            last = new_cache.length[:, None] - 1
            pos_k = last - ((last - j) % w)                  # (B, W)
            # query qi sees ring positions in (newpos - w, newpos]
            valid = (pos_k[:, None, :] <= newpos[..., None]) \
                & (pos_k[:, None, :] > newpos[..., None] - w) \
                & (pos_k[:, None, :] >= 0)
        else:
            (ck, cv), new_cache = _append((cache.k, cache.v), (k, v),
                                          newpos, token_mask, length)
            t = ck.shape[1]
            j = jnp.arange(t)[None, None, :]
            valid = j <= newpos[..., None]                   # (B, s, T)
            if cfg.window is not None:
                valid &= j > (newpos[..., None] - cfg.window)
        mask = jnp.where(valid, 0.0, -1e30)               # (B, s, T)
        out = _sdpa(q, ck, cv, mask, sm_scale)
    else:
        raise ValueError(mode)

    out = out.astype(x.dtype).reshape(b, s, h * hd)
    return dense(params["wo"], out), new_cache


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V2's ``yarn_get_mscale``: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, y: YarnCfg) -> np.ndarray:
    """YaRN inverse frequencies (dim/2,), float32: each frequency is
    interpolated between the extrapolated ``1/base^(2i/dim)`` and the
    interpolated ``1/(factor * base^(2i/dim))`` by a linear ramp between
    the correction dimensions of ``beta_fast`` and ``beta_slow`` (the
    published ``DeepseekV2YarnRotaryEmbedding``)."""
    def corr_dim(rot):
        return (dim * math.log(y.original_max_position
                               / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr_dim(y.beta_fast)), 0)
    high = min(math.ceil(corr_dim(y.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y.factor
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                       # 1: extrapolate, 0: interpolate
    return (inter * (1.0 - keep) + extra * keep).astype(np.float32)


def mla_rope(cfg: ModelConfig):
    """(inv_freq or None, cos/sin scale, softmax scale) of MLA's rope
    part: YaRN where ``cfg.rope_scaling`` is set, plain RoPE otherwise.
    The published code permutes each head's rope dimensions from
    interleaved pairs to halves before rotating halves; on weights made
    here that is a fixed permutation of the columns of ``wq``'s rope part
    and of ``wkr``, which matters only for loading a published
    checkpoint."""
    sm_scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    y = cfg.rope_scaling
    if y is None:
        return None, 1.0, sm_scale
    freqs = jnp.asarray(yarn_inv_freq(cfg.qk_rope_dim, cfg.rope_theta, y))
    scale = yarn_mscale(y.factor, y.mscale) \
        / yarn_mscale(y.factor, y.mscale_all_dim)
    if y.mscale_all_dim:
        m = yarn_mscale(y.factor, y.mscale_all_dim)
        sm_scale = sm_scale * m * m
    return freqs, scale, sm_scale


def mla_init(key, cfg: ModelConfig, dtype):
    d, h = cfg.d_model, cfg.n_heads
    r, nd, rd, vd = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    ks = jax.random.split(key, 6)
    return {"wq": dense_init(ks[0], d, h * (nd + rd), dtype),
            "wdkv": dense_init(ks[1], d, r, dtype),
            "wkr": dense_init(ks[2], d, rd, dtype),
            "wuk": dense_init(ks[3], r, h * nd, dtype),
            "wuv": dense_init(ks[4], r, h * vd, dtype),
            "wo": dense_init(ks[5], h * vd, d, dtype),
            "c_norm": jnp.ones((r,), dtype)}


def mla_apply(params, x, cfg: ModelConfig, *, positions, mode: str = "train",
              cache: Optional[MLACache] = None, token_mask=None):
    """Returns (out, new_cache). Decode runs fully absorbed in the latent
    space — the cache stores only (c_kv, k_rope): r+rd floats per token.
    In decode, ``new_cache`` is the step's ``CacheAppend``, and
    ``token_mask`` (B, s) marks the tokens that enter the cache (see
    ``_append``)."""
    from .layers import rmsnorm  # local import to avoid cycle at module load

    b, s, d = x.shape
    h = cfg.n_heads
    r, nd, rd, vd = (cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
                     cfg.v_head_dim)
    freqs, rscale, sm_scale = mla_rope(cfg)

    q = _split_heads(dense(params["wq"], x), h, nd + rd)   # (B,S,H,nd+rd)
    qn, qr = q[..., :nd], q[..., nd:]
    qr = apply_rope(qr, positions, cfg.rope_theta, freqs=freqs, scale=rscale)

    c = rmsnorm(params["c_norm"], dense(params["wdkv"], x), cfg.norm_eps,
                policy=cfg.norm_reduce_policy)
    kr = dense(params["wkr"], x)[:, :, None, :]             # (B,S,1,rd)
    kr = apply_rope(kr, positions, cfg.rope_theta, freqs=freqs,
                    scale=rscale)[:, :, 0]                  # (B,S,rd)

    if mode in ("train", "prefill"):
        kn = _split_heads(dense(params["wuk"], c), h, nd)   # (B,S,H,nd)
        v = _split_heads(dense(params["wuv"], c), h, vd)    # (B,S,H,vd)
        knf = kn.astype(jnp.float32)
        krf = kr.astype(jnp.float32)
        vf = v.astype(jnp.float32)

        def block(qn_blk, qr_blk, offset):
            sc = (jnp.einsum("bshd,bthd->bhst", qn_blk, knf)
                  + jnp.einsum("bshd,btd->bhst", qr_blk, krf)) * sm_scale
            sc = sc + causal_mask(qn_blk.shape[1], s, offset=offset)[None, None]
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bhst,bthd->bshd", p, vf)

        qchunk = cfg.attn_qchunk
        if s > qchunk and s % qchunk == 0:
            nblk = s // qchunk
            qnb = qn.astype(jnp.float32).reshape(
                b, nblk, qchunk, h, nd).transpose(1, 0, 2, 3, 4)
            qrb = qr.astype(jnp.float32).reshape(
                b, nblk, qchunk, h, rd).transpose(1, 0, 2, 3, 4)

            block_ckpt = jax.checkpoint(block)

            def scan_blk(carry, args):
                i, qnq, qrq = args
                return carry, block_ckpt(qnq, qrq, i * qchunk)

            _, outs = jax.lax.scan(scan_blk, (),
                                   (jnp.arange(nblk), qnb, qrb))
            out = outs.transpose(1, 0, 2, 3, 4).reshape(b, s, h, vd)
        else:
            out = block(qn.astype(jnp.float32), qr.astype(jnp.float32), 0)
        new_cache = cache
        if mode == "prefill":
            from .layers import shard_hint
            c_sh = shard_hint(c, cfg, ("dp", None, "model"))
            kr_sh = shard_hint(kr, cfg, ("dp", None, None))
            new_cache = MLACache(c_kv=c_sh, k_rope=kr_sh,
                                 length=jnp.full((b,), s, jnp.int32))
    elif mode == "decode":
        # Per-row append (continuous-batching slots sit at heterogeneous
        # lengths); s > 1 is a chunked-prefill extend with chunk-causal
        # masking, mirroring the GQA decode/extend branch.
        if cache is None:
            raise ValueError("mla_apply: mode='decode' needs a cache")
        length = cache.length
        newpos = length[:, None] + jnp.arange(s, dtype=length.dtype)[None, :]
        (cc, ckr), new_cache = _append((cache.c_kv, cache.k_rope), (c, kr),
                                       newpos, token_mask, length)
        t = cc.shape[1]
        # absorb W_uk into the query: q_eff (B,s,H,r)
        wuk = params["wuk"].reshape(r, h, nd)
        q_eff = jnp.einsum("bshd,rhd->bshr", qn.astype(jnp.float32),
                           wuk.astype(jnp.float32))
        scores = (jnp.einsum("bshr,btr->bhst", q_eff,
                             cc.astype(jnp.float32))
                  + jnp.einsum("bshd,btd->bhst", qr.astype(jnp.float32),
                               ckr.astype(jnp.float32))) * sm_scale
        valid = jnp.arange(t)[None, None, :] <= newpos[..., None]  # (B,s,t)
        scores = scores + jnp.where(valid, 0.0, -1e30)[:, None, :, :]
        p = jax.nn.softmax(scores, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", p, cc.astype(jnp.float32))
        wuv = params["wuv"].reshape(r, h, vd)
        out = jnp.einsum("bshr,rhd->bshd", o_lat, wuv.astype(jnp.float32))
    else:
        raise ValueError(mode)

    out = out.astype(x.dtype).reshape(b, s, h * vd)
    return dense(params["wo"], out), new_cache
