"""Plain float32 reference of a DeepSeek-V2 decoder (``ModelConfig`` with
``attn_type="mla"``), for the tests that compare the program with it.

Straightforward ``jax.numpy`` under ``default_matmul_precision("highest")``
over one sequence: no kernel, cache or batching, and nothing of the
program's model code.  Per layer, with h = RMSNorm(x):

  * MLA: q = h Wq split into (nope, rope) per head; c = RMSNorm(h Wdkv);
    k_nope = c Wuk, v = c Wuv per head; k_rope = h Wkr shared by the
    heads; the rope parts rotated by YaRN frequencies (cos and sin times
    mscale / mscale_all_dim); causal softmax of (q_nope k_nope + q_rope
    k_rope) at (nope + rope)^-0.5 * mscale_all_dim's mscale^2; x += o Wo.
  * The leading dense layers: x += SwiGLU(h) at ``d_ff``.
  * An MoE layer: the router's softmax over every expert, the top-k
    probabilities (renormalised only if ``router_norm_topk``); each held
    expert's SwiGLU applied to the tokens
    that chose it, weighted by its gate; plus the shared experts.

The rope rotates the two halves of each head's rope part (the program's
layout; the published code first permutes interleaved pairs to halves).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The published ``DeepseekV2YarnRotaryEmbedding`` inverse
    frequencies, float64."""
    def corr(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return freq_inter * (1 - mask) + freq_extra * mask


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _f32(w):
    return jnp.asarray(w, jnp.float32)


def _rmsnorm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(g)


def _swiglu(p, h):
    return (jax.nn.silu(h @ _f32(p["wg"])) * (h @ _f32(p["wi"]))) \
        @ _f32(p["wo"])


def _rope(x, ang, scale):
    """x (T, ..., rd): rotate the two halves by ``ang`` (T, rd/2)."""
    half = x.shape[-1] // 2
    shape = (ang.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = jnp.cos(ang).reshape(shape) * scale
    sin = jnp.sin(ang).reshape(shape) * scale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _mla(p, h, cfg, ang, rscale, sm_scale):
    t = h.shape[0]
    hh, nd, rd, vd = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                      cfg.v_head_dim)
    q = (h @ _f32(p["wq"])).reshape(t, hh, nd + rd)
    qn, qr = q[..., :nd], _rope(q[..., nd:], ang, rscale)
    c = _rmsnorm(h @ _f32(p["wdkv"]), p["c_norm"], cfg.norm_eps)
    kr = _rope(h @ _f32(p["wkr"]), ang, rscale)                # (T, rd)
    kn = (c @ _f32(p["wuk"])).reshape(t, hh, nd)
    v = (c @ _f32(p["wuv"])).reshape(t, hh, vd)
    s = (jnp.einsum("thd,shd->hts", qn, kn)
         + jnp.einsum("thd,sd->hts", qr, kr)) * sm_scale
    causal = jnp.tril(jnp.ones((t, t), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
    o = jnp.einsum("hts,shd->thd", a, v).reshape(t, hh * vd)
    return o @ _f32(p["wo"])


def moe_layer(p, h, moe):
    """The held experts' part of one MoE layer plus the shared experts:
    h (T, D) f32 -> (T, D) f32.  ``p`` holds the held experts' weights
    (``wi``/``wg`` (n, D, F), ``wo`` (n, F, D)) and the full router."""
    probs = jax.nn.softmax(h @ _f32(p["router"]), -1)          # (T, E)
    w, idx = jax.lax.top_k(probs, moe.top_k)
    if moe.router_norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    n = p["wi"].shape[0]
    chose = idx[:, :, None] == moe.held_first + jnp.arange(n)  # (T, k, n)
    gates = jnp.einsum("tk,tkn->tn", w, chose.astype(w.dtype))
    hg = jnp.einsum("td,ndf->ntf", h, _f32(p["wg"]))
    hi = jnp.einsum("td,ndf->ntf", h, _f32(p["wi"]))
    y = jnp.einsum("ntf,nfd->ntd", jax.nn.silu(hg) * hi, _f32(p["wo"]))
    out = jnp.einsum("ntd,tn->td", y, gates)
    if "shared" in p:
        out = out + _swiglu(p["shared"], h)
    return out


def logits(params, cfg, tokens):
    """Float32 logits (T, vocab) of one sequence ``tokens`` (T,)."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, cfg, jnp.asarray(tokens))


def _logits(params, cfg, tokens):
    t = tokens.shape[0]
    rd = cfg.qk_rope_dim
    sm_scale = (cfg.qk_nope_dim + rd) ** -0.5
    y = cfg.rope_scaling
    if y is None:
        inv, rscale = 1.0 / cfg.rope_theta ** (np.arange(0, rd, 2) / rd), 1.0
    else:
        inv = yarn_inv_freq(rd, cfg.rope_theta, y.factor,
                            y.original_max_position, y.beta_fast,
                            y.beta_slow)
        rscale = yarn_get_mscale(y.factor, y.mscale) \
            / yarn_get_mscale(y.factor, y.mscale_all_dim)
        if y.mscale_all_dim:
            sm_scale *= yarn_get_mscale(y.factor, y.mscale_all_dim) ** 2
    ang = jnp.arange(t)[:, None].astype(jnp.float32) \
        * jnp.asarray(inv, jnp.float32)[None, :]
    x = _f32(params["embed"])[tokens]

    def layer(x, p, moe):
        h = _rmsnorm(x, p["norm1"], cfg.norm_eps)
        x = x + _mla(p["core"], h, cfg, ang, rscale, sm_scale)
        h = _rmsnorm(x, p["norm2"], cfg.norm_eps)
        mlp = moe_layer(p["mlp"], h, cfg.moe) if moe else _swiglu(p["mlp"], h)
        return x + mlp

    stacks = []
    if cfg.first_dense:
        stacks.append((params["lead_blocks"], False))
    stacks.append((params["blocks"][0], cfg.period[0].mlp == "moe"))
    for stack, moe in stacks:
        n = jax.tree.leaves(stack)[0].shape[0]
        for i in range(n):
            x = layer(x, jax.tree.map(lambda a: a[i], stack), moe)
    x = _rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return (x @ _f32(params["lm_head"]))[:, :cfg.vocab]
