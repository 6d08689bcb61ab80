"""Basic layers: inits, norms, MLPs, rotary embeddings.

Parameters are plain pytrees (nested dicts of jnp arrays); every layer is a
pair of functions (init, apply).  Compute-critical matmuls take
``preferred_element_type=float32`` so bf16 params accumulate in f32 (MXU
native behavior).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig


def shard_hint(x, cfg: ModelConfig, dims: Sequence):
    """with_sharding_constraint helper: ``dims`` entries are 'dp' (the
    configured data-parallel axes), 'sp' (the sequence-parallel axis), a
    mesh-axis name, or None.  No-op when cfg.act_dp_axes is unset (smoke
    runs without a mesh)."""
    if not cfg.act_dp_axes:
        return x
    spec = []
    for d in dims:
        if d == "dp":
            dp = cfg.act_dp_axes
            spec.append(dp if len(dp) > 1 else dp[0])
        elif d == "sp":
            if cfg.act_sp_axis is None:
                spec.append(None)
            else:
                spec.append(cfg.act_sp_axis)
        else:
            spec.append(d)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: Optional[float] = None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def dense(w, x):
    return jnp.einsum("...d,df->...f", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def rmsnorm_init(d: int, dtype):
    return jnp.ones((d,), dtype)


def rmsnorm(g, x, eps: float = 1e-5, *, policy: Optional[str] = None):
    """``policy=None`` (default) is the legacy XLA mean — bit for bit.
    A policy name routes the per-token mean square through the
    ``repro.reduce`` front door instead: the feature axis becomes the
    stream (one (D, T) ``op="sumsq"`` pass, tokens as the element
    width), so under an integer tier the norm denominator is bitwise
    independent of how XLA tiles the reduction."""
    xf = x.astype(jnp.float32)
    if policy is None:
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)  # detlint: ok[DET001] policy=None legacy path, bits pinned; sumsq front door is the knob
    else:
        from repro import reduce as _reduce
        d = xf.shape[-1]
        cols = xf.reshape(-1, d).T                       # (D, T)
        ssq = _reduce.reduce(cols, op="sumsq", policy=policy)
        var = (ssq / d).reshape(xf.shape[:-1] + (1,))
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g


def swiglu_init(key, d: int, d_ff: int, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"wi": dense_init(k1, d, d_ff, dtype),
            "wg": dense_init(k2, d, d_ff, dtype),
            "wo": dense_init(k3, d_ff, d, dtype)}


def swiglu(p, x):
    h = jax.nn.silu(dense(p["wg"], x).astype(jnp.float32)).astype(x.dtype)
    return dense(p["wo"], h * dense(p["wi"], x))


def gelu_mlp_init(key, d: int, d_ff: int, dtype):
    k1, k2 = jax.random.split(key)
    return {"wi": dense_init(k1, d, d_ff, dtype),
            "wo": dense_init(k2, d_ff, d, dtype)}


def gelu_mlp(p, x):
    return dense(p["wo"], jax.nn.gelu(dense(p["wi"], x).astype(jnp.float32))
                 .astype(x.dtype))


def embed_init(key, vocab: int, d: int, dtype):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def embed_lookup(table, tokens):
    return jnp.take(table, tokens, axis=0)


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(hdim: int, theta: float) -> jnp.ndarray:
    # detlint: ok[DET006] RoPE frequency grid: hdim/2 well under 2^24
    return 1.0 / (theta ** (jnp.arange(0, hdim, 2, dtype=jnp.float32) / hdim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 1e4, *, freqs=None,
               scale: float = 1.0) -> jnp.ndarray:
    """x (..., S, H, hd); positions (..., S) int32.  ``freqs`` (hd/2,)
    replaces the standard grid (YaRN's interpolated one); ``scale``
    multiplies cos and sin."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                     # (hd/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    ang = ang[..., None, :]                               # (..., S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions3: jnp.ndarray, theta: float,
                sections: Sequence[int] = (16, 24, 24)) -> jnp.ndarray:
    """Qwen2-VL multimodal RoPE.

    x (B, S, H, hd); positions3 (B, S, 3) = (temporal, height, width) ids.
    The hd/2 frequency slots are partitioned into 3 sections, each rotated by
    its own position stream.  For pure text all three streams are equal and
    M-RoPE == RoPE.
    """
    hd = x.shape[-1]
    half = hd // 2
    sections = list(sections)
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(hd, theta)                         # (half,)
    sec_idx = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                         total_repeat_length=half)        # (half,)
    # gather each slot's position stream: pos_per_slot (B, S, half)
    pos_per_slot = positions3.astype(jnp.float32)[..., sec_idx]
    ang = pos_per_slot * freqs                            # (B, S, half)
    ang = ang[..., None, :]                               # (B, S, 1, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def causal_mask(s_q: int, s_k: int, *, offset: int = 0,
                window: Optional[int] = None) -> jnp.ndarray:
    """(s_q, s_k) additive mask. offset = first query position."""
    qi = jnp.arange(s_q)[:, None] + offset
    kj = jnp.arange(s_k)[None, :]
    ok = kj <= qi
    if window is not None:
        ok &= kj > (qi - window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)
