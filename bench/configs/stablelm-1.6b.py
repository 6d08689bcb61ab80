"""Plain reference of ``stablelm-1.6b`` as run: a decoder-only transformer
in float32 ``jax.numpy``, with no kernel, cache or batching.

Per layer: x += Wo attn(RoPE(Wq h), RoPE(Wk h), Wv h) with h = RMSNorm(x),
causal softmax at scale hd^-0.5; then x += Wdown(silu(Wgate h) * Wup h)
with h = RMSNorm(x); then RMSNorm and the output head.  RoPE rotates the
two halves of each head (theta from the file, the whole head as run;
the published model rotates a quarter).  RMSNorm: x / sqrt(mean(x^2) +
eps) * g.  Imports nothing of the program.

``make_params`` makes the served weights on the device from the seed in
one jitted call, in bfloat16, in the parameter tree the program takes;
the reference reads the same weights, widened to float32 one layer at a
time.  ``next_token_stats`` gives, at every position of one sequence,
the best logit, the logit of the token that follows and the
log-normaliser; ``precision`` ``"fp8"`` is the control: every matrix
product's inputs rounded to float8 (e4m3, one scale per tensor).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(m: dict):
    d, h = m["hidden_size"], m["num_attention_heads"]
    return (m["num_hidden_layers"], d, h, m["num_key_value_heads"], d // h,
            m["intermediate_size"], m["vocab_size"])


def make_params(key, model: dict):
    """The program's parameter tree, bfloat16, from ``key``: dense
    weights normal * fan_in^-0.5, the embedding normal, norm gains
    1 + 0.1 * normal."""
    layers, d, h, kv, hd, f, v = _dims(model)

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def dense(shape):
            w = jax.random.normal(next(ks), shape, jnp.float32)
            return (w * shape[-2] ** -0.5).astype(jnp.bfloat16)

        def gain(shape):
            g = 1 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
            return g.astype(jnp.bfloat16)

        block = {"norm1": gain((layers, d)),
                 "core": {"wq": dense((layers, d, h * hd)),
                          "wk": dense((layers, d, kv * hd)),
                          "wv": dense((layers, d, kv * hd)),
                          "wo": dense((layers, h * hd, d))},
                 "norm2": gain((layers, d)),
                 "mlp": {"wi": dense((layers, d, f)),
                         "wg": dense((layers, d, f)),
                         "wo": dense((layers, f, d))}}
        return {"embed": jax.random.normal(next(ks), (v, d), jnp.float32)
                .astype(jnp.bfloat16),
                "blocks": [block],
                "final_norm": gain((d,)),
                "lm_head": dense((d, v))}

    return jax.jit(build)(key)


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, precision: str):
    if precision == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.matmul(a, w.astype(jnp.float32), precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, theta):
    """x (T, H, hd): rotate the two halves of each head by position."""
    t, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


@functools.partial(jax.jit, static_argnames=("model_items", "precision"))
def _stats(params, tokens, nxt, *, model_items, precision):
    model = dict(model_items)
    layers, d, h, kv, hd, f, v = _dims(model)
    eps, theta = model["layer_norm_eps"], float(model["rope_theta"])
    t = tokens.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    x = params["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        hn = _rmsnorm(x, p["norm1"], eps)
        q = _rope(_mm(hn, p["core"]["wq"], precision).reshape(t, h, hd), theta)
        k = _rope(_mm(hn, p["core"]["wk"], precision).reshape(t, kv, hd),
                  theta)
        val = _mm(hn, p["core"]["wv"], precision).reshape(t, kv, hd)
        k = jnp.repeat(k, h // kv, axis=1)
        val = jnp.repeat(val, h // kv, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k, precision=HIGHEST) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", a, val, precision=HIGHEST)
        x = x + _mm(o.reshape(t, h * hd), p["core"]["wo"], precision)
        hn = _rmsnorm(x, p["norm2"], eps)
        up = _mm(hn, p["mlp"]["wi"], precision)
        gate = _mm(hn, p["mlp"]["wg"], precision)
        x = x + _mm(jax.nn.silu(gate) * up, p["mlp"]["wo"], precision)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    logits = _mm(_rmsnorm(x, params["final_norm"], eps), params["lm_head"],
                 precision)[:, :v]
    best = jnp.max(logits, -1)
    top = jnp.argmax(logits, -1)
    picked = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
    return best, picked, jax.nn.logsumexp(logits, -1), top


def next_token_stats(params, model: dict, tokens, nxt,
                     precision: str = "float32"):
    """At every position of ``tokens`` (T,): (best logit, logit of
    ``nxt`` at that position, log-normaliser, argmax token), float32."""
    return _stats(params, tokens, nxt, model_items=tuple(sorted(
        (k, v) for k, v in model.items() if not isinstance(v, (dict, list)))),
        precision=precision)
