"""Plain reference of ``deepseek-v2-lite-ep8`` as run: DeepSeek-V2-Lite's
decoder in float32 ``jax.numpy`` at HIGHEST, with no kernel, cache or
batching, and with this chip's share of the routed experts.

Per layer, with h = RMSNorm(x) (x / sqrt(mean(x^2) + eps) * g):

  * MLA (no q LoRA): q = h Wq, split per head into a nope part (128) and
    a rope part (64); c = RMSNorm(h Wdkv) (rank 512); k_nope = c Wuk and
    v = c Wuv per head; k_rope = h Wkr, one for all heads.  The rope
    parts are rotated (the two halves of each rope part) by YaRN
    frequencies: each 1/theta^(2i/64) interpolated toward the same over
    ``factor`` by a linear ramp between the correction dimensions of
    ``beta_fast`` and ``beta_slow`` over ``original_max_position_embeddings``;
    cos and sin times mscale(mscale) / mscale(mscale_all_dim), with
    mscale(m) = 0.1 m ln(factor) + 1.  Causal softmax of q_nope.k_nope +
    q_rope.k_rope at 192^-0.5 * mscale(mscale_all_dim)^2; x += o Wo.
  * Layer 0 (``first_k_dense_replace``): x += Wdown(silu(Wgate h) * Wup h)
    at ``intermediate_size``.
  * Layers 1-26: the router's softmax over all ``router_outputs`` experts,
    the top ``num_experts_per_tok`` probabilities (not renormalised:
    ``norm_topk_prob`` false) times ``routed_scaling_factor``; each held
    expert (``first_expert`` + j, j < ``n_routed_experts``) adds its SwiGLU
    at ``moe_intermediate_size`` of h, times its gate, for the tokens that
    chose it; the shared experts (one SwiGLU of width
    ``n_shared_experts`` x ``moe_intermediate_size``) add theirs for every
    token.  What the experts held on the other chips would add is left
    out, as in the program.

Then RMSNorm and the output head.  Imports nothing of the program.

``make_params`` makes the served weights on the device from the seed in
one jitted call, bfloat16 (the router float32, as the program keeps it),
in the parameter tree the program takes; the reference reads the same
weights, widened to float32 one layer at a time.  ``next_token_stats``
gives, at every position of one sequence, the best logit, the logit of
the token that follows, the log-normaliser and the argmax; ``precision``
``"fp8"`` is the control: every matrix product's inputs rounded to
float8 (e4m3, one scale per tensor).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: what the static argument of the jitted reference holds, by name
_KEYS = ("num_hidden_layers", "first_k_dense_replace", "hidden_size",
         "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "intermediate_size",
         "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
         "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
         "rms_norm_eps", "rope_theta", "vocab_size")


def _static(model: dict) -> tuple:
    y, share = model["rope_scaling"], model["share"]
    return tuple(model[k] for k in _KEYS) + (
        share["router_outputs"], share["first_expert"], y["factor"],
        y["original_max_position_embeddings"], y["beta_fast"],
        y["beta_slow"], y["mscale"], y["mscale_all_dim"])


def make_params(key, model: dict):
    """The program's parameter tree, bfloat16, from ``key``: dense
    weights normal * fan_in^-0.5, the router the same in float32, the
    embedding normal, norm gains 1 + 0.1 * normal."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    r, nd = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rd, vd = model["qk_rope_head_dim"], model["v_head_dim"]
    lead = model["first_k_dense_replace"]
    moe = model["num_hidden_layers"] - lead
    held, fe = model["n_routed_experts"], model["moe_intermediate_size"]
    fs = model["n_shared_experts"] * fe
    e_all, v = model["share"]["router_outputs"], model["vocab_size"]

    def build(key):
        ks = iter(jax.random.split(key, 40))

        def dense(shape, dtype=jnp.bfloat16):
            w = jax.random.normal(next(ks), shape, jnp.float32)
            return (w * shape[-2] ** -0.5).astype(dtype)

        def gain(shape):
            g = 1 + 0.1 * jax.random.normal(next(ks), shape, jnp.float32)
            return g.astype(jnp.bfloat16)

        def attn(n):
            return {"wq": dense((n, d, h * (nd + rd))),
                    "wdkv": dense((n, d, r)), "wkr": dense((n, d, rd)),
                    "wuk": dense((n, r, h * nd)), "wuv": dense((n, r, h * vd)),
                    "wo": dense((n, h * vd, d)), "c_norm": gain((n, r))}

        def swiglu(*lead_shape, f):
            return {"wi": dense(lead_shape + (d, f)),
                    "wg": dense(lead_shape + (d, f)),
                    "wo": dense(lead_shape + (f, d))}

        first = {"norm1": gain((lead, d)), "core": attn(lead),
                 "norm2": gain((lead, d)), "mlp": swiglu(lead, f=model[
                     "intermediate_size"])}
        mlp = swiglu(moe, held, f=fe)
        mlp["router"] = dense((moe, d, e_all), jnp.float32)
        mlp["shared"] = swiglu(moe, f=fs)
        block = {"norm1": gain((moe, d)), "core": attn(moe),
                 "norm2": gain((moe, d)), "mlp": mlp}
        return {"embed": jax.random.normal(next(ks), (v, d), jnp.float32)
                .astype(jnp.bfloat16),
                "lead_blocks": first, "blocks": [block],
                "final_norm": gain((d,)), "lm_head": dense((d, v))}

    return jax.jit(build)(key)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """The published YaRN inverse frequencies (dim / 2,), float64."""
    def corr(rot):
        return dim * math.log(original / (rot * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return extra / factor * ramp + extra * (1 - ramp)


def _fp8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, precision: str):
    w = w.astype(jnp.float32)
    if precision == "fp8":
        a, w = _fp8(a), _fp8(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rope(x, cos, sin):
    """x (T, ..., rd): rotate the two halves; cos, sin (T, rd/2)."""
    half = x.shape[-1] // 2
    shape = (cos.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


@functools.partial(jax.jit, static_argnames=("dims", "precision"))
def _stats(params, tokens, nxt, *, dims, precision):
    (layers, lead, d, h, r, nd, rd, vd, f_dense, fe, held, n_shared, k,
     norm_topk, scaling, eps, theta, v, e_all, first_expert, factor,
     original, beta_fast, beta_slow, mscale, mscale_all) = dims
    t = tokens.shape[0]
    inv = yarn_inv_freq(rd, float(theta), factor, original, beta_fast,
                        beta_slow)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    rscale = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all)
    cos, sin = jnp.cos(ang) * rscale, jnp.sin(ang) * rscale
    sm_scale = (nd + rd) ** -0.5
    if mscale_all:
        sm_scale *= yarn_mscale(factor, mscale_all) ** 2
    causal = jnp.tril(jnp.ones((t, t), bool))

    def mm(a, w):
        return _mm(a, w, precision)

    def swiglu(p, hn):
        return mm(jax.nn.silu(mm(hn, p["wg"])) * mm(hn, p["wi"]), p["wo"])

    def attention(p, hn):
        q = mm(hn, p["wq"]).reshape(t, h, nd + rd)
        qn, qr = q[..., :nd], _rope(q[..., nd:], cos, sin)
        c = _rmsnorm(mm(hn, p["wdkv"]), p["c_norm"], eps)
        kr = _rope(mm(hn, p["wkr"]), cos, sin)
        kn = mm(c, p["wuk"]).reshape(t, h, nd)
        val = mm(c, p["wuv"]).reshape(t, h, vd)
        s = (jnp.einsum("thd,shd->hts", qn, kn, precision=HIGHEST)
             + jnp.einsum("thd,sd->hts", qr, kr, precision=HIGHEST)) \
            * sm_scale
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hts,shd->thd", a, val, precision=HIGHEST)
        return mm(o.reshape(t, h * vd), p["wo"])

    def experts(p, hn):
        probs = jax.nn.softmax(mm(hn, p["router"]), axis=-1)
        w, idx = jax.lax.top_k(probs, k)
        if norm_topk:
            w = w / jnp.sum(w, -1, keepdims=True)
        w = w * scaling
        out = swiglu(p["shared"], hn)
        for j in range(held):
            gate = jnp.sum(jnp.where(idx == first_expert + j, w, 0.0), -1)
            e = {n: p[n][j] for n in ("wi", "wg", "wo")}
            out = out + gate[:, None] * swiglu(e, hn)
        return out

    def layer(mlp):
        def body(x, p):
            x = x + attention(p["core"], _rmsnorm(x, p["norm1"], eps))
            hn = _rmsnorm(x, p["norm2"], eps)
            return x + mlp(p["mlp"], hn), None
        return body

    x = params["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(layer(swiglu), x, params["lead_blocks"])
    x, _ = jax.lax.scan(layer(experts), x, params["blocks"][0])
    logits = mm(_rmsnorm(x, params["final_norm"], eps),
                params["lm_head"])[:, :v]
    best = jnp.max(logits, -1)
    top = jnp.argmax(logits, -1)
    picked = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
    return best, picked, jax.nn.logsumexp(logits, -1), top


def next_token_stats(params, model: dict, tokens, nxt,
                     precision: str = "float32"):
    """At every position of ``tokens`` (T,): (best logit, logit of
    ``nxt`` at that position, log-normaliser, argmax token), float32."""
    return _stats(params, tokens, nxt, dims=_static(model),
                  precision=precision)
