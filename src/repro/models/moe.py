"""Mixture-of-Experts: top-k router + capacity-based dispatch (EP-shardable).

Two dispatch strategies with one contract, ``moe_apply`` -> (y, aux):

  * ``capacity``  — production/dry-run path: tokens are packed into a fixed
    (E, C) buffer with one-hot dispatch/combine einsums (MaxText-style).
    Under pjit with the expert axis sharded on 'model', XLA turns the
    dispatch/combine einsums into all-to-alls — expert parallelism.
  * ``dense``     — small-scale/oracle path: every expert runs on every token,
    gated combine.  O(E) compute, exact (no capacity drops); used by smoke
    tests as the reference for the capacity path.

and one of its own, ``moe_apply_held`` -> (y, routed): one chip's share of
expert parallelism (``MoECfg.held``).  The router scores all experts, the
(token, choice) pairs that land on the held experts run through a grouped
matmul (``jax.lax.ragged_dot``) with no capacity and no drops, and each
token's pairs are summed through ``combine_segsum``.  The serving engine's
path for share configurations (``moe_impl="held"`` in ``models.forward``).

The **combine** step is a segmented accumulation (each token sums its top-k
expert contributions — variable "set" sizes once capacity drops happen);
``combine_segsum`` routes it through the JugglePAC segmented-reduction
kernel, which is the paper's technique doing real work in the MoE layer.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .config import ModelConfig, MoECfg
from .layers import dense_init


def moe_init(key, cfg: ModelConfig, dtype):
    """A share configuration holds only its own experts' weights."""
    m = cfg.moe
    d = cfg.d_model
    v = cfg.moe_virtual_split
    assert v == 1 or not m.is_share
    e, f = m.n_held * v, m.d_ff_expert // v
    assert m.d_ff_expert % v == 0
    ks = jax.random.split(key, 5)
    p = {"router": dense_init(ks[0], d, m.num_experts, jnp.float32),
         "wi": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                * d ** -0.5).astype(dtype),
         "wg": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                * d ** -0.5).astype(dtype),
         "wo": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                * (f * v) ** -0.5).astype(dtype)}
    if m.num_shared:
        fs = m.d_ff_shared or m.d_ff_expert
        from .layers import swiglu_init
        p["shared"] = swiglu_init(ks[4], d, m.num_shared * fs, dtype)
    return p


def router_topk(router_w, x, m: MoECfg):
    """Returns (weights (T,k) f32, idx (T,k) i32, aux_loss scalar)."""
    # float32 scores at full precision (a TPU's default f32 matmul rounds
    # its inputs to bf16, which flips near-tied experts)
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router_w,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, m.top_k)
    if m.router_norm_topk:
        if m.router_norm_policy is not None:
            # combine-weight normalization through the front door: the
            # top-k axis is the stream (k rows, tokens as the width), so
            # the denominator every combine weight divides by reduces
            # under the configured accuracy tier
            from repro import reduce as _reduce
            den = _reduce.reduce(w.T, policy=m.router_norm_policy)  # (T,)
            w = w / jnp.maximum(den[:, None], 1e-9)
        else:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)  # detlint: ok[DET001] legacy branch, bits pinned; router_norm_policy is the front door
    # load-balancing auxiliary loss (Switch-style)
    e = m.num_experts
    # detlint: ok[DET001] Switch aux-loss stats over E experts: legacy
    # bits pinned by tests (next pragma covers all three reductions)
    me = jnp.mean(probs, axis=0)                            # mean router prob
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], e), axis=0)  # detlint: ok[DET001] top-1 load, E experts
    aux = e * jnp.sum(me * ce)  # detlint: ok[DET001] aux-loss scalar, E experts
    return w, idx, aux


def _expert_ffn(p, xe):
    """xe (E, C, D) -> (E, C, D); batched swiglu over the expert axis."""
    hi = jnp.einsum("ecd,edf->ecf", xe, p["wi"],
                    preferred_element_type=jnp.float32)
    hg = jnp.einsum("ecd,edf->ecf", xe, p["wg"],
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(hg) * hi).astype(xe.dtype)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"],
                      preferred_element_type=jnp.float32).astype(xe.dtype)


MOE_GROUP = 4096   # tokens per capacity group (aligns with dp shards)


def moe_apply_capacity(params, x, cfg: ModelConfig, *,
                       capacity: Optional[int] = None,
                       group_size: int = MOE_GROUP):
    """x (B, S, D) -> (B, S, D).  Grouped gather/scatter dispatch.

    Tokens are processed in groups of ``group_size`` with a fixed per-group
    expert capacity Cg = ceil(G*k*cf/E).  Dispatch and combine are pure
    gathers (batched over the group axis, so the dp sharding of tokens never
    moves), and the expert FFN is an einsum with the expert axis sharded on
    'model' — EP without any fake one-hot matmul FLOPs.  The group axis is
    the JugglePAC "block stream": each group is a block, expert buffers are
    the label-addressed registers, and capacity drops are the bounded-storage
    rule made explicit.
    """
    m = cfg.moe
    if m.is_share:
        raise ValueError("the capacity path has no expert share; a share "
                         "configuration runs moe_impl='held'")
    b, s, d = x.shape
    t = b * s
    v = cfg.moe_virtual_split
    e, k = m.num_experts * v, m.top_k * v
    xt = x.reshape(t, d)
    w, idx, aux = router_topk(params["router"], xt, m)      # (T,k) f32/i32
    if v > 1:
        # each chosen expert expands to its v virtual column shards; the
        # shards' partial outputs sum in the combine (weights unchanged:
        # y = sum_v (x @ wi_v) @ wo_v)
        idx = (idx[:, :, None] * v
               + jnp.arange(v)[None, None, :]).reshape(t, k)
        w = jnp.repeat(w, v, axis=1)

    g = min(group_size, t)
    ng = -(-t // g)
    padt = ng * g - t
    if padt:
        xt = jnp.pad(xt, ((0, padt), (0, 0)))
        idx = jnp.pad(idx, ((0, padt), (0, 0)), constant_values=0)
        w = jnp.pad(w, ((0, padt), (0, 0)))                 # zero weight
    cg = capacity or max(1, int(m.capacity_factor * g * k / e))

    idx_g = idx.reshape(ng, g * k)                          # token-major
    w_g = w.reshape(ng, g, k)

    # position of each (token, choice) in its expert's per-group buffer
    onehot = jax.nn.one_hot(idx_g, e, dtype=jnp.int32)      # (nG, G*k, E)
    pos = jnp.cumsum(onehot, axis=1) - 1  # detlint: ok[DET001] int32 slot-position prefix count: exact, part of the dispatch algorithm
    pos = jnp.take_along_axis(pos, idx_g[..., None], axis=-1)[..., 0]
    keep = pos < cg                                         # (nG, G*k)

    # scatter token ids into expert slots: slots (nG, E*Cg [+1 overflow])
    slot = jnp.where(keep, idx_g * cg + pos, e * cg)
    tok_in_g = jnp.broadcast_to(
        (jnp.arange(g)[:, None]).reshape(1, g, 1), (ng, g, k)).reshape(ng, g * k)
    slots = jnp.full((ng, e * cg + 1), g, jnp.int32)
    slots = slots.at[jnp.arange(ng)[:, None], slot].set(tok_in_g, mode="drop")
    slots = slots[:, :e * cg]                               # drop overflow

    # dispatch gather: (nG, G+1, D) -> (nG, E*Cg, D)
    from .layers import shard_hint
    xg = shard_hint(xt.reshape(ng, g, d), cfg, ("dp", None, None))
    xg_pad = jnp.pad(xg, ((0, 0), (0, 1), (0, 0)))          # zero row @ G
    xe = jnp.take_along_axis(xg_pad, slots[..., None], axis=1)
    ea, fa = cfg.moe_expert_axis, cfg.moe_ff_axis
    xe = shard_hint(xe.reshape(ng, e, cg, d), cfg, ("dp", ea, None, None))

    # expert FFN (E sharded on 'model' => expert parallelism)
    hi = jnp.einsum("gecd,edf->gecf", xe, params["wi"],
                    preferred_element_type=jnp.float32)
    hg = jnp.einsum("gecd,edf->gecf", xe, params["wg"],
                    preferred_element_type=jnp.float32)
    h = (jax.nn.silu(hg) * hi).astype(xe.dtype)
    h = shard_hint(h, cfg, ("dp", ea, None, fa))
    # under expert-TP the contraction over the F-sharded axis emits a
    # cross-shard all-reduce of the partials; bf16 halves that traffic
    # (per-shard MXU accumulation remains f32 either way)
    combine_dtype = (jnp.bfloat16 if (cfg.moe_bf16_combine and fa)
                     else jnp.float32)
    ye = jnp.einsum("gecf,efd->gecd", h, params["wo"],
                    preferred_element_type=combine_dtype).astype(xe.dtype)
    ye = shard_hint(ye, cfg, ("dp", ea, None, None))

    # combine gather: each (token, choice) reads its slot back
    ye_flat = ye.reshape(ng, e * cg, d)
    ye_pad = jnp.pad(ye_flat, ((0, 0), (0, 1), (0, 0)))     # zero row
    src = jnp.where(keep, idx_g * cg + pos, e * cg)         # (nG, G*k)
    y_tk = jnp.take_along_axis(ye_pad, src[..., None], axis=1)
    y_tk = y_tk.reshape(ng, g, k, d)
    yt = jnp.einsum("ngkd,ngk->ngd", y_tk.astype(jnp.float32),
                    w_g.astype(jnp.float32)).reshape(ng * g, d)
    yt = yt[:t].astype(x.dtype)

    if m.num_shared:
        from .layers import swiglu
        yt = yt + swiglu(params["shared"], x.reshape(t, d))
    return yt.reshape(b, s, d), aux


def moe_apply_dense(params, x, cfg: ModelConfig):
    """Exact O(E)-compute reference: every expert sees every token (every
    held expert, for a share configuration)."""
    m = cfg.moe
    v = cfg.moe_virtual_split
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    w, idx, aux = router_topk(params["router"], xt, m)
    e_eff = m.n_held * v
    ye = _expert_ffn(params, jnp.broadcast_to(xt, (e_eff,) + xt.shape))
    if v > 1:   # sum virtual shards back into parent experts
        ye = ye.reshape(m.num_experts, v, *ye.shape[1:]).sum(1)  # detlint: ok[DET001] v virtual shards, fixed axis order; pinned by moe tests
    if m.is_share:   # pairs on experts held elsewhere fall off the end
        idx = _held_index(idx, m)
    gates = jnp.zeros((b * s, m.n_held), jnp.float32).at[
        jnp.arange(b * s)[:, None], idx].add(w, mode="drop")
    yt = jnp.einsum("etd,te->td", ye.astype(jnp.float32), gates)
    if m.num_shared:
        from .layers import swiglu
        yt = yt + swiglu(params["shared"], xt).astype(jnp.float32)
    return yt.astype(x.dtype).reshape(b, s, d), aux


def combine_segsum(expert_rows, row_token_ids, num_tokens, *, interpret=None):
    """Top-k combine as a JugglePAC segmented sum.

    expert_rows (R, D): already gate-weighted expert outputs, one row per
    (token, choice) pair that survived capacity; row_token_ids (R,): which
    token each row belongs to.  Variable rows-per-token == the paper's
    variable-length sets.  Returns (num_tokens, D).

    Goes through the ``repro.reduce`` front door: backend auto-selection
    picks the pallas kernel on TPU and the scanned blocks elsewhere —
    both produce bitwise-identical results.
    """
    from repro import reduce as _reduce
    backend = "pallas" if interpret is not None else None
    return _reduce.reduce(expert_rows, segment_ids=row_token_ids,
                          num_segments=num_tokens, backend=backend,
                          interpret=interpret)


def _held_index(idx, m: MoECfg):
    """Each chosen expert's index among the held ones; ``m.n_held`` for
    an expert held elsewhere."""
    local = idx - m.held_first
    return jnp.where((local >= 0) & (local < m.n_held), local, m.n_held)


def moe_apply_held(params, x, cfg: ModelConfig, token_mask=None):
    """One chip's share of an expert-parallel layer: x (B, S, D) ->
    (y (B, S, D), routed (n_held,) int32).

    The router scores every expert (softmax, top-k by value, weights as
    ``router_topk`` gives them).  The (token, choice) pairs whose expert
    is held here, and whose token ``token_mask`` (B, S) keeps, are
    ordered by expert and run through a grouped SwiGLU
    (``jax.lax.ragged_dot``, one group per held expert), with no
    capacity: no pair is dropped.  Each pair is weighted by its gate, the
    pairs go back to token order, and each token's set of 0 to top_k
    rows is summed through ``combine_segsum`` (a token with no held pair
    gives a zero row).  The shared experts run once on every token.
    ``routed`` counts the pairs each held expert computed.
    """
    m = cfg.moe
    b, s, d = x.shape
    t, k, n = b * s, m.top_k, m.n_held
    xt = x.reshape(t, d)
    w, idx, _ = router_topk(params["router"], xt, m)        # (T,k)
    grp = _held_index(idx, m)                               # (T,k)
    if token_mask is not None:
        grp = jnp.where(token_mask.reshape(t, 1), grp, n)
    grp = grp.reshape(t * k)
    order = jnp.argsort(grp, stable=True)                   # held first
    routed = jnp.bincount(grp, length=n + 1)[:n].astype(jnp.int32)
    tok = order // k                                        # pair -> token
    xs = xt[tok]
    h = (jax.nn.silu(jax.lax.ragged_dot(
        xs, params["wg"], routed, preferred_element_type=jnp.float32))
        * jax.lax.ragged_dot(xs, params["wi"], routed,
                             preferred_element_type=jnp.float32))
    ys = jax.lax.ragged_dot(h.astype(x.dtype), params["wo"], routed,
                            preferred_element_type=jnp.float32)
    held = grp[order] < n
    rows = jnp.where(held[:, None], ys * w.reshape(t * k)[order][:, None],
                     0.0)
    inv = jnp.argsort(order)                                # token order
    from repro import reduce as _reduce
    ids = jnp.where(grp < n, jnp.arange(t * k) // k,
                    _reduce.OUT_OF_RANGE_LABEL)
    yt = combine_segsum(rows[inv], ids, t)
    if m.num_shared:
        from .layers import swiglu
        yt = yt + swiglu(params["shared"], xt).astype(jnp.float32)
    return yt.astype(x.dtype).reshape(b, s, d), routed


def moe_apply(params, x, cfg: ModelConfig, *, impl: str = "capacity",
              capacity: Optional[int] = None):
    if cfg.moe is None:
        raise ValueError("moe_apply on a non-MoE config")
    if impl == "capacity":
        return moe_apply_capacity(params, x, cfg, capacity=capacity)
    if impl == "dense":
        return moe_apply_dense(params, x, cfg)
    raise ValueError(impl)
