"""Train / prefill / decode step builders.

``make_train_step`` builds the canonical production step: forward + backward
(+ remat), gradient clip, AdamW.  Under pjit the data-parallel gradient
reduction is emitted by XLA from the shardings; ``make_shardmap_train_step``
(distributed/collectives.py) is the explicit-collective variant with the
INTAC compressed all-reduce and the gradient juggler — the paper's technique
on the distributed-optimization path.

``make_decode_step`` / ``make_prefill_step`` are the serving pair.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import decode_step as model_decode_step
from repro.models import encode, forward, loss_fn
from repro.models.config import ModelConfig
from repro.optim import adamw


def make_train_step(cfg: ModelConfig, *, lr_fn: Callable,
                    moe_impl: str = "capacity", remat: bool = True,
                    clip_norm: float = 1.0, weight_decay: float = 0.1,
                    logits_pspec=None, num_microbatches: int = 1,
                    grad_reduce: Optional[str] = None,
                    grad_reduce_mesh=None,
                    norm_policy: Optional[str] = None):
    """num_microbatches > 1: the batch splits along dim 0 and gradients
    accumulate through the JugglePAC binary-counter pairing tree
    (repro.reduce.TreeAccumulator) — activation memory scales down by the
    microbatch count while only O(log m) gradient copies stay live, and the
    fixed pairing schedule keeps the result independent of the grouping.

    ``grad_reduce`` (a policy name: "fast" ... "procrastinate") instead
    routes the microbatch-gradient mean through the ``repro.reduce`` front
    door (``reduce_microbatch_grads``): per-microbatch gradients stack
    into an (m, |leaf|) stream and reduce leaf-by-leaf under the chosen
    accuracy policy — for the integer tiers the mean is bitwise
    independent of microbatch count and executor.  Costs m live gradient
    copies instead of O(log m); pick it when accuracy/determinism of the
    gradient sum matters more than peak memory.  ``grad_reduce_mesh``
    additionally routes each leaf's reduction through the ``shard_map``
    backend on that mesh; leave it None (local executor) unless you
    specifically want the reduction itself distributed — the bits are
    identical either way for the integer tiers, and an m-row stream per
    leaf rarely merits per-leaf collectives.

    For data-parallel training whose step must be bitwise-reproducible
    across *device topologies* (checkpoint on 2 devices, resume on 8),
    use ``repro.distributed.collectives.make_elastic_train_step``
    instead — it pins the microbatch grid to the global stream and
    reduces through ``elastic_reduce_mean`` (docs/robustness.md).

    ``norm_policy`` routes the gradient-clipping global norm through the
    ``repro.reduce`` front door (``adamw.global_norm``); together with
    ``cfg.norm_reduce_policy`` (rmsnorm) and
    ``MoECfg.router_norm_policy`` (combine weights) it makes the
    in-model reductions policy-governed end to end (docs/algebra.md)."""
    from repro import reduce as _reduce

    def grad_fn(p, b):
        def loss_wrap(pp):
            return loss_fn(pp, cfg, b, moe_impl=moe_impl, remat=remat,
                           logits_pspec=logits_pspec)
        (loss, metrics), grads = jax.value_and_grad(
            loss_wrap, has_aux=True)(p)
        return grads, (loss, metrics)

    def train_step(params, opt_state, batch):
        if num_microbatches > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape(
                    (num_microbatches, x.shape[0] // num_microbatches)
                    + x.shape[1:]), batch)
            if grad_reduce is not None:
                grads, (losses, metricses) = \
                    _reduce.reduce_microbatch_grads(
                        grad_fn, params, mbs,
                        num_microbatches=num_microbatches,
                        policy=grad_reduce, mesh=grad_reduce_mesh)
            else:
                grads, (losses, metricses) = \
                    _reduce.accumulate_microbatch_grads(
                        grad_fn, params, mbs,
                        num_microbatches=num_microbatches, mean=True)
            loss = jnp.mean(losses)  # detlint: ok[DET001] m microbatch scalars; grads take the front door above
            metrics = jax.tree.map(jnp.mean, metricses)
        else:
            grads, (loss, metrics) = grad_fn(params, batch)
        lr = lr_fn(opt_state.count + 1)   # count is 0-based
        params, opt_state, gnorm = adamw.update(
            grads, opt_state, params, lr=lr, clip_norm=clip_norm,
            weight_decay=weight_decay, norm_policy=norm_policy)
        metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
        return params, opt_state, metrics
    return train_step


def make_eval_step(cfg: ModelConfig, *, moe_impl: str = "capacity"):
    def eval_step(params, batch):
        loss, metrics = loss_fn(params, cfg, batch, moe_impl=moe_impl,
                                remat=False)
        return dict(metrics, loss=loss)
    return eval_step


def make_prefill_step(cfg: ModelConfig, *, moe_impl: str = "capacity"):
    def prefill_step(params, batch):
        enc_out = None
        if cfg.is_encdec:
            enc_out = encode(params, cfg, batch["enc_embeds"])
        logits, caches, _, _ = forward(
            params, cfg, tokens=batch.get("tokens"),
            embeds=batch.get("embeds"), positions=batch.get("positions"),
            mode="prefill", enc_out=enc_out, moe_impl=moe_impl)
        # next-token distribution of the last position only
        return logits[:, -1:], caches
    return prefill_step


def make_decode_step(cfg: ModelConfig, *, moe_impl: str = "capacity"):
    def dstep(params, token, caches, position, enc_out=None):
        return model_decode_step(params, cfg, token, caches, position,
                                 enc_out=enc_out, moe_impl=moe_impl)
    return dstep
