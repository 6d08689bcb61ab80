"""Explicit-collective training step (shard_map) — the paper's technique on
the distributed-optimization path.

``make_shardmap_train_step`` builds a data-parallel training step where the
gradient reduction is *explicit* rather than XLA-inserted.  The reduction
itself goes through the ``repro.reduce`` front door: microbatch gradients
stream through the Accumulator protocol (or, with ``microbatch_reduce``,
through a ``repro.reduce`` segment reduction under any accuracy policy),
and the cross-device mean is a ``repro.reduce.collective_mean`` policy —
``fast`` (plain hierarchical), ``compensated`` (INTAC compressed + error
feedback), or an integer tier, ``exact``, ``exact2`` or
``procrastinate``, which runs its ``Policy`` as ``repro.reduce`` does.
The JugglePAC/INTAC distributed tricks:

  1. **INTAC compressed all-reduce** — gradients are quantized to ``bits``-bit
     fixed point with a shared power-of-two scale, summed in the exact
     integer domain (associative => bitwise identical for any reduction
     topology / pod layout), dequantized once, with error-feedback residuals
     carried between steps.  Payload: bits/32 of fp32 (int8 => 4x).

  2. **Gradient juggler microbatching** — within a step, microbatch
     gradients accumulate through the binary-counter pairing tree
     (repro.reduce.TreeAccumulator): O(log m) live gradient copies, O(log m) rounding-error
     growth, schedule independent of microbatch grouping.

  3. **Hierarchical reduction** — 'data' (in-pod ICI) first, then 'pod'
     (cross-pod DCI), matching the physical topology.

  4. **Fused merge collectives** — every cross-device merge on this path
     is batched per dtype rather than issued per component: the fast-tier
     gradient tree fuses all leaves into one psum per mesh axis
     (``collective_mean_tree``), and the integer tiers' carries —
     exact2's [hi | lo | residual digits | overflow count] — merge as
     one int32 psum through ``reduce.policy.fused_psum``.  psum is
     elementwise, so the fusion is bitwise invisible — it only removes
     per-collective latency floors, which dominate once the per-shard
     kernel tail shrinks.

``make_elastic_train_step`` is the topology-elastic variant: gradients
and loss cross the device boundary only through
``repro.reduce.elastic_reduce_mean`` under a bitwise policy, and the
microbatch grid is pinned to the global stream — so the same global
batch produces bit-identical params on any mesh shape or device count
(the resume-anywhere half of docs/robustness.md).

The pjit path (train/steps.py) remains the default for the dry-run; this
step is benchmarked against it in benchmarks/ and exercised by tests.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import reduce as _reduce
from repro.models import loss_fn
from repro.models.config import ModelConfig
from repro.optim import adamw


def make_shardmap_train_step(cfg: ModelConfig, mesh, *, lr_fn: Callable,
                             num_microbatches: int = 1,
                             compress_bits: Optional[int] = 8,
                             reduce_policy: Optional[str] = None,
                             microbatch_reduce: Optional[str] = None,
                             moe_impl: str = "dense",
                             remat: bool = False,
                             clip_norm: float = 1.0):
    """Data-parallel over every mesh axis; params replicated per shard.

    state = (params, opt_state, ef_residuals); batch leading dim must be
    divisible by (dp_size * num_microbatches).

    ``reduce_policy`` picks the collective accuracy tier explicitly
    ("fast" | "compensated" | "exact" | "exact2" | "procrastinate"); when
    None it is derived from ``compress_bits`` (bits set => "compensated",
    else "fast") for backward compatibility.

    ``microbatch_reduce`` (a policy name) routes the per-shard microbatch
    gradient mean through the ``repro.reduce`` segment-reduction front
    door instead of the pairing tree: per-microbatch gradients stack into
    an (m, |leaf|) stream per leaf and reduce under the chosen accuracy
    policy, so e.g. ``microbatch_reduce="exact2",
    reduce_policy="exact2"`` makes the *whole* gradient path — in-shard
    accumulation and cross-device mean — integer-exact and bitwise
    independent of microbatch count and device layout.  (The backend is
    pinned to a local executor: this already runs inside shard_map.)
    """
    axes = tuple(mesh.axis_names)
    policy = reduce_policy or ("compensated" if compress_bits is not None
                               else "fast")
    bits = compress_bits if compress_bits is not None else 8

    def step(params, opt_state, residuals, batch):
        # ---- per-shard microbatch gradients through the pairing tree ----
        def grad_fn(p, mb):
            (loss, metrics), g = jax.value_and_grad(
                lambda pp: loss_fn(pp, cfg, mb, moe_impl=moe_impl,
                                   remat=remat), has_aux=True)(p)
            return g, (loss, metrics["xent"])

        if num_microbatches > 1:
            mbs = jax.tree.map(
                lambda x: x.reshape((num_microbatches,
                                     x.shape[0] // num_microbatches)
                                    + x.shape[1:]), batch)
            if microbatch_reduce is not None:
                # backend pinned local: this already runs inside shard_map
                grads, (losses, _) = _reduce.reduce_microbatch_grads(
                    grad_fn, params, mbs,
                    num_microbatches=num_microbatches,
                    policy=microbatch_reduce, backend="blocked")
            else:
                grads, (losses, _) = _reduce.accumulate_microbatch_grads(
                    grad_fn, params, mbs, num_microbatches=num_microbatches,
                    mean=True)
            loss = jnp.mean(losses)  # detlint: ok[DET001] m microbatch scalars; grads take the front door below
        else:
            grads, (loss, _) = grad_fn(params, batch)

        # ---- gradient reduction across the fleet: one policy knob ----
        grads, residuals = _reduce.collective_mean_tree(
            grads, residuals, axes, policy=policy, bits=bits)

        lr = lr_fn(opt_state.count + 1)   # count is 0-based
        params, opt_state, gnorm = adamw.update(
            grads, opt_state, params, lr=lr, clip_norm=clip_norm)
        loss = jax.lax.pmean(loss, axes)  # detlint: ok[DET001] logging metric only; grads go through collective_mean_tree
        return params, opt_state, residuals, {"loss": loss,
                                              "grad_norm": gnorm, "lr": lr}

    pspec = P()           # params replicated (pure DP; FSDP stays on pjit)
    bspec = P(axes if len(axes) > 1 else axes[0])
    return jax.shard_map(step, mesh=mesh,
                         in_specs=(pspec, pspec, pspec, bspec),
                         out_specs=(pspec, pspec, pspec, pspec),
                         check_vma=False)


def make_elastic_train_step(cfg: ModelConfig, mesh, *, lr_fn: Callable,
                            microbatch_size: int = 1,
                            moe_impl: str = "dense",
                            remat: bool = False,
                            clip_norm: float = 1.0,
                            policy: str = "exact2",
                            block_size: int = 512):
    """The topology-elastic training step: same params + same global batch
    => bitwise-identical new params and loss on *any* mesh.

    The difference from ``make_shardmap_train_step`` is that every
    quantity crossing the device boundary goes through
    ``repro.reduce.elastic_reduce_mean`` under a bitwise policy (exact2
    by default — all-int32 carry, residual included), and the unit of
    work is pinned to the *global* stream, not the topology:

      * ``microbatch_size`` is a fixed global constant.  shard_map splits
        the batch contiguously, each shard scans its rows in
        ``microbatch_size`` slices, so the set of microbatch gradients
        {rows [k*mb, (k+1)*mb)} is identical however many shards exist —
        only their assignment to devices changes.
      * the gradient mean and the loss mean are elastic reductions over
        that global microbatch stack: quantization grid shared by pmax,
        partition-invariant integer carries, one associative psum per
        component.  Bin-packing the same items differently cannot change
        a single bit.

    Combined with checkpointing this is the elastic-resume guarantee
    (docs/robustness.md): train on 2 devices, checkpoint, resume on 8 —
    the loss curve continues bit-for-bit (proven in tests/test_faults.py).

    Requires the per-shard row count (batch / n_devices) to be a
    multiple of ``microbatch_size``.

    state = (params, opt_state); returns (params, opt_state, metrics).
    """
    axes = tuple(mesh.axis_names)

    def step(params, opt_state, batch):
        def grad_fn(p, mb):
            (loss, metrics), g = jax.value_and_grad(
                lambda pp: loss_fn(pp, cfg, mb, moe_impl=moe_impl,
                                   remat=remat), has_aux=True)(p)
            return g, loss

        rows = jax.tree.leaves(batch)[0].shape[0]       # per-shard, static
        if rows % microbatch_size:
            raise ValueError(
                f"elastic step: per-shard batch of {rows} rows is not a "
                f"multiple of microbatch_size={microbatch_size}; the "
                f"global microbatch grid must tile every shard")
        m_local = rows // microbatch_size
        mbs = jax.tree.map(
            lambda x: x.reshape((m_local, microbatch_size) + x.shape[1:]),
            batch)

        def scan_body(_, mb):
            g, loss = grad_fn(params, mb)
            return None, (g, loss)

        _, (gstack, losses) = jax.lax.scan(scan_body, None, mbs)
        # one elastic mean per leaf over the global microbatch stack;
        # the loss is the same reduction (NOT a pmean — its combine
        # order would follow the topology)
        grads = jax.tree.map(
            lambda gs: _reduce.elastic_reduce_mean(
                gs, axes, policy=policy, block_size=block_size), gstack)
        loss = _reduce.elastic_reduce_mean(losses, axes, policy=policy,
                                           block_size=block_size)

        lr = lr_fn(opt_state.count + 1)   # count is 0-based
        params, opt_state, gnorm = adamw.update(
            grads, opt_state, params, lr=lr, clip_norm=clip_norm)
        return params, opt_state, {"loss": loss, "grad_norm": gnorm,
                                   "lr": lr}

    pspec = P()           # params replicated (pure DP)
    bspec = P(axes if len(axes) > 1 else axes[0])
    return jax.shard_map(step, mesh=mesh,
                         in_specs=(pspec, pspec, bspec),
                         out_specs=(pspec, pspec, pspec),
                         check_vma=False)


def init_residuals(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
