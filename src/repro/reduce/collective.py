"""Policy-selectable cross-device means — the distributed face of
``repro.reduce``.

Gradient all-reduces take the same accuracy knob the array API exposes:

  * ``fast``        — hierarchical fp32 psum ('data' in-pod ICI first,
                      then 'pod' DCI), divide once.
  * ``compensated`` — INTAC *compressed* all-reduce with error feedback:
    quantize to ``bits``-bit fixed point on a shared power-of-two scale,
    psum in the exact integer domain, dequantize once; the local
    quantization error is carried as next step's residual — the
    collective analogue of a Kahan compensation term (bits/32 of the
    fp32 payload on the wire).  A different algorithm from ``reduce``'s
    two-sum ``compensated`` tier, under the same name.
  * ``exact`` / ``exact2`` / ``procrastinate`` — the integer tiers are
    their ``Policy`` (``policy.py``), run by the recipe
    ``elastic_reduce_mean`` runs (``_policy_mean``), one term per
    device: a ``pmax``-shared max and the device count size the grid
    (``Policy.prepare_ctx``), ``Policy.to_domain`` maps the leaf into
    the integer domain a chunk of rows at a time, the carries merge with
    one associative int32 ``psum`` (``merge_carry_across``), and
    ``Policy.finalize`` resolves each chunk once.
    That is the path the ``shard_map`` backend of ``reduce`` runs, so a
    collective mean is bitwise the ``op="mean"`` reduction of the same
    rows, at any device count, mesh shape or device permutation.

All tiers share one signature so training code switches policy without
rewiring residual plumbing: ``(mean, new_residual)`` — every tier except
compensated passes ``residual`` through untouched (including ``None``;
only compensated materializes an error-feedback state).

Must be called inside ``shard_map`` (they use named-axis collectives).

``merge_carry_across`` is the second face of this module: where
``collective_mean`` reduces *raw gradients* across devices, it reduces
*policy carries* — the partial block-schedule state each shard of the
``shard_map`` backend produced — with the policy's own combiner (one
fused integer ``psum`` for the integer tiers, a gathered in-order
two-sum fold for compensated's float carry).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import intac
from .backends import get_backend
from .policy import Policy, fused_psum, get_policy

COLLECTIVE_POLICIES = ("fast", "compensated", "exact", "exact2",
                       "procrastinate")
#: the integer tiers' layout of a leaf: rows of this many lanes, reduced
#: in chunks of at most this many rows (2^21 values: an exact2 chunk's
#: temporaries are about 0.2 GiB, whatever the leaf's size)
_ROW_LANES = 1024
_CHUNK_ROWS = 2048


def merge_carry_across(policy: Policy, carry, axis_names):
    """Merge per-shard policy carries across mesh axes (inside shard_map).

    ``carry`` is the policy carry tuple a local backend produced from a
    shard's blocks.  The lowering is the policy's own
    (``Policy.merge_across``): one associative int32 psum per integer
    carry component (any psum topology gives the same bits; every
    component of exact, exact2 and procrastinate is an int32 that merges
    by addition), and an all-gather + strict
    device-order fold with ``policy.merge`` for order-sensitive float
    state (compensated's carry), which pins the combine schedule the way
    the block schedule pins per-shard order.
    """
    return policy.merge_across(carry, axis_names)


def collective_mean(x: jnp.ndarray, axis_names: Sequence[str], *,
                    policy: str = "fast", bits: int = 8,
                    residual: Optional[jnp.ndarray] = None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-device mean of one array under an accuracy policy.

    ``axis_names`` is ordered outermost (slowest, e.g. 'pod') to innermost
    (fastest, e.g. 'data'); reductions run innermost-first to match the
    physical topology.  Returns (mean, new_residual).

    Must run inside ``shard_map`` — e.g. on a one-device mesh:

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from jax.sharding import Mesh, PartitionSpec as P
    >>> mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    >>> f = lambda x: collective_mean(x, ("data",), policy="exact2")[0]
    >>> out = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
    ...                     check_vma=False)(jnp.asarray([1.5, -2.0]))
    >>> [float(v) for v in out]
    [1.5, -2.0]
    """
    axes = tuple(axis_names)
    if policy == "fast":
        g = x
        for a in reversed(axes):
            g = jax.lax.psum(g, a)      # innermost (fastest) axis first
        return g / jax.lax.psum(jnp.float32(1.0), axes), residual  # detlint: ok[DET006] device count well under 2^24; one collective keeps the fast tier fast

    # the integer tiers are their policies, one term per device.  The
    # leaf is laid out as lane-dense rows, each row its own segment (a
    # device's contribution to a segment is its mapped row, so one
    # ``update`` gives the carry a one-row block would), in chunks of
    # rows reduced one after another, so the domain's temporaries are a
    # chunk's and not the leaf's
    if policy in ("exact", "exact2", "procrastinate"):
        pol = get_policy(policy)
        n = x.size
        w = min(_ROW_LANES, max(n, 1))
        rows = -(-n // w)
        chunks = -(-rows // _CHUNK_ROWS)
        step = -(-rows // chunks)
        flat = jnp.pad(x.reshape(-1), (0, chunks * step * w - n))

        def carry(domain):
            return pol.update(pol.init(step, domain.shape[1]),
                              domain.astype(pol.acc_dtype))

        out = _policy_mean(pol, flat.reshape(chunks, step, w), 1, axes,
                           carry)
        return out.reshape(-1)[:n].reshape(x.shape), residual

    if policy == "compensated":
        if residual is None:       # only this policy materializes a state
            residual = jnp.zeros(x.shape, jnp.float32)
        return intac.compressed_psum_mean(x, residual, axes, bits=bits)

    raise ValueError(f"unknown collective policy {policy!r}; "
                     f"choose from {COLLECTIVE_POLICIES}")


def collective_weighted_mean(x: jnp.ndarray, w: jnp.ndarray, axis_names,
                             *, policy: str = "fast", bits: int = 8,
                             eps: float = 1e-9) -> jnp.ndarray:
    """Cross-device weighted mean ``sum(w*x) / sum(w)`` under an
    accuracy policy — the collective face of ``op="weighted_sum"``.

    Both the weighted numerator and the weight mass reduce through
    ``collective_mean`` (the per-device counts cancel in the ratio), so
    each gets its own policy-sized quantization grid; for the bitwise
    tiers the result is invariant to topology like the mean itself.
    Must run inside ``shard_map``.

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from jax.sharding import Mesh, PartitionSpec as P
    >>> mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    >>> f = lambda x, w: collective_weighted_mean(x, w, ("data",),
    ...                                           policy="exact2")
    >>> out = jax.shard_map(f, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
    ...                     check_vma=False)(jnp.asarray([1.0, 4.0]),
    ...                                      jnp.asarray([3.0, 1.0]))
    >>> [float(v) for v in out]                    # per-element w*x / w
    [1.0, 4.0]
    """
    num, _ = collective_mean(x * w, axis_names, policy=policy, bits=bits)
    den, _ = collective_mean(w, axis_names, policy=policy, bits=bits)
    return num / jnp.maximum(den, eps)


def collective_moments(x: jnp.ndarray, axis_names, *,
                       policy: str = "fast", bits: int = 8
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cross-device running moments: elementwise (mean, var) over the
    device axis — the collective face of ``op="moments"``.

    Two ``collective_mean`` passes (E[x] and E[x^2]) rather than one
    concatenated payload: the integer tiers size their quantization
    grid per collective, and x and x^2 live on very different scales —
    sharing a grid would cost the smaller component its resolution.
    ``var = max(E[x^2] - E[x]^2, 0)`` with the clamp guarding float-tier
    cancellation; under a bitwise tier both expectations — hence the
    moments — are invariant to topology.  Must run inside ``shard_map``.

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from jax.sharding import Mesh, PartitionSpec as P
    >>> mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    >>> f = lambda x: collective_moments(x, ("data",), policy="exact2")
    >>> m, v = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=(P(), P()),
    ...                      check_vma=False)(jnp.asarray([1.5, -2.0]))
    >>> [float(a) for a in m], [float(a) for a in v]
    ([1.5, -2.0], [0.0, 0.0])
    """
    m1, _ = collective_mean(x, axis_names, policy=policy, bits=bits)
    m2, _ = collective_mean(x * x, axis_names, policy=policy, bits=bits)
    return m1, jnp.maximum(m2 - m1 * m1, 0.0)


def elastic_reduce_mean(stack: jnp.ndarray, axis_names, *,
                        policy: str = "exact2",
                        block_size: int = 512) -> jnp.ndarray:
    """Topology-elastic global mean of a sharded item stack.

    ``stack`` is this shard's (m_local, ...) slice of a global stack of
    items (microbatch gradients, per-example losses); the result is the
    mean over *all* items on *all* shards, with the elastic guarantee:
    for a bitwise policy (``exact2`` since the residual-digit redesign,
    ``exact``, ``procrastinate``) the returned floats are bit-identical
    no matter how the same global stack is split across devices — 1x8,
    2x4, 8x1, or any permutation.  Three ingredients make that hold:

      * the quantization scale is sized from a ``pmax``-shared global
        max, so every shard prepares on the same grid;
      * the carry out of the local block schedule is partition-invariant
        (canonical integer limbs / exponent-indexed digits are pure
        functions of the global integer sums);
      * cross-shard merge is one associative integer ``psum`` per carry
        component (``merge_carry_across``).

    Must run inside ``shard_map``.  This is the reduction under
    ``repro.distributed.collectives.make_elastic_train_step`` and the
    resume-anywhere checkpoint story in ``docs/robustness.md``.

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from jax.sharding import Mesh, PartitionSpec as P
    >>> mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    >>> f = lambda x: elastic_reduce_mean(x, ("data",))
    >>> out = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P(),
    ...                     check_vma=False)(jnp.asarray([[1.0, 3.0]]))
    >>> [float(v) for v in out]
    [1.0, 3.0]
    """
    pol = get_policy(policy)
    m_local = stack.shape[0]
    flat = stack.reshape(m_local, -1)                       # (m, D)

    def carry(domain):
        return get_backend("blocked").run(
            domain, jnp.zeros(m_local, jnp.int32), 1, policy=pol,
            block_size=block_size)

    out = _policy_mean(pol, flat[None], m_local, tuple(axis_names), carry)
    return out[0, 0].reshape(stack.shape[1:])


def _policy_mean(pol: Policy, rows: jnp.ndarray, num_local: int, axes,
                 local_carry) -> jnp.ndarray:
    """The cross-device mean of one policy, shared by ``collective_mean``
    and ``elastic_reduce_mean``: ``num_local`` terms on this shard,
    ``rows`` their values as (C, R, D) chunks, and ``local_carry`` the
    shard's (R-segment) carry of one chunk's mapped domain.  Every shard
    maps on the grid of the ``pmax``-shared global max with the global
    term count; chunk by chunk, the carries merge across ``axes`` and
    the finalized sums divide by the count.  The map is row-local, so
    the chunking moves no bit.
    """
    num_total = jax.lax.psum(num_local, axes)
    gmax = jax.lax.pmax(jnp.max(jnp.abs(rows)), axes)
    ctx = pol.prepare_ctx(gmax, num_total)

    def one(chunk):
        domain = pol.to_domain(chunk.astype(jnp.float32), ctx)
        carry = merge_carry_across(pol, local_carry(domain), axes)
        return pol.finalize(carry, ctx) / num_total

    if rows.shape[0] == 1:
        return one(rows[0])[None]
    return jax.lax.map(one, rows)


def collective_mean_tree(grads, residuals, axis_names, *,
                         policy: str = "fast", bits: int = 8):
    """Pytree version of ``collective_mean``; residuals may be None.

    The fast tier fuses the whole tree: instead of one hierarchical psum
    per leaf (a per-leaf collective latency floor that dominates small
    parameter trees), every leaf ravel-concats into one batched psum per
    dtype per mesh axis (``fused_psum``, innermost axis first as before).
    psum is elementwise, so each leaf's bits are identical to the
    per-leaf lowering.  The integer tiers keep per-leaf collectives:
    their quantization grids (pmax-shared scale / window anchor) are
    sized per leaf, which is an accuracy property worth one collective
    each.
    """
    flat_g, tdef = jax.tree.flatten(grads)
    flat_r = ([None] * len(flat_g) if residuals is None
              else tdef.flatten_up_to(residuals))
    if policy == "fast" and len(flat_g) > 1:
        axes = tuple(axis_names)
        leaves = flat_g
        for a in reversed(axes):    # innermost (fastest) axis first
            leaves = fused_psum(leaves, (a,))
        n = jax.lax.psum(jnp.float32(1.0), axes)  # detlint: ok[DET006] device count well under 2^24
        return tdef.unflatten([g / n for g in leaves]), \
            tdef.unflatten(flat_r)
    means, res = [], []
    for g, r in zip(flat_g, flat_r):
        m, nr = collective_mean(g, axis_names, policy=policy, bits=bits,
                                residual=r)
        means.append(m)
        res.append(nr)
    return tdef.unflatten(means), tdef.unflatten(res)
