"""The streaming ``Accumulator`` protocol — one contract for the streaming
state machines in the repo.

JugglePAC is ultimately a streaming accumulator with bounded state; the
repo grew ad-hoc incarnations of that idea (the gradient juggler's
``JugglerState``, flash-decode's (m, l, o) partials, the cascaded
stages), each with its own init/step/merge spelling.  This module gives
them one protocol:

    init(template)      -> state        bounded, pytree-shaped
    push(state, x)      -> state        consume one stream element
    merge(a, b)         -> state        combine two partial streams
                                        (cross-block / cross-device)
    finalize(state)     -> value        the once-per-set "final addition"

Any instance composes with ``lax.scan`` (push is the step function) and
with fixed pairing trees (``merge_tree``), so the same code path handles
microbatch gradients and attention partials.  The accuracy tiers are not
accumulators: each is one ``Policy`` (``policy.py``), which ``reduce``,
the ``shard_map`` backend and the collectives all run.

Instances:
  * ``TreeAccumulator``  — binary-counter pairwise tree (wraps
    ``core.juggler``): O(log n) live state, O(log n) error growth.
  * ``FlashAccumulator`` — online-softmax (m, l, o) triple (wraps
    ``core.segmented``): the "any multi-cycle operator" clause of the
    paper, instantiated for attention.
  * ``CascadeAccumulator`` — ``depth`` chained plain accumulators, the
    cascaded-PAC time-index weighting.
"""

from __future__ import annotations

import math
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import juggler


@runtime_checkable
class Accumulator(Protocol):
    """Structural protocol: anything with init/push/merge/finalize.

    ``merge`` is the declared combiner — it is what ``merge_tree`` folds
    with locally and what ``merge_across`` folds with across devices, so
    stating it once gives a state machine both a streaming and a
    distributed face.

    >>> import jax.numpy as jnp
    >>> acc = TreeAccumulator(num_slots=2)
    >>> st = acc.init(jnp.zeros(2))
    >>> st = acc.push(st, jnp.asarray([1.0, 2.0]))
    >>> st = acc.push(st, jnp.asarray([3.0, 4.0]))
    >>> [float(v) for v in acc.finalize(st)]
    [4.0, 6.0]
    >>> isinstance(acc, Accumulator)
    True
    """

    def init(self, template) -> Any: ...

    def push(self, state, x) -> Any: ...

    def merge(self, a, b) -> Any: ...

    def finalize(self, state) -> Any: ...


class TreeAccumulator:
    """Binary-counter pairwise-tree accumulation of pytrees.

    The software PIS: ``num_slots`` >= ceil(log2 pushes) + 1 slots bound
    the live state; the pairing schedule depends only on the push count.
    """

    def __init__(self, num_slots: int):
        self.num_slots = num_slots

    @classmethod
    def for_count(cls, num_pushes: int) -> "TreeAccumulator":
        return cls(juggler.num_slots_for(num_pushes))

    def init(self, template) -> juggler.JugglerState:
        return juggler.juggler_init(template, self.num_slots)

    def push(self, state, x) -> juggler.JugglerState:
        return juggler.juggler_push(state, x)

    def merge(self, a, b) -> juggler.JugglerState:
        """Fold b's slots to one partial and insert it into a's counter —
        a fixed, deterministic (if unbalanced) pairing of the two trees."""
        folded = juggler.juggler_finalize(b)
        merged = juggler.juggler_push(a, folded)
        return merged._replace(count=a.count + b.count)

    def finalize(self, state, *, mean: bool = False):
        return juggler.juggler_finalize(state, mean=mean)


class FlashAccumulator:
    """Online-softmax partials: state = (max m, denom l, weighted out o).

    ``push``/``merge`` are the same associative combine (flash partials are
    their own partial-stream type); ``finalize`` returns the normalized
    output ``o / l``.
    """

    _NEG = -1e30

    def init(self, template):
        m, l, o = template
        return (jnp.full(jnp.shape(m), self._NEG, jnp.float32),
                jnp.zeros(jnp.shape(l), jnp.float32),
                jnp.zeros(jnp.shape(o), jnp.float32))

    def push(self, state, partial):
        # lazy import: core.segmented imports repro.reduce for the shared
        # sentinel, so this edge must not exist at module-load time.
        from repro.core.segmented import flash_partial_combine
        m1, l1, o1 = state
        m2, l2, o2 = partial
        return flash_partial_combine(m1, l1, o1, m2, l2, o2)

    def merge(self, a, b):
        return self.push(a, b)

    def finalize(self, state):
        m, l, o = state
        return o / jnp.maximum(l, 1e-30)[..., None]


class CascadeAccumulator:
    """``depth`` chained plain accumulators — the cascaded-PAC
    construction of arXiv 2509.15069, as a streaming state machine.

    Every push folds the element into stage 1 and then re-folds each
    stage's running value into the next: after n pushes stage k holds
    the binomially time-index-weighted sum
    ``sum_i C(n-1-i + k-1, k-1) x_i`` (``algebra.cascade_weights``), so
    a fixed linear combination of the stages realizes any polynomial
    time-index weighting (``algebra.cascade_poly_coeffs``) — FIR-style
    weighted reduction out of nothing but plain adders.

    State is ``(count, stage sums)``; ``merge`` concatenates two
    partial streams *in argument order* (a then b) via the exact
    stage-mixing law — for ``m = b.count`` trailing elements,
    ``S_k = A_k + B_k + sum_{j<k} C(m+k-j-1, k-j) A_j`` — so chunked or
    scanned evaluation matches the one-shot stream.  ``finalize``
    stacks the stage sums (leading axis = stage).

    >>> import jax.numpy as jnp
    >>> acc = CascadeAccumulator(2)
    >>> st = acc.init(jnp.zeros(()))
    >>> for v in (1.0, 10.0, 100.0):
    ...     st = acc.push(st, jnp.asarray(v))
    >>> [float(v) for v in acc.finalize(st)]      # [sum, 3*1+2*10+1*100]
    [111.0, 123.0]
    >>> a = acc.init(jnp.zeros(())); b = acc.init(jnp.zeros(()))
    >>> a = acc.push(a, jnp.asarray(1.0))
    >>> for v in (10.0, 100.0):
    ...     b = acc.push(b, jnp.asarray(v))
    >>> [float(v) for v in acc.finalize(acc.merge(a, b))]
    [111.0, 123.0]
    """

    def __init__(self, depth: int):
        if depth < 1:
            raise ValueError(f"cascade depth must be >= 1, got {depth}")
        self.depth = int(depth)

    def init(self, template):
        z = jnp.zeros(jnp.shape(template), jnp.float32)
        return (jnp.zeros((), jnp.int32), (z,) * self.depth)

    def push(self, state, x):
        count, sums = state
        run = x.astype(jnp.float32)
        new = []
        for s in sums:
            run = s + run               # stage k folds stage k-1's value
            new.append(run)
        return (count + 1, tuple(new))

    def merge(self, a, b):
        ca, sa = a
        cb, sb = b
        m = cb.astype(jnp.float32)
        out = []
        for k in range(1, self.depth + 1):
            s = sa[k - 1] + sb[k - 1]
            # detlint: ok[DET002] closed-form cascade merge: fixed small
            # depth, order is part of the formula; property tests pin it
            for j in range(1, k):
                r = k - j               # C(m + r - 1, r), m traced
                coef = jnp.float32(1.0)
                for t in range(r):
                    coef = coef * (m + t)
                s = s + (coef / math.factorial(r)) * sa[j - 1]
            out.append(s)
        return (ca + cb, tuple(out))

    def finalize(self, state):
        return jnp.stack(state[1], axis=0)


# ---------------------------------------------------------------------------
# Composition helpers
# ---------------------------------------------------------------------------


def scan_accumulate(acc: Accumulator, xs, template=None):
    """Fold a stacked stream (leading axis) through ``acc`` with lax.scan."""
    if template is None:
        template = jax.tree.map(lambda x: x[0], xs)
    state0 = acc.init(template)
    state, _ = jax.lax.scan(lambda s, x: (acc.push(s, x), None), state0, xs)
    return acc.finalize(state)


def merge_tree(acc: Accumulator, states):
    """Fixed pairwise-tree merge of a list of accumulator states."""
    items = list(states)
    if not items:
        raise ValueError("merge_tree: empty state list")
    while len(items) > 1:
        nxt = [acc.merge(items[i], items[i + 1])
               for i in range(0, len(items) - 1, 2)]
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]


def merge_across(acc: Accumulator, state, axis_names):
    """Cross-device merge of per-device accumulator states (inside
    shard_map).

    Every ``Accumulator`` states its combiner as ``merge``; this is the
    collective face of that contract — the same role
    ``collective.merge_carry_across`` plays for policy carries.  Each
    state leaf all-gathers along ``axis_names`` and the per-device
    states fold strictly in device order, so the combine schedule is a
    pure function of the mesh — deterministic, and exact whenever
    ``merge`` is.

    Example (one-device mesh; any device count works the same way):

    >>> import jax, jax.numpy as jnp, numpy as np
    >>> from jax.sharding import Mesh, PartitionSpec as P
    >>> mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    >>> acc = CascadeAccumulator(1)
    >>> def f(x):
    ...     st = acc.push(acc.init(x), x)          # local partial stream
    ...     return acc.finalize(merge_across(acc, st, ("data",)))[0]
    >>> out = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
    ...                     check_vma=False)(jnp.asarray([2.0, 3.0]))
    >>> [float(v) for v in out]
    [2.0, 3.0]
    """
    axes = tuple(axis_names)
    gathered = jax.tree.map(
        lambda x: jax.lax.all_gather(x, axes, axis=0), state)
    nshards = jax.tree.leaves(gathered)[0].shape[0]
    merged = jax.tree.map(lambda x: x[0], gathered)
    # detlint: ok[DET002] strict device-order merge is the contract:
    # the fold order is a pure function of the mesh
    for k in range(1, nshards):
        merged = acc.merge(merged, jax.tree.map(lambda x: x[k], gathered))
    return merged


def reduce_microbatch_grads(grad_fn, params, microbatches, *,
                            num_microbatches: int, policy: str,
                            backend=None, mesh=None):
    """Microbatch gradient mean through the ``repro.reduce`` front door.

    The policy-exact alternative to ``accumulate_microbatch_grads``:
    per-microbatch gradients stack into an (m, |leaf|) stream per leaf
    (one row per microbatch = one schedule block) and mean under any
    accuracy policy — with the integer tiers, the result is bitwise
    independent of microbatch count and executor.  Costs m live gradient
    copies instead of O(log m).  ``backend=None`` auto-selects; pass
    ``mesh`` to route the reduction through the ``shard_map`` backend
    explicitly (ambient-mesh auto-selection is deliberately inert inside
    a jit trace, and for m-row streams the local executor is normally
    the right choice anyway).  Returns (mean_grads, aux_stacked); leaf
    dtypes are preserved.
    """
    from .api import ReduceSpec, reduce as _reduce
    spec = ReduceSpec(op="mean", policy=policy, backend=backend,
                      block_size=1)

    def scan_step(_, mb):
        g, aux = grad_fn(params, mb)
        return 0, (g, aux)

    _, (stacked, aux) = jax.lax.scan(scan_step, 0, microbatches)
    grads = jax.tree.map(
        lambda g: _reduce(
            g.astype(jnp.float32).reshape(num_microbatches, -1),
            spec=spec, mesh=mesh)
        .reshape(g.shape[1:]).astype(g.dtype), stacked)
    return grads, aux


def accumulate_microbatch_grads(grad_fn, params, microbatches, *,
                                num_microbatches: int, mean: bool = True):
    """Microbatch gradient accumulation through the Accumulator protocol.

    Scans ``grad_fn(params, mb)`` over stacked microbatches, pushing each
    gradient into a ``TreeAccumulator`` (O(log n) live copies, fixed
    pairing schedule).  Returns (mean_or_sum, aux_stacked).
    """
    acc = TreeAccumulator.for_count(num_microbatches)

    template = jax.eval_shape(
        lambda p, m: grad_fn(p, m)[0], params,
        jax.tree.map(lambda x: x[0], microbatches))
    template = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), template)

    def step(state, mb):
        g, aux = grad_fn(params, mb)
        return acc.push(state, g), aux

    state, aux = jax.lax.scan(step, acc.init(template), microbatches)
    return acc.finalize(state, mean=mean), aux
