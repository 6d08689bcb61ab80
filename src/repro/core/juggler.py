"""GradientJuggler — streaming pairwise-tree accumulation with bounded slots.

The software twin of JugglePAC's PIS: when microbatch gradients arrive one
per scan step, accumulate them with a *binary-counter* pairing tree instead
of a serial ``+=``:

    step 1:  slots = [g1]
    step 2:  slots = [g1+g2]            (carry to level 1)
    step 3:  slots = [g1+g2, g3]
    step 4:  slots = [(g1+g2)+(g3+g4)]  (carry chain)

This reproduces the Fig. 2 accumulation tree exactly: level-0 insertions are
FSM state 1 (pair raw inputs), carry-chain combines are state 0 (pair
partials), and the slot array is the PIS register file — ``num_slots`` =
ceil(log2 n) registers bound the live storage, the paper's "2–8 registers"
area argument translated to memory footprint (log n live gradient copies vs
n for a naive tree, 1 for serial).

Why bother vs serial ``+=``: the pairing tree's rounding-error growth is
O(log n) instead of O(n) — the paper's numerical motivation — and the fixed
schedule makes gradient accumulation bitwise independent of how microbatches
are grouped, which combines with an integer-tier ``collective_mean`` to
give fully deterministic distributed training.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp


class JugglerState(NamedTuple):
    slots: object        # pytree of (K, *leaf_shape) stacked slot arrays
    occupancy: jnp.ndarray  # (K,) bool
    count: jnp.ndarray      # scalar int32: number of items pushed


def juggler_init(grad_template, num_slots: int) -> JugglerState:
    """``num_slots`` must be >= ceil(log2(num_pushes))."""
    slots = jax.tree.map(
        lambda g: jnp.zeros((num_slots,) + g.shape, g.dtype), grad_template)
    return JugglerState(slots, jnp.zeros((num_slots,), bool), jnp.int32(0))


def juggler_push(state: JugglerState, grad) -> JugglerState:
    """Insert one gradient; resolve the binary carry chain.

    The insertion level is the number of trailing occupied slots (they are
    all merged into the incoming value, lowest level first — a fixed order).
    """
    k = state.occupancy.shape[0]
    lvl = jnp.argmin(state.occupancy)        # first free slot
    # all slots below `lvl` are occupied (binary-counter invariant)
    lvl = jnp.where(jnp.all(state.occupancy), k, lvl)  # overflow guard

    def merge_leaf(slot_arr, g):
        def body(i, c):
            return jnp.where(i < lvl, slot_arr[i] + c, c)
        carry = jax.lax.fori_loop(0, k, body, g)
        mask = (jnp.arange(k) == lvl)
        mask = mask.reshape((k,) + (1,) * g.ndim)
        return jnp.where(mask, carry[None], slot_arr)

    new_slots = jax.tree.map(merge_leaf, state.slots, grad)
    idx = jnp.arange(k)
    new_occ = (idx == lvl) | (state.occupancy & (idx > lvl))
    return JugglerState(new_slots, new_occ, state.count + 1)


def juggler_finalize(state: JugglerState, *, mean: bool = False):
    """Fold remaining slots low->high (fixed order); optionally average."""
    k = state.occupancy.shape[0]

    def fold_leaf(slot_arr):
        def body(i, c):
            return jnp.where(state.occupancy[i], c + slot_arr[i], c)
        return jax.lax.fori_loop(0, k, body,
                                 jnp.zeros(slot_arr.shape[1:], slot_arr.dtype))

    total = jax.tree.map(fold_leaf, state.slots)
    if mean:
        denom = jnp.maximum(state.count, 1).astype(jnp.float32)
        total = jax.tree.map(lambda t: t / denom.astype(t.dtype), total)
    return total


def num_slots_for(num_microbatches: int) -> int:
    k = 0
    while (1 << k) < max(num_microbatches, 1):
        k += 1
    return max(k, 1) + 1  # +1 headroom for the final carry
