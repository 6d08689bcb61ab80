"""jit'd public wrappers around the Pallas kernels.

On CPU (this container) the kernels run with ``interpret=True`` — the kernel
body executes in Python, block by block, which validates the exact TPU
schedule.  On a real TPU backend the same code lowers to Mosaic.

The wrappers own the padding/tiling contracts so kernel bodies stay minimal:
  * segment_sum   pads N to the row-block, tiles the label space when the
                  (S, D) accumulator would not fit the VMEM budget;
  * intac_accum   pads N, enforces the int32 overflow bound;
  * flash_decode  pads S to the KV block with -inf bias, vmaps over
                  (batch, kv_head), broadcasts GQA groups.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.reduce.backends import OUT_OF_RANGE_LABEL
from repro.reduce.backends import interpret_default as _interpret_default
from repro.reduce.policy import get_policy

from . import flash_decode as _fd
from . import intac_accum as _ia
from . import jugglepac_segsum as _ss


# VMEM budget the segsum accumulator tile may claim (floats).
_SEGSUM_ACC_BUDGET = 2 * 1024 * 1024  # 8 MiB of f32 out of ~16 MiB VMEM


def seg_tile_for(num_segments: int, d: int, carries: int = 1) -> int:
    """Label-space tile size so all ``carries`` (S, D) carry tiles together
    fit the VMEM budget — the "few PIS registers, not a BRAM" rule.  The
    one source of truth for both this wrapper and the repro.reduce pallas
    backend (which passes ``policy.carry_len``)."""
    return max(1, min(num_segments,
                      _SEGSUM_ACC_BUDGET // (max(d, 1) * max(carries, 1))))


@functools.partial(jax.jit, static_argnames=("num_segments", "block_rows",
                                             "blocks_per_step", "interpret"))
def segment_sum(values: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int, *, block_rows: int = 512,
                blocks_per_step: Optional[int] = None,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """JugglePAC segmented sum. values (N, D) or (N,), ids (N,) int32.

    A thin wrapper over the one kernel body with the ``fast`` policy
    (f32 carry, identity finalize) — ``repro.reduce`` drives the same
    kernel for every other policy.  ``blocks_per_step`` sets the
    double-buffered supertile depth (None = sized from the VMEM window);
    it never changes the result bits, only how tiles stream.
    """
    interpret = _interpret_default() if interpret is None else interpret
    policy = get_policy("fast")
    squeeze = values.ndim == 1
    if squeeze:
        values = values[:, None]
    values = values.astype(jnp.float32)        # the fast policy's domain
    n, d = values.shape
    pad = (-n) % block_rows
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
        segment_ids = jnp.pad(segment_ids, (0, pad),
                              constant_values=OUT_OF_RANGE_LABEL)

    # Tile the label space so the accumulator fits the VMEM budget.
    seg_tile = seg_tile_for(num_segments, d)
    outs = []
    for off in range(0, num_segments, seg_tile):
        s = min(seg_tile, num_segments - off)
        outs.append(_ss.segsum_policy_pallas(
            values, segment_ids, s, policy=policy, block_rows=block_rows,
            seg_offset=off, blocks_per_step=blocks_per_step,
            interpret=interpret)[0])
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out[:, 0] if squeeze else out


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def intac_accum(values: jnp.ndarray, scale: jnp.ndarray, *,
                block_rows: int = 256,
                interpret: Optional[bool] = None) -> jnp.ndarray:
    """Exact fixed-point accumulation -> int32 limbs (2, D)."""
    interpret = _interpret_default() if interpret is None else interpret
    n, d = values.shape
    if n > (1 << 15):
        raise ValueError("intac_accum: N > 2^15 would risk limb overflow; "
                         "split the stream and limb_merge the results")
    pad = (-n) % block_rows
    if pad:
        values = jnp.pad(values, ((0, pad), (0, 0)))
    return _ia.intac_accum_pallas(values, scale, block_rows=block_rows,
                                  interpret=interpret)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_kv",
                                             "interpret", "partial_chunks"))
def flash_decode(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                 kv_len: jnp.ndarray, *, sm_scale: float,
                 window: Optional[int] = None, block_kv: int = 512,
                 partial_chunks: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """Batched GQA decode attention for one new token.

    q (B, H, d); k, v (B, S, K, d) with H = K * G; kv_len (B,) valid lengths.
    ``window``: optional sliding-window size (mixtral-style SWA masking).
    ``partial_chunks``: split the KV stream into this many chunks, run each
    as an independent kernel emitting a raw (m, l, o) partial, and combine
    the partials with ``repro.reduce``'s ``FlashAccumulator`` in a fixed
    pairwise tree — the single-host rehearsal of the cross-device decode
    path (each KV shard = one partial).
    Returns (B, H, d) f32.
    """
    interpret = _interpret_default() if interpret is None else interpret
    b, h, d = q.shape
    s_len, kheads = k.shape[1], k.shape[2]
    assert h % kheads == 0
    g = h // kheads
    pad = (-s_len) % block_kv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    sp = s_len + pad

    pos = jnp.arange(sp)[None, :]                       # (1, S)
    valid = pos < kv_len[:, None]
    if window is not None:
        valid &= pos >= (kv_len[:, None] - window)
    bias = jnp.where(valid, 0.0, _fd._NEG_INF)[:, None, :]  # (B, 1, S)
    bias = jnp.broadcast_to(bias, (b, kheads, sp))

    qg = q.reshape(b, kheads, g, d)
    kk = jnp.moveaxis(k, 2, 1)                          # (B, K, S, d)
    vv = jnp.moveaxis(v, 2, 1)

    if partial_chunks is not None and partial_chunks > 1:
        from repro.reduce import FlashAccumulator, merge_tree
        nb = sp // block_kv
        per = -(-nb // partial_chunks)                  # blocks per chunk
        runp = functools.partial(_fd.flash_decode_partial_pallas,
                                 sm_scale=sm_scale, block_kv=block_kv,
                                 interpret=interpret)
        acc = FlashAccumulator()

        def one(qq, k1, v1, b1):
            states = []
            for c in range(0, nb, per):
                lo, hi = c * block_kv, min(c + per, nb) * block_kv
                states.append(runp(qq, k1[lo:hi], v1[lo:hi],
                                   b1[None, lo:hi]))
            return acc.finalize(merge_tree(acc, states))

        out = jax.vmap(jax.vmap(one))(qg, kk, vv, bias)
        return out.reshape(b, h, d)

    run = functools.partial(_fd.flash_decode_pallas, sm_scale=sm_scale,
                            block_kv=block_kv, interpret=interpret)
    out = jax.vmap(jax.vmap(lambda qq, k1, v1, b1: run(qq, k1, v1, b1[None])))(
        qg, kk, vv, bias)                               # (B, K, G, d)
    return out.reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def flash_decode_paged(q: jnp.ndarray, k_pages: jnp.ndarray,
                       v_pages: jnp.ndarray, page_tables: jnp.ndarray,
                       kv_len: jnp.ndarray, *, sm_scale: float,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Paged-gather GQA decode attention for one new token.

    The KV cache lives in a shared pool of fixed-size pages
    (``serve.PagedKVPool``); each request addresses its logical context
    through a page table instead of a contiguous slab.

    q (B, H, d); k_pages, v_pages (P, ps, K, d) — the *shared* physical
    pool (P pages of ps tokens, K kv-heads); page_tables (B, nb) int32,
    ``FREE_PAGE``-padded (padded entries are clamped to page 0 and masked
    via the length bias); kv_len (B,) valid lengths.  Returns (B, H, d)
    f32 — bitwise identical to ``flash_decode`` with ``block_kv=ps`` on
    the logically-assembled contiguous cache.
    """
    interpret = _interpret_default() if interpret is None else interpret
    if q.ndim != 3 or k_pages.ndim != 4:
        raise ValueError(
            "flash_decode_paged: expected q (B, H, d) and k_pages/v_pages "
            f"(P, ps, K, d); got q {q.shape}, k_pages {k_pages.shape}")
    if page_tables.ndim != 2 or page_tables.shape[0] != q.shape[0]:
        raise ValueError(
            "flash_decode_paged: page_tables must be (B, nb) matching "
            f"q's batch {q.shape[0]}; got {page_tables.shape}")
    b, h, d = q.shape
    ps, kheads = k_pages.shape[1], k_pages.shape[2]
    assert h % kheads == 0
    g = h // kheads
    nb = page_tables.shape[1]
    sp = nb * ps

    pos = jnp.arange(sp)[None, :]
    bias = jnp.where(pos < kv_len[:, None], 0.0, _fd._NEG_INF)  # (B, S)
    tables = jnp.maximum(page_tables.astype(jnp.int32), 0)      # clamp pads

    qg = q.reshape(b, kheads, g, d)
    kp = jnp.moveaxis(k_pages, 2, 0)                    # (K, P, ps, d)
    vp = jnp.moveaxis(v_pages, 2, 0)

    run = functools.partial(_fd.flash_decode_paged_pallas,
                            sm_scale=sm_scale, interpret=interpret)
    rows = []
    for bi in range(b):                 # page tables are per-request: loop,
        heads = [run(qg[bi, kh], kp[kh], vp[kh], bias[bi][None],
                     tables[bi])        # don't vmap over prefetch operands
                 for kh in range(kheads)]
        rows.append(jnp.stack(heads))                   # (K, G, d)
    return jnp.stack(rows).reshape(b, h, d)
