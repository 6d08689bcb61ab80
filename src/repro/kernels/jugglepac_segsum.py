"""Pallas TPU kernel: JugglePAC segmented streaming sum.

The circuit's streaming schedule, mapped to the TPU grid:

  * the serial 1-value/cycle input bus  ->  one (K*B, D) VMEM supertile per
    grid step holding K consecutive schedule blocks (TPU grid steps execute
    sequentially on a core, so the stream order is preserved — "cycles"
    become grid steps);
  * the paper's back-to-back overlap   ->  double buffering at two levels:
    Pallas's automatic grid pipelining copies supertile i+1 HBM->VMEM while
    the kernel body runs supertile i, and *inside* the body the loop is
    software-pipelined — block j+1's (ids, vals) tiles are loaded before
    ``policy.update`` folds block j, so the gather stage of the next block
    overlaps the compute stage of the current one (the JugglePAC overlap,
    in-kernel);
  * FSM state 1 (pair raw inputs)       ->  the intra-tile reduction — the
    staged program's contrib stage: the one-hot MXU matmul (f32 at full
    precision for the float tiers, exact bf16 planes for the integer
    tiers — see ``repro.reduce.policy.int_plane_dot``); the PhasedAccu
    lane-parallel scatter runs only under interpret mode;
  * the PIS register file               ->  the policy's carry tuple — (S, D)
    tiles resident in VMEM across grid steps (same output block revisited),
    addressed by segment label exactly like the PIS registers are addressed
    by set label;
  * in-order emission                   ->  row s of the output is segment s.

There is exactly ONE kernel body for the block schedule:
``_segsum_policy_kernel`` executes the staged contrib
(``repro.reduce.program.block_contrib`` — the very helper ref/blocked
call) + ``policy.update`` — so the cross-backend bitwise contract holds
for every policy (fast / compensated f32 carries, exact single-limb,
exact2 limbs + residual-digit planes, procrastinate bins) by construction
rather than by duplicated code.  Multi-block supertiles change only *when*
tiles move, never the fold order: block j still folds before block j+1,
so results are bitwise identical at any ``blocks_per_step``.

The input stage of the integer tiers runs here too, as the paper's
pipelined input stage: given ``to_domain`` (``Policy.map_rows``), the
kernel reads raw (K*B, D) f32 rows and, per block, zeroes the dropped
rows, maps the block into the policy's domain in VMEM (exact2: the
quantized part and seven residual digit planes, 8·D wide) and folds it —
so the wide domain never exists in HBM.  The map's scalars (the scale,
its exact power-of-two inverse factors) arrive in SMEM, computed once
outside the kernel; the body needs only multiplies, round-half-even,
converts, shifts and masks.  ``ref`` and ``blocked`` call the same map
per block, so the bitwise contract still holds by construction.

VMEM budget per step: K*B*D (values) + K*B (ids) + carry_len*S*W floats —
the callers (ops.segment_sum, the reduce pallas backend) tile the label
space when the carry would exceed the budget, and ``blocks_per_step_for``
sizes K so the double-buffered input window stays modest (the software
analogue of "2–8 PIS registers, not a BRAM").

The reduction algebra (``repro.reduce.algebra``) needs no kernel of its
own: an op's ``pre`` widens the stream *before* dispatch (``moments``
folds ``[v | v*v]`` planes, components*D wide), so the width ``d`` this
file sees is already the op-widened domain — ``blocks_per_step_for``
shrinks the supertile depth to keep the same VMEM window, and the fold
order (hence every bitwise contract) is untouched.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.reduce.backends import OUT_OF_RANGE_LABEL, carry_width, map_block
from repro.reduce.program import block_contrib

#: bytes of f32 input tiles one grid step may hold; with Pallas's grid
#: pipelining double-buffering the window, the live footprint is 2x this
_INPUT_WINDOW_BYTES = 1 << 19           # 512 KiB


def blocks_per_step_for(block_rows: int, width: int) -> int:
    """Schedule blocks per grid step (the supertile depth K).

    Sized so the (K*B, W) values + (K*B, 1) ids input window fits
    ``_INPUT_WINDOW_BYTES`` — deep enough that the per-grid-step copy
    amortizes over K contrib+update stages, shallow enough that double
    buffering the window stays far from the VMEM the carry needs.
    """
    per_block = block_rows * (width + 1) * 4
    return int(max(1, min(8, _INPUT_WINDOW_BYTES // max(per_block, 1))))


def _segsum_policy_kernel(ids_ref, vals_ref, *refs, num_segments: int,
                          seg_offset: int, policy, program,
                          block_rows: int, blocks_per_step: int,
                          interpret: bool, to_domain, num_prep: int):
    """The streaming schedule with the accuracy-policy carry baked in.

    With ``to_domain``, ``vals_ref`` holds raw rows and ``refs`` starts
    with the (num_prep,) SMEM vector of the map's scalars; each block is
    masked and mapped (``backends.map_block``) before its contrib.

    The staged contrib (``block_contrib`` — the dot form; the lane form
    only under interpret mode) and ``policy.update`` are traced straight
    into the grid loop — the one canonical op sequence per (policy,
    program); the cross-backend bitwise contract depends on these being
    the very functions the blocked/ref backends call.  Policies executed
    here must zero-init their carry.

    The body is software-pipelined over the supertile's blocks: tile j+1
    loads from the VMEM supertile before ``update`` folds tile j, telling
    the compiler the next gather never waits on the current fold.  The
    fold order is untouched — bitwise identical at any supertile depth.
    """
    step = pl.program_id(0)
    prep = ()
    if num_prep:
        prep = tuple(refs[0][k] for k in range(num_prep))
        refs = refs[1:]
    out_refs = refs

    @pl.when(step == 0)
    def _init():
        for r in out_refs:
            r[...] = jnp.zeros_like(r)

    def load(j):
        rows = pl.dslice(j * block_rows, block_rows)
        return ids_ref[rows, :], vals_ref[rows, :]

    carry = tuple(r[...] for r in out_refs)
    nxt = load(0)
    for j in range(blocks_per_step):
        ids, vals = nxt                             # (B, 1), (B, W)
        if j + 1 < blocks_per_step:
            nxt = load(j + 1)       # prefetch while this block folds
        vals = map_block(vals, ids, to_domain, prep)
        contrib = block_contrib(vals, ids.reshape(block_rows),
                                num_segments, policy, program,
                                seg_offset=seg_offset)
        carry = policy.update(carry, contrib)
        if interpret:
            # pin the fold boundary: interpret mode runs the unrolled
            # supertile loop as one XLA computation, which may fuse
            # consecutive float folds into a single larger reduction (at
            # S=1 the one-hot dot degenerates to a plain reduce), silently
            # changing the addition order the program fixes.  Mosaic keeps
            # the traced order and has no lowering for the barrier.
            carry = jax.lax.optimization_barrier(carry)
    for r, c in zip(out_refs, carry):
        r[...] = c


def segsum_policy_pallas(values: jnp.ndarray, segment_ids: jnp.ndarray,
                         num_segments: int, *, policy,
                         block_rows: int = 512, seg_offset: int = 0,
                         interpret: bool = False, program=None,
                         blocks_per_step=None, to_domain=None,
                         prep_state=()):
    """values (N, W) already in ``policy``'s domain (``Policy.prepare``
    already ran; W may exceed the raw feature width D — e.g. exact2's
    quantized|residual halves), ids (N,) int32 -> tuple of
    ``policy.carry_len`` carry arrays, not finalized.

    N must be a multiple of block_rows (the callers pad with
    ``OUT_OF_RANGE_LABEL``, which contributes a zero row); this wrapper
    additionally pads the *block count* up to a ``blocks_per_step``
    multiple with whole sentinel blocks — an identity for every policy
    whose ``update`` folds a zero contribution as a no-op (true of all
    registered tiers: f32 ``+0`` and ``two_sum(acc, 0)`` are exact,
    integer ``+0`` is trivial), so the supertile depth never changes the
    result bits.

    With ``to_domain`` (``Policy.map_rows``) and ``prep_state`` (its f32
    scalars, ``Policy.domain_args``), ``values`` are the raw (N, D) rows
    instead, at any N: only the labels pad to whole supertiles, and the
    kernel zeroes every row whose label is the sentinel — dropped rows
    and the unspecified rows past N of the last supertile — before it
    maps each block into the domain.  The supertile depth is then sized
    from the raw width D.

    ``program`` is a planned ``BlockProgram`` (contrib mode);
    ``blocks_per_step=None`` sizes the supertile from the VMEM window
    (``blocks_per_step_for``).  The compiled kernel runs only the dot
    form: Mosaic has no scatter-add lowering, so a ``"lanes"`` program
    is refused unless ``interpret=True``.
    """
    if not interpret and program is not None and program.contrib == "lanes":
        raise ValueError(
            "segsum_policy_pallas: the compiled kernel runs only the "
            "one-hot dot contrib (Mosaic has no scatter-add); plan "
            "contrib='dot', or use backend='blocked' for the lane form")
    n, d = values.shape
    if to_domain is None and n % block_rows:
        raise ValueError(f"segsum_policy_pallas: N={n} must be a multiple "
                         f"of block_rows={block_rows}; pad in the caller")
    nb = -(-n // block_rows)
    if blocks_per_step is None:
        blocks_per_step = blocks_per_step_for(block_rows, d)
    bps = max(1, min(int(blocks_per_step), nb))
    pad = (-nb) % bps * block_rows + (nb * block_rows - n)
    if pad:                         # whole sentinel blocks: fold identity
        if to_domain is None:
            values = jnp.pad(values, ((0, pad), (0, 0)))
        segment_ids = jnp.pad(segment_ids, (0, pad),
                              constant_values=OUT_OF_RANGE_LABEL)
    ids2 = segment_ids.reshape(-1, 1).astype(jnp.int32)
    num_prep = len(prep_state) if to_domain is not None else 0
    kernel = functools.partial(_segsum_policy_kernel,
                               num_segments=num_segments,
                               seg_offset=seg_offset, policy=policy,
                               program=program, block_rows=block_rows,
                               blocks_per_step=bps, interpret=interpret,
                               to_domain=to_domain, num_prep=num_prep)
    # the policy's init is the one source of truth for per-component carry
    # shapes/dtypes (exact2 mixes int32 limbs with f32 residuals, and its
    # carries are narrower than the domain); the zeros are traced away
    carry0 = policy.init(num_segments, carry_width(policy, d, to_domain))
    in_specs = [pl.BlockSpec((bps * block_rows, 1), lambda b: (b, 0)),
                pl.BlockSpec((bps * block_rows, d), lambda b: (b, 0))]
    args = [ids2, values]
    if num_prep:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.stack([jnp.asarray(a, jnp.float32)
                               for a in prep_state]))
    out = pl.pallas_call(
        kernel,
        grid=(ids2.shape[0] // (bps * block_rows),),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec(c.shape, lambda b: (0, 0))
                   for c in carry0],
        out_shape=[jax.ShapeDtypeStruct(c.shape, c.dtype) for c in carry0],
        interpret=interpret,
    )(*args)
    return tuple(out) if isinstance(out, (list, tuple)) else (out,)
