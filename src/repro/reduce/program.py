"""The staged block-program: what a backend actually executes.

JugglePAC's thesis is that a fixed schedule plus *overlap* keeps the
adder busy; this module is where the repo's schedule stops being an
implicit convention buried in each backend and becomes a declared,
pipelineable program.  A ``BlockProgram`` names, per schedule block, the
two stages every executor runs:

  * **contrib** (the gather stage, memory-bound) — map a (B, W) domain
    tile + its (B,) labels into the (S, W) per-block contribution, in one
    of two forms the policy declares:

      - ``"dot"``   — the one-hot matmul ``onehot(ids).T @ vals``
        (``Policy.contrib``): MXU-friendly, but its flops grow with
        B*S*W, so at large label counts it drowns in work the scatter
        form skips;
      - ``"lanes"`` — PhasedAccu-style per-lane scatter-add partial sums
        folded in lane order (``Policy.contrib_lanes``): O(B*W) adds.
        **Bitwise equal to the dot for integer domains** (associative
        int32 addition — same multiset of adds per segment), a different
        rounding order for float domains, so float tiers only run it on
        explicit opt-in.

  * **update** (the carry stage, compute-bound) — fold the contribution
    into the policy carry (``Policy.update``), strictly in stream order.

Because the stages are declared — with per-block byte/flop cost hints
from ``Policy.stage_costs`` — executors know what to overlap: the pallas
kernel prefetches block i+1's tiles while ``update`` folds block i
(see ``kernels/jugglepac_segsum.py``), and ``plan_program`` picks the
contrib form from the cost model instead of hard-coding the matmul.

``plan_program(policy, ...)`` is the one planner: every backend executes
whatever program it returns, so the contrib-mode decision — like the
block schedule itself — is made once, above the executor.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from .policy import LANES_DEFAULT, Policy, get_policy

#: contrib-mode crossover: below this label count the one-hot dot wins
#: (it is one dense MXU op); at and above it the dot's B*S*W flops cost
#: more than the scatter's B*W adds even off-accelerator.  Measured on
#: the int32 tiers (W=128, B=512) the crossover sits near S~16-24; 32 is
#: the conservative side of it.
LANE_MIN_SEGMENTS = 32

#: fewest label rows the one-hot dot is built with (one f32 sublane tile)
MIN_LABEL_ROWS = 8


@dataclasses.dataclass(frozen=True)
class BlockStage:
    """One declared stage of the per-block program.

    ``bound`` is the stage's declared roofline regime ("memory" for the
    gather/contrib stage, "compute" for the carry update); ``bytes`` and
    ``flops`` are the per-block cost hints from ``Policy.stage_costs``.
    """

    name: str
    bound: str
    bytes: float
    flops: float


@dataclasses.dataclass(frozen=True)
class BlockProgram:
    """A planned, staged execution of the block schedule — frozen and
    hashable, so it rides through jit static args like ``ReduceSpec``.

    ``contrib`` is the resolved gather form ("dot" | "lanes"); ``stages``
    carries the declared cost hints for this (policy, shape) pair.  The
    program never changes *what* is computed for integer-domain policies
    (both contrib forms produce bitwise-identical contributions there) —
    it changes how the same schedule maps onto the hardware.
    """

    policy: str
    contrib: str                      # "dot" | "lanes"
    lanes: int
    block_size: int
    num_segments: int
    domain_width: int
    stages: Tuple[BlockStage, ...]
    #: the algebra op this plan serves ("sum" unless the front door says
    #: otherwise).  The op's cost is already folded into the stage hints
    #: — its ``pre`` widens ``domain_width`` by ``components`` (moments'
    #: [v | v*v] planes double every byte/flop figure below), which is
    #: exactly how the kernel's supertile sizing sees it too — so the
    #: field is the planner's provenance record for roofline/debug
    #: output, never a behavioral switch.
    op: str = "sum"

    def stage(self, name: str) -> BlockStage:
        for s in self.stages:
            if s.name == name:
                return s
        raise KeyError(f"block program has no stage {name!r}; "
                       f"stages: {[s.name for s in self.stages]}")


def plan_program(policy, *, num_segments: int, domain_width: int,
                 block_size: int = 512, contrib: str = "auto",
                 lanes: int = LANES_DEFAULT, op: str = "sum",
                 plans_lanes: bool = True) -> BlockProgram:
    """Plan the staged block-program for one (policy, shape) pair.

    ``contrib="auto"`` applies the cost model: integer-domain policies
    switch to the lane-parallel scatter form once ``num_segments``
    crosses ``LANE_MIN_SEGMENTS`` (where the one-hot dot's B*S*W flops
    make it the slower *and* still memory-bound stage) — a pure
    performance decision, bitwise-invisible by associativity — unless
    the executor cannot run the scatter (``plans_lanes=False``: the
    pallas kernel, whose compiled form has no scatter-add).  Float
    tiers always plan the dot under "auto"; ``contrib="lanes"`` forces
    the lane form anywhere (for float domains that is a documented
    rounding-order change, exactly like the shard_map fast merge).

    >>> prog = plan_program(get_policy("exact2"), num_segments=64,
    ...                     domain_width=128, block_size=512)
    >>> prog.contrib, prog.stage("contrib").bound
    ('lanes', 'memory')
    >>> plan_program(get_policy("fast"), num_segments=64,
    ...              domain_width=16).contrib
    'dot'
    """
    if isinstance(policy, str):
        policy = get_policy(policy)
    if contrib not in ("auto", "dot", "lanes"):
        raise ValueError(f"contrib must be 'auto', 'dot', or 'lanes', "
                         f"got {contrib!r}")
    if contrib == "auto":
        integer_domain = jnp.issubdtype(policy.acc_dtype, jnp.integer)
        contrib = ("lanes" if plans_lanes and integer_domain
                   and num_segments >= LANE_MIN_SEGMENTS else "dot")
    costs = policy.stage_costs(block_size, domain_width, num_segments,
                               contrib=contrib)
    stages = tuple(BlockStage(name=name, bound=c["bound"],
                              bytes=c["bytes"], flops=c["flops"])
                   for name, c in costs.items())
    return BlockProgram(policy=policy.name, contrib=contrib,
                        lanes=int(lanes), block_size=int(block_size),
                        num_segments=int(num_segments),
                        domain_width=int(domain_width), stages=stages,
                        op=str(op))


def block_contrib(vals, ids, num_segments: int, policy: Policy,
                  program: BlockProgram = None, *, seg_offset: int = 0):
    """Execute the program's gather stage for one (B, W) block.

    The one shared implementation behind ref, blocked, and the pallas
    kernel body: with no program (or ``contrib="dot"``) it builds the
    (B, S) boolean one-hot exactly the way the kernel does — ids as a
    (B, 1) column against a (1, S) label row — and delegates the dot
    lowering to ``policy.contrib``; with ``contrib="lanes"`` it runs the
    policy's lane-parallel scatter form instead.  Keeping both forms
    here, written once, is what makes the cross-backend bitwise contract
    hold per (policy, program) rather than per backend.
    """
    if program is not None and program.contrib == "lanes":
        return policy.contrib_lanes(ids, vals, num_segments,
                                    seg_offset=seg_offset,
                                    lanes=program.lanes)
    # broadcasted_iota, not arange: this exact line also runs inside the
    # pallas kernel body, where 1-D iota does not lower on TPU.  The label
    # row is at least one sublane tile (MIN_LABEL_ROWS) tall: a one-label
    # one-hot degenerates the dot into a vector product, which XLA's CPU
    # backend emits with context-dependent summation order; the extra
    # labels' rows are sliced away
    rows = max(num_segments, MIN_LABEL_ROWS)
    labels = jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1) + seg_offset
    out = policy.contrib(ids[:, None] == labels, vals)
    return out if rows == num_segments else out[:num_segments]
