"""The front door: ``repro.reduce(...)`` and ``ReduceSpec``.

One call for every reduction in the repo — segmented or whole-stream,
any registered op of the reduction algebra (``sum`` / ``mean`` /
``weighted_sum`` / ``sumsq`` / ``moments`` / ``poly`` — see
``repro.reduce.algebra``), any accuracy policy, any executor:

    from repro import reduce
    out = reduce(values)                                   # (N, D) -> (D,)
    out = reduce(values, segment_ids=ids, num_segments=8)  # -> (8, D)
    out = reduce(values, segment_ids=ids, num_segments=8,
                 op="mean", policy="exact", backend="pallas")
    out = reduce(values, op="weighted_sum", weights=w, policy="exact2")

The paper's contract is preserved end to end: one in-order result per
variable-length set, a fixed pairing schedule (results depend only on
shapes, never on the executor), bounded accumulator state.

``ReduceSpec`` captures everything static about a reduction (op, policy,
backend, block size) in one frozen, hashable value — build it once, reuse
it across calls and jit boundaries, and the dispatch cache keys on it
directly.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..core import intac
from .algebra import get_op
from .backends import (OUT_OF_RANGE_LABEL, ambient_mesh, default_mesh,
                       get_backend, mask_out_of_range, select_backend,
                       select_local_backend)
from .policy import get_policy
from .program import plan_program


@dataclasses.dataclass(frozen=True)
class ReduceSpec:
    """Static description of a reduction — hashable, so jit-cache-friendly.

    ``backend=None`` means auto-select (shard_map under a multi-device
    mesh, TPU kernel on TPU, scanned blocks elsewhere); ``interpret=None``
    lets the pallas backend decide.  Build one spec, reuse it across calls
    and jit boundaries:

    >>> spec = ReduceSpec(op="mean", policy="exact2", backend="blocked")
    >>> spec.replace(op="sum").op
    'sum'
    >>> spec == ReduceSpec(op="mean", policy="exact2", backend="blocked")
    True
    """

    op: str = "sum"                   # any op in algebra.REDUCE_OPS
    policy: str = "fast"              # any registered policy name
    backend: Optional[str] = None
    block_size: int = 512
    interpret: Optional[bool] = None
    #: static coefficients for coefficient-taking ops (``op="poly"``'s
    #: ascending polynomial); a tuple so the spec stays hashable and the
    #: weights trace as constants under jit
    coeffs: Optional[tuple] = None
    #: gather-stage form of the staged block-program: "auto" lets
    #: ``plan_program``'s cost model pick (lane-parallel scatter for
    #: integer tiers at large label counts — bitwise-invisible by
    #: associativity; the one-hot dot otherwise), "dot"/"lanes" force a
    #: form.  "lanes" on a float tier is a documented rounding-order
    #: change (like the shard_map fast merge), never auto-selected.
    contrib: str = "auto"

    def __post_init__(self):
        op = get_op(self.op)                         # validate eagerly
        if self.coeffs is not None:
            if not op.takes_coeffs:
                raise ValueError(f"op {self.op!r} takes no coeffs")
            object.__setattr__(self, "coeffs",
                               tuple(float(c) for c in self.coeffs))
        if self.contrib not in ("auto", "dot", "lanes"):
            raise ValueError(f"contrib must be 'auto', 'dot', or 'lanes', "
                             f"got {self.contrib!r}")
        get_policy(self.policy)                      # validate eagerly
        if self.backend is not None:
            get_backend(self.backend)

    def replace(self, **kw) -> "ReduceSpec":
        return dataclasses.replace(self, **kw)


class ReduceStatus(NamedTuple):
    """Guard-rail flags for one reduction, returned by
    ``reduce(..., with_status=True)``.

    All fields are scalar jax arrays (jit-friendly; force with ``bool()``/
    ``int()`` only outside traced code):

    * ``nonfinite`` — True iff any *kept* row (in-range segment label)
      carried a NaN/Inf payload.  Sentinel-dropped rows are zeroed before
      any tier sees them, so their payloads can never poison a result —
      and never trip this flag.
    * ``saturated`` — True iff the policy's integer carry wrapped (int32
      limb saturation, procrastinate bin overflow).  Within the eager
      bounds ``reduce`` enforces (``max_terms`` / ``max_blocks``) the
      headroom analysis makes this impossible; it exists as defense in
      depth for direct ``backend.run`` callers and for escalation in
      ``on_overflow="degrade"``.
    * ``degraded`` — True iff ``on_overflow="degrade"`` re-planned the
      reduction (chunked the stream, or escalated to a stronger tier).
    * ``kept_rows`` — int32 count of in-range rows that entered the sum.

    The contract: ``saturated`` is False whenever the finalized value is
    the canonical one, and trips exactly when an int32 carry component
    wrapped (see the boundary tests in ``tests/test_core.py``).
    """

    nonfinite: jnp.ndarray
    saturated: jnp.ndarray
    degraded: jnp.ndarray
    kept_rows: jnp.ndarray


def _status_false() -> ReduceStatus:
    return ReduceStatus(jnp.asarray(False), jnp.asarray(False),
                        jnp.asarray(False), jnp.asarray(0, jnp.int32))


def _maps_per_block(backend, policy) -> bool:
    """Where the domain map runs: True when the block schedule maps each
    block of raw rows into the domain as it folds it (a staged executor,
    and a tier whose map is sized by the stream's max-|value| statistic:
    ``exact``, ``exact2``, ``procrastinate``), so no N x domain-width
    array is built; False when ``_dispatch`` maps the whole stream
    first (``fast``/``compensated``, whose map is a cast, and executors
    that take prepared values)."""
    return backend.staged and policy.needs_max_stat


@functools.partial(jax.jit, static_argnames=("spec", "num_segments",
                                             "segmented", "squeeze_d",
                                             "mesh", "axis_names",
                                             "with_status"))
def _dispatch(values, segment_ids, *, spec: ReduceSpec, num_segments: int,
              segmented: bool, squeeze_d: bool, mesh=None, axis_names=None,
              with_status: bool = False):
    policy = get_policy(spec.policy)
    op_ = get_op(spec.op)
    # values arrive already transformed by the op's ``pre`` (``reduce``
    # ran it before the jit boundary), so ``d`` here is the op-widened
    # stream width (components * raw D) and everything below — domain
    # planning, the kernels, the shard merges — is op-agnostic.
    n, d = values.shape
    # ``reduce`` resolved backend=None before the jit boundary, so specs
    # arriving here are concrete; keep the fallback for direct callers.
    backend = (get_backend(spec.backend) if spec.backend is not None
               else select_backend(policy, traced=True))
    if not backend.supports(policy):
        raise ValueError(f"backend {backend.name!r} does not implement "
                         f"policy {policy.name!r} "
                         f"(capabilities: {sorted(backend.policies)})")
    if policy.max_block_size and spec.block_size > policy.max_block_size:
        raise ValueError(
            f"policy {policy.name!r} admits blocks of at most "
            f"{policy.max_block_size} rows (its integer-headroom bound); "
            f"got block_size={spec.block_size}")
    nb = -(-n // spec.block_size)
    if policy.max_blocks and nb > policy.max_blocks:
        raise ValueError(
            f"policy {policy.name!r} admits at most {policy.max_blocks} "
            f"schedule blocks (its per-block carry headroom), but "
            f"{n} rows at block_size={spec.block_size} need {nb}; "
            f"raise block_size or split the stream")

    status = _status_false() if with_status else None
    if n == 0:
        # empty stream: identity on every backend (the pallas grid cannot
        # be empty, and exact's max-abs pass needs at least one row)
        out = jnp.zeros((num_segments, d), jnp.float32)
    else:
        segment_ids = mask_out_of_range(segment_ids, num_segments)
        kept = (segment_ids >= 0)[:, None]
        if with_status:
            # kept rows only, so a NaN/Inf in a *dropped* row never trips
            # the flag (it provably never enters any tier either); two
            # reductions that write no array
            status = status._replace(
                nonfinite=jnp.any(kept & ~jnp.isfinite(values)),
                kept_rows=jnp.sum(kept.astype(jnp.int32)))
        run_kw = ({"mesh": mesh, "axis_names": axis_names}
                  if backend.distributed else {})
        if backend.staged:
            # plan the staged block-program once, above the executor: the
            # contrib mode (one-hot dot vs lane-parallel scatter) and the
            # stage cost hints are a (policy, shape) decision, not a
            # backend one, bar whether the executor that runs the
            # blocks (each shard's local one, under shard_map) can
            # scatter at all
            executor = (select_local_backend(policy)
                        if backend.distributed else backend)
            run_kw["program"] = plan_program(
                policy, num_segments=num_segments,
                domain_width=policy.domain_width(d),
                block_size=spec.block_size, contrib=spec.contrib,
                op=spec.op, plans_lanes=executor.plans_lanes)
        if _maps_per_block(backend, policy):
            # compute only the global statistic here — one reduction of
            # the kept rows' |value| that writes no array — and hand the
            # *raw* rows to the block schedule, which zeroes each block's
            # dropped rows and maps it into the domain as it folds it
            # (in VMEM, on pallas; per shard, under shard_map).  Bit-
            # identical to whole-stream prepare (the map is row-local),
            # without the N x domain-width array in HBM.  Dropped rows
            # stay out of the statistic: a huge sentinel-labeled row
            # must not poison the scale for the kept rows.
            m = jnp.max(jnp.where(kept, jnp.abs(values.astype(jnp.float32)),
                                  jnp.float32(0)))
            ctx = policy.prepare_ctx(m, n)
            carry = backend.run(values, segment_ids, num_segments,
                                policy=policy, block_size=spec.block_size,
                                interpret=spec.interpret,
                                to_domain=policy.map_rows,
                                prep_state=policy.domain_args(ctx),
                                **run_kw)
        else:
            # zero dropped rows' payloads: the one-hot schedule ignores
            # them, but a NaN there would still poison the dot, and an
            # integer tier's prepare sizes its scale from max |value|
            values = jnp.where(kept, values, jnp.zeros((), values.dtype))
            domain, ctx = policy.prepare(values, n)
            carry = backend.run(domain, segment_ids, num_segments,
                                policy=policy, block_size=spec.block_size,
                                interpret=spec.interpret, **run_kw)
        if with_status:
            sat = policy.carry_status(carry)
            if sat is not None:
                status = status._replace(saturated=sat)
        out = policy.finalize(carry, ctx)            # (S, D) f32

    cnt = None
    if op_.needs_count:
        if n > 0:
            # Counts: exact integers, so a single scatter-add is bitwise-
            # identical to running the block schedule again at a fraction
            # of the cost, and backend-independent by construction.
            # Accumulate in int32 — an f32 count buffer silently saturates
            # at 2^24 (adding 1.0 to 16777216.0 is a no-op) — and cast
            # once for the divide.  segment_ids is already sentinel-
            # masked; park dropped rows on a scratch row.
            ids_safe = jnp.where(segment_ids >= 0, segment_ids,
                                 num_segments)
            cnt = jnp.zeros((num_segments + 1, 1), jnp.int32) \
                .at[ids_safe].add(1, mode="drop")[:num_segments]   # (S, 1)
        else:
            cnt = jnp.zeros((num_segments, 1), jnp.int32)
    out = op_.post(out, cnt)

    if not segmented:
        out = out[0]
    if squeeze_d:
        out = out[..., 0]
    return (out, status) if with_status else out


def _chunk_limit(policy, block_size: int) -> int:
    """Largest block-aligned row count that satisfies every eager headroom
    bound of ``policy`` at this ``block_size``."""
    limit = policy.max_terms
    if policy.max_blocks:
        cap = policy.max_blocks * block_size
        limit = cap if limit is None else min(limit, cap)
    return max(block_size, (limit // block_size) * block_size)


def _reduce_degrade(values, segment_ids, *, spec: ReduceSpec,
                    num_segments: int, segmented: bool, squeeze_d: bool,
                    mesh, axis_names):
    """The ``on_overflow="degrade"`` planner (eager only).

    Streams beyond the policy's headroom bounds are split into bound-sized
    chunks in stream order; chunk sums are folded with a compensated
    (two_sum) accumulator, so the degraded result stays within ulp-level
    error of the unchunked one.  A tripped saturation flag escalates the
    whole reduction to ``policy.escalation`` (the next-stronger tier).
    Returns ``(out, ReduceStatus)``.
    """
    policy = get_policy(spec.policy)
    op_ = get_op(spec.op)        # values already carry the op's ``pre``
    n, d = values.shape
    nb = -(-n // spec.block_size)
    over = bool((policy.max_terms is not None and n > policy.max_terms)
                or (policy.max_blocks and nb > policy.max_blocks))
    sum_spec = spec.replace(op="sum")
    run = functools.partial(_dispatch, spec=sum_spec,
                            num_segments=num_segments, segmented=True,
                            squeeze_d=False, mesh=mesh,
                            axis_names=axis_names, with_status=True)
    degraded = over
    if over:
        chunk = _chunk_limit(policy, spec.block_size)
        acc = jnp.zeros((num_segments, d), jnp.float32)
        comp = jnp.zeros_like(acc)
        status = _status_false()
        # detlint: ok[DET002] eager-only degrade fold: runs outside jit
        # at dispatch boundaries, XLA never sees the cross-chunk chain
        for i in range(0, n, chunk):
            part, st = run(values[i:i + chunk], segment_ids[i:i + chunk])
            acc, err = intac.two_sum(acc, part)
            comp = comp + err
            status = ReduceStatus(
                jnp.logical_or(status.nonfinite, st.nonfinite),
                jnp.logical_or(status.saturated, st.saturated),
                status.degraded, status.kept_rows + st.kept_rows)
        out = acc + comp
    else:
        out, status = run(values, segment_ids)

    if bool(status.saturated):
        if policy.escalation is None:
            raise OverflowError(
                f"policy {policy.name!r} saturated an int32 carry and has "
                f"no stronger tier to escalate to; split the stream")
        out, status = _reduce_degrade(
            values, segment_ids, spec=spec.replace(policy=policy.escalation),
            num_segments=num_segments, segmented=segmented,
            squeeze_d=squeeze_d, mesh=mesh, axis_names=axis_names)
        return out, status._replace(degraded=jnp.asarray(True))
    cnt = None
    if op_.needs_count:
        if n > 0:
            # same exact-integer count scheme as _dispatch, over the full
            # stream (bitwise independent of the chunking)
            mids = mask_out_of_range(segment_ids, num_segments)
            ids_safe = jnp.where(mids >= 0, mids, num_segments)
            cnt = jnp.zeros((num_segments + 1, 1), jnp.int32) \
                .at[ids_safe].add(1, mode="drop")[:num_segments]
        else:
            cnt = jnp.zeros((num_segments, 1), jnp.int32)
    out = op_.post(out, cnt)

    status = status._replace(
        degraded=jnp.logical_or(status.degraded, jnp.asarray(degraded)))
    if not segmented:
        out = out[0]
    if squeeze_d:
        out = out[..., 0]
    return out, status


def reduce(values, *, segment_ids=None, num_segments: Optional[int] = None,
           op: str = "sum", policy: str = "fast",
           backend: Optional[str] = None, block_size: int = 512,
           contrib: str = "auto",
           interpret: Optional[bool] = None,
           weights=None, coeffs=None,
           mesh=None, axis_names=None,
           spec: Optional[ReduceSpec] = None,
           with_status: bool = False,
           on_overflow: str = "raise") -> jnp.ndarray:
    """Reduce a value stream, optionally partitioned into labeled sets.

    Args:
      values: (N,) or (N, D) array; any float dtype (accumulation is f32
        or exact int32 per ``policy``; the result is f32).
      segment_ids: optional (N,) int labels.  Rows labeled outside
        [0, num_segments) — including the repo-wide padding sentinel
        ``OUT_OF_RANGE_LABEL`` — are dropped from sums *and* counts.
      num_segments: static label-space size; required with ``segment_ids``.
      op: any op of the reduction algebra (``repro.reduce.algebra``) —
        "sum", "mean" (counts only in-range rows), "weighted_sum"
        (requires ``weights``), "sumsq", "moments" (per-segment
        (mean, var) via one double-width pass; adds a leading size-2
        statistic axis to the result), or "poly" (requires ``coeffs``;
        time-index polynomial weighting).  The op's row-local ``pre``
        runs before dispatch, so every accuracy tier folds the
        transformed rows in its own domain and every backend/shard/
        degrade guarantee applies unchanged.
      policy: accuracy tier — "fast", "compensated", "exact", "exact2",
        or "procrastinate" (see ``repro.reduce.policy`` for the ladder).
      backend: executor — "ref", "blocked", "pallas", "shard_map", or
        None to auto-select (shard_map under a multi-device mesh, the
        TPU kernel on TPU, blocked elsewhere).
      block_size: rows per schedule block (the paper's cycle granularity).
      contrib: gather-stage form for the staged block-program — "auto"
        (default: the planner's cost model, which picks the lane-parallel
        scatter for integer-domain tiers at large label counts, a
        bitwise-invisible swap), "dot" (always the one-hot matmul), or
        "lanes" (force the scatter form; for float tiers this is a
        documented rounding-order change).  See ``repro.reduce.program``.
      interpret: force/forbid pallas interpret mode (None = auto).
      weights: (N,) or (N, 1) per-row weights for weight-taking ops
        (``op="weighted_sum"``).  Applied row-locally before dispatch;
        sentinel-labeled rows drop out exactly as their values do.
      coeffs: ascending polynomial coefficients for coefficient-taking
        ops (``op="poly"``); static — becomes ``ReduceSpec.coeffs``.
      mesh: the device mesh for a distributed backend; None uses the
        ambient ``with jax.set_mesh(mesh):`` context, else one flat axis
        over every visible device.  Rejected for single-device backends.
        Note the ambient mesh only steers *auto-selection* for calls on
        concrete arrays — on traced values pass ``mesh=`` (or
        ``backend="shard_map"``) explicitly; see ``select_backend``.
      axis_names: mesh axes to shard the stream over (default: all of
        the mesh's axes); only meaningful with a distributed backend.
      spec: a prebuilt ``ReduceSpec``; overrides the per-call knobs above
        (``mesh``/``axis_names`` are environment, not spec, and still
        apply).
      with_status: also return a ``ReduceStatus`` (NaN/Inf in kept rows,
        int32 carry saturation, degradation, kept-row count).  Static, so
        ``False`` (the default) costs the hot path nothing.
      on_overflow: "raise" (default) rejects streams beyond the policy's
        integer-headroom bounds with an eager ``ValueError``; "degrade"
        re-plans instead — over-bound streams are chunked and folded with
        a compensated accumulator, and a saturated carry escalates to the
        policy's next-stronger tier (``Policy.escalation``).  Degradation
        is eager-only (it inspects runtime flags), and is reported via
        ``ReduceStatus.degraded``.

    Returns:
      f32 array: (num_segments, D) / (num_segments,) when segmented,
      (D,) / scalar otherwise.  With ``with_status=True``, a tuple
      ``(result, ReduceStatus)``.

    >>> import jax.numpy as jnp
    >>> from repro.reduce import reduce
    >>> float(reduce(jnp.arange(4.0)))                       # whole stream
    6.0
    >>> out = reduce(jnp.arange(6.0),                        # three sets
    ...              segment_ids=jnp.asarray([0, 0, 1, 1, 1, 2]),
    ...              num_segments=3)
    >>> [float(v) for v in out]
    [1.0, 9.0, 5.0]
    >>> float(reduce(jnp.arange(6.0), policy="exact2",       # multi-device
    ...              backend="shard_map"))
    15.0
    >>> out, status = reduce(jnp.arange(4.0), policy="exact2",
    ...                      with_status=True)
    >>> (float(out), bool(status.nonfinite), bool(status.saturated),
    ...  int(status.kept_rows))
    (6.0, False, False, 4)
    >>> float(reduce(jnp.asarray([1.0, 2.0, 3.0]), op="weighted_sum",
    ...              weights=jnp.asarray([1.0, 0.5, 2.0]),
    ...              policy="exact2"))                    # 1 + 1 + 6
    8.0
    >>> mv = reduce(jnp.asarray([1.0, 3.0]), op="moments")  # (mean, var)
    >>> [float(v) for v in mv]
    [2.0, 1.0]
    >>> float(reduce(jnp.ones(4), op="poly", coeffs=(0.0, 1.0)))  # sum i
    6.0
    """
    with TraceAnnotation("repro.reduce") as span:
        if on_overflow not in ("raise", "degrade"):
            raise ValueError(f"on_overflow must be 'raise' or 'degrade', "
                             f"got {on_overflow!r}")
        if spec is None:
            spec = ReduceSpec(op=op, policy=policy, backend=backend,
                              block_size=block_size, contrib=contrib,
                              interpret=interpret, coeffs=coeffs)
        elif coeffs is not None and spec.coeffs is None:
            spec = spec.replace(coeffs=coeffs)
        # Resolve auto-selection and the mesh *before* the jit boundary: the
        # dispatch cache keys on the concrete (spec, mesh, axis_names), so an
        # activated-then-deactivated ambient mesh can never serve a stale
        # cached executor choice.
        pol = get_policy(spec.policy)
        auto = spec.backend is None
        traced = any(isinstance(x, jax.core.Tracer)
                     for x in (values, segment_ids, weights))
        bk = (select_backend(pol, mesh=mesh, traced=traced) if auto
              else get_backend(spec.backend))
        if spec.backend != bk.name:
            spec = spec.replace(backend=bk.name)
        if bk.distributed:
            if mesh is None:
                mesh = ambient_mesh() or default_mesh()
            if axis_names is not None:
                axis_names = tuple(axis_names)
        elif auto:
            # auto-selection declined the mesh (single device, or unsupported
            # policy): run the local backend.  A 1-device mesh dropping to the
            # local path is the intended "scale if useful" contract, but
            # explicit axis_names state distributed intent — refuse rather
            # than silently reduce on one device.
            if axis_names is not None:
                raise ValueError(
                    "axis_names was given but backend auto-selection chose a "
                    "single-device executor (no multi-device mesh in reach); "
                    "pass backend='shard_map' and/or a multi-device mesh")
            mesh = None
        elif mesh is not None or axis_names is not None:
            raise ValueError(f"backend {bk.name!r} is single-device; mesh/"
                             f"axis_names only apply to distributed backends "
                             f"(e.g. 'shard_map')")
        values = jnp.asarray(values)
        if values.ndim not in (1, 2):
            raise ValueError(f"values must be (N,) or (N, D), "
                             f"got shape {values.shape}")
        squeeze_d = values.ndim == 1
        if squeeze_d:
            values = values[:, None]
        width = values.shape[1]

        # The algebra's one interception point: run the op's row-local
        # ``pre`` here, above the jit boundary and above every executor, so
        # the dispatch/degrade/shard machinery below only ever sees a plain
        # (possibly wider) sum of the transformed rows.
        op_ = get_op(spec.op)
        if op_.requires_weights and weights is None:
            raise ValueError(f"op {spec.op!r} requires per-row weights=")
        if weights is not None and not op_.takes_weights:
            raise ValueError(f"op {spec.op!r} takes no weights")
        if op_.requires_coeffs and spec.coeffs is None:
            raise ValueError(f"op {spec.op!r} requires coeffs=")
        if weights is not None:
            weights = jnp.asarray(weights)
            if weights.ndim == 2 and weights.shape[-1] == 1:
                weights = weights[:, 0]
            if weights.ndim != 1 or weights.shape[0] != values.shape[0]:
                raise ValueError(
                    f"weights must be (N,) or (N, 1) matching values' "
                    f"N={values.shape[0]}, got shape {weights.shape}")
        with TraceAnnotation("repro.reduce.pre"):
            values = op_.pre(values, weights=weights, coeffs=spec.coeffs)

        segmented = segment_ids is not None
        if segmented:
            if num_segments is None:
                raise ValueError("num_segments (static int) is required with "
                                 "segment_ids")
            segment_ids = jnp.asarray(segment_ids)
        else:
            if num_segments is not None:
                raise ValueError("num_segments was given without segment_ids; "
                                 "pass both for a segmented reduction")
            num_segments = 1
            segment_ids = jnp.zeros((values.shape[0],), jnp.int32)

        if on_overflow == "degrade" and isinstance(values, jax.core.Tracer):
            raise ValueError(
                "on_overflow='degrade' re-plans the reduction from runtime "
                "flags and is eager-only; call reduce outside jit, or keep "
                "on_overflow='raise'")
        with TraceAnnotation("repro.reduce.dispatch") as dspan:
            if dspan.is_enabled():
                # where the domain map runs: per schedule block inside
                # the executor, or over the whole stream before it
                dspan.set_metadata(
                    domain="block" if _maps_per_block(bk, pol) else "stream")
            if on_overflow == "degrade":
                out = _reduce_degrade(
                    values, segment_ids, spec=spec,
                    num_segments=int(num_segments), segmented=segmented,
                    squeeze_d=squeeze_d, mesh=mesh, axis_names=axis_names)
                out = out if with_status else out[0]
            else:
                out = _dispatch(values, segment_ids, spec=spec,
                                num_segments=int(num_segments),
                                segmented=segmented, squeeze_d=squeeze_d,
                                mesh=mesh, axis_names=axis_names,
                                with_status=with_status)
        if span.is_enabled():
            span.set_metadata(rows=values.shape[0], width=width,
                              segments=int(num_segments), policy=spec.policy,
                              op=spec.op)
        return out
