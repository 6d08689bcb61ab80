"""Continuous-batching serving engine: paged KV admission, chunked
prefill, per-slot decode, in-order results.

This is the paper's scenario run at serving granularity.  JugglePAC
juggles back-to-back variable-length datasets through one pipelined
accumulator and emits per-set results in input order; the engine juggles
back-to-back variable-length *requests* through a fixed array of decode
slots and delivers per-request results in submission order:

  * requests  = the paper's variable-length sets;
  * decode slots = the pipeline stages (``max_batch`` of them, never
    reshaped — admission swaps a retired request's slot to the next
    arrival mid-stream, the batch keeps stepping);
  * reorder buffer = the in-order output contract (``Scheduler``);
  * ``PagedKVPool`` = the bounded intermediate storage (admission is
    gated on free KV pages, the "few PIS registers" rule).

Prefill streams in ``prefill_chunk``-token pieces interleaved with decode
steps (chunked prefill), so one long prompt cannot stall the in-flight
batch.  Every chunk is padded to the same width and every decode step runs
at the full ``max_batch`` width with idle slots masked, so the engine
compiles exactly two model programs — and a request's logits are bitwise
independent of batch composition (row-parallel math at fixed shapes),
which is what makes the sequential one-at-a-time oracle an *exact* spec
for the batched engine under greedy decoding.

Per-request accuracy plumbing goes through ``repro.reduce``:

  * sampling keys derive from (engine seed, request id or ``Request.seed``,
    step) — never from a shared stream split — so sampled tokens are
    reproducible under any batch composition;
  * per-request ``mean_logprob`` is one segmented mean over the flat
    (step x slot) logprob stream with the ``logprob_policy`` knob —
    ``compensated`` by default; ``exact2`` makes the mean *bitwise*
    invariant to batch composition (serving replicas agree to the last
    bit, the property pinned by tests/test_serve.py).

A configuration that holds one chip's share of the experts
(``MoECfg.held``) runs its MoE layers on the held-expert path
(``moe_impl="held"``): only the held experts compute, and only for the
tokens of slots that decode (the ``active`` mask) or for a chunk's real
prompt tokens, so idle slots and chunk padding route nothing.  Its
programs also return the pairs routed to each held expert in each MoE
layer, read in the same ``device_get`` as the sampled tokens (no
transfer of its own) and handed to ``Engine.on_routing`` when it is set.
Every other configuration runs every expert on every token
(``moe_impl="dense"``) and its programs return nothing extra.

Cache updates (docs/serving.md "The cache-update contract"): the
attention caches take only the rows of the tokens the mask keeps — an
idle slot's row and a chunk's padding go to an index past the cache and
enter nothing — and each slot's length grows by its kept tokens, so the
decode returns them as they come out.  Only recurrent
states (Mamba, mLSTM, sLSTM), which have no row to drop, are kept for
idle slots by a select.  The decode takes the caches donated (updated
in place) for every configuration; the prefill chunk does so on the
held-expert path only.

The old all-at-once API survives as a thin wrapper: ``generate()``
enqueues every request at time zero and drains the loop.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro import reduce as _reduce
from repro.models import forward, init_caches, pad_caches_to
from repro.models.attention import KVCache, MLACache
from repro.models.config import ModelConfig

from .kv_pool import PagedKVPool
from .scheduler import Scheduler, TrackedRequest


@dataclasses.dataclass
class Request:
    prompt: Sequence[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: Optional[int] = None
    #: optional per-request sampling seed: when set, sampled tokens depend
    #: only on (engine seed, this seed, step) — stable even if the request
    #: is resubmitted under a different request id
    seed: Optional[int] = None


@dataclasses.dataclass
class Result:
    tokens: List[int]
    prompt_len: int
    mean_logprob: Optional[float] = None
    rid: int = -1
    finish_reason: Optional[str] = None
    latency_s: float = 0.0


class Engine:
    """Continuous-batching engine over ``Scheduler`` + ``PagedKVPool``.

    ``max_batch`` decode slots share one pre-allocated cache of
    ``max_len`` context each; ``num_pages`` x ``page_size`` tokens of KV
    pool gate admission (default: exactly enough for every slot at full
    context, so admission is slot-bound; shrink it to exercise queueing).
    """

    def __init__(self, cfg: ModelConfig, params, *, max_len: int = 512,
                 seed: int = 0, max_batch: int = 8, page_size: int = 16,
                 num_pages: Optional[int] = None,
                 prefill_chunk: int = 32,
                 logprob_policy: str = "compensated"):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.max_batch = max_batch
        self.prefill_chunk = max(1, int(prefill_chunk))
        self.logprob_policy = logprob_policy
        _reduce.get_policy(logprob_policy)        # fail fast on a typo
        self._base_key = jax.random.PRNGKey(seed)
        pool_pages = num_pages if num_pages is not None else \
            max_batch * PagedKVPool(1, page_size).pages_for(max_len)
        self.pool = PagedKVPool(pool_pages, page_size)
        self.scheduler = Scheduler(max_batch, self.pool)
        self._caches = init_caches(cfg, max_batch, max_len)
        # chunked prefill streams through the attention extend path; SSM
        # states need sequential prefill and ring (SWA) caches must not
        # see padded chunk writes — those archs prefill whole-prompt.
        self._extend_ok = (all(sp.kind == "attn" for sp in cfg.period)
                           and cfg.window is None)
        self._clock = 0
        self._rid_base = 0
        self._lp_vals: List[np.ndarray] = []
        self._lp_ids: List[np.ndarray] = []

        held = cfg.moe is not None and cfg.moe.is_share
        impl = "held" if held else "dense"
        #: called as ``on_routing(engine step, "prefill" | "decode",
        #: counts)`` with each program's (MoE layers, held experts) count
        #: of routed pairs as it is read (held-expert path only)
        self.on_routing: Optional[Callable] = None
        self._pending: List[tuple] = []       # counts not yet read
        self._span_routed: List[np.ndarray] = []

        # Both programs return (logits, caches, routed); ``routed`` is the
        # held-expert path's count, else None (no output).
        def _decode_fn(params, tok, caches, pos, active):
            logits, new_caches, _, routed = forward(
                params, cfg, tokens=tok, mode="decode", caches=caches,
                position_offset=pos, moe_impl=impl,
                token_mask=active[:, None])
            # idle / mid-prefill slots wrote no attention row (the mask);
            # a recurrent state has no row to drop, so keep theirs
            def keep(new, old):
                sel = active.reshape((1, -1) + (1,) * (new.ndim - 2))
                return jnp.where(sel, new, old)
            new_caches = [
                n if isinstance(n["core"], (KVCache, MLACache))
                else jax.tree.map(keep, n, o)
                for n, o in zip(new_caches, caches, strict=True)]
            return logits, new_caches, routed

        def _with_length(caches, value):
            out = []
            for c in caches:
                core = c["core"]
                if hasattr(core, "length"):
                    core = core._replace(
                        length=jnp.full_like(core.length, value))
                out.append({**c, "core": core})
            return out

        def _prefill_chunk_fn(params, caches, slot, toks, start, n_valid):
            # one prompt chunk for one slot: slice the slot's cache view,
            # extend it with the chunk's real tokens (the padding neither
            # writes a row nor routes, so the length comes out
            # start + n_valid), splice it back
            sub = jax.tree.map(
                lambda l: jax.lax.dynamic_slice_in_dim(l, slot, 1, axis=1),
                caches)
            sub = _with_length(sub, start)
            mask = (jnp.arange(toks.shape[1]) < n_valid)[None, :]
            logits, new_sub, _, routed = forward(
                params, cfg, tokens=toks, mode="decode", caches=sub,
                moe_impl=impl, position_offset=start, token_mask=mask)
            caches = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one, slot, axis=1),
                caches, new_sub)
            last = jax.lax.dynamic_slice_in_dim(logits, n_valid - 1, 1,
                                                axis=1)
            return last, caches, routed

        def _classic_prefill_fn(params, caches, slot, toks):
            # whole-prompt fallback (SSM / sliding-window archs): standard
            # prefill at B=1, pad to max_len, splice into the slot
            logits, new_sub, _, _ = forward(params, cfg, tokens=toks,
                                            mode="prefill", moe_impl=impl)
            new_sub = pad_caches_to(cfg, new_sub, self.max_len)
            caches = jax.tree.map(
                lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                    full, one, slot, axis=1),
                caches, new_sub)
            return logits[:, -1:], caches

        def _sample_fn(key, logits, custom, idv, steps, temps):
            # per-request PRNG: (engine seed, request id | Request.seed,
            # step) — batchmates and finish order cannot perturb a
            # request's sample stream
            def mk(c, i, s):
                return jax.random.fold_in(
                    jax.random.fold_in(jax.random.fold_in(key, c), i), s)
            keys = jax.vmap(mk)(custom, idv, steps)
            lg = logits[:, -1, :cfg.vocab]
            greedy = jnp.argmax(lg, axis=-1)
            scaled = lg / jnp.maximum(temps[:, None], 1e-6)
            sampled = jax.vmap(jax.random.categorical)(keys, scaled)
            tok = jnp.where(temps > 0, sampled, greedy)
            logp = jax.nn.log_softmax(lg, axis=-1)
            lp = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
            return tok.astype(jnp.int32), lp.astype(jnp.float32)

        # The decode updates the caches in place (donated): the chip
        # holds one copy of them between programs, not one per program
        # in flight.  The prefill chunk does so on the held-expert path;
        # the dense configurations' chunk still copies (ROADMAP Speed 3).
        self._decode = jax.jit(_decode_fn, donate_argnums=(2,))
        self._prefill_chunk = jax.jit(_prefill_chunk_fn,
                                      donate_argnums=(1,) if held else ())
        self._classic_prefill = jax.jit(_classic_prefill_fn)
        self._sample = jax.jit(_sample_fn)

    # -- intake ------------------------------------------------------------

    def submit(self, request: Request, *, arrival: float = 0.0) -> int:
        """Enqueue one request; ``arrival`` is in engine steps relative to
        the start of the next :meth:`run`.  Returns the request id, which
        is also its delivery position."""
        plen = len(request.prompt)
        need = min(plen + max(request.max_new_tokens, 1), self.max_len)
        return self.scheduler.submit(request, arrival=arrival,
                                     need_tokens=need)

    def cancel(self, rid: int) -> bool:
        """Kill a request wherever it is (queued, prefilling, or
        mid-decode).  Its KV pages and slot are released immediately;
        other requests' outputs are untouched (per-slot isolation).  The
        reorder buffer still delivers a ``cancelled`` result in order."""
        tr = self.scheduler.tracked(rid)
        if tr.state == "done":
            return False
        if not tr.out:
            tr.out = list(tr.request.prompt)
        tr.finish_reason = "cancelled"
        self.scheduler.finish(tr, self._result_of(tr), reason="cancelled")
        return True

    # -- the continuous loop ----------------------------------------------

    def run(self, *, on_step: Optional[Callable] = None) -> List[Result]:
        """Drain every submitted request; returns results in submission
        order.  ``on_step(engine, step)`` fires after each engine step
        (fault injection, probes).

        Each step is a ``repro.engine.step`` profiler span (stats
        ``prefill_chunks``, ``decode_slots``) holding its phases'
        spans, ``repro.engine.admit``, ``.prefill`` and ``.decode``;
        ``repro.engine.sync`` marks each wait for sampled tokens, and
        ``repro.engine.first_token`` each request's first token (stats
        ``queue_ms``, ``prefill_ms``, ``chunks``).  On the held-expert
        path ``.prefill`` and ``.decode`` also carry ``routed_pairs`` and
        ``experts_hit`` over the routing counts read inside them.  See
        docs/serving.md "Measuring it"."""
        sched = self.scheduler
        self._clock = 0
        self._rid_base = sched._next_deliver
        self._lp_vals, self._lp_ids = [], []
        delivered: List[Result] = []
        while sched.has_work():
            with TraceAnnotation("repro.engine.step") as step:
                with TraceAnnotation("repro.engine.admit"):
                    sched.advance(self._clock)
                    admitted = sched.admit()
                with TraceAnnotation("repro.engine.prefill") as span:
                    chunks = self._prefill_work()
                    self._routing_stats(span)
                with TraceAnnotation("repro.engine.decode") as span:
                    slots = self._decode_work()
                    self._routing_stats(span)
                delivered.extend(sched.pop_ready())
                if step.is_enabled():
                    step.set_metadata(prefill_chunks=chunks,
                                      decode_slots=slots)
            progressed = bool(admitted or chunks or slots)
            if on_step is not None:
                on_step(self, self._clock)
                delivered.extend(sched.pop_ready())
            if not progressed and sched.next_arrival() is None \
                    and not any(r is not None for r in sched.slots) \
                    and sched._queue:
                raise RuntimeError(
                    "admission deadlock: queued requests cannot be "
                    "admitted and no slot is active")
            self._clock += 1
        if self._pending:
            self._fetch()
        self._span_routed = []
        self._finalize_logprobs(delivered)
        return delivered

    def generate(self, requests: List[Request], *,
                 truncate_prompts: bool = False) -> List[Result]:
        """Generate for a batch of requests (all enqueued at time zero,
        then drained — the all-at-once wrapper over the continuous loop).

        Validation happens up front — an empty batch, an empty prompt,
        or a prompt that cannot fit the engine's ``max_len`` context
        (together with at least one new token) fails fast with a
        ``ValueError`` naming the offending request, instead of a shape
        error deep in prefill.  ``truncate_prompts=True`` instead keeps
        the *last* ``max_len - 1`` tokens of an over-long prompt (the
        usual sliding-context behavior); ``Result.prompt_len`` then
        reports the truncated length.
        """
        if not requests:
            raise ValueError("generate() needs at least one request; "
                             "got an empty batch")
        limit = self.max_len - 1       # decode stops at max_len - 1
        for i, r in enumerate(requests):
            if len(r.prompt) == 0:
                raise ValueError(f"request {i} has an empty prompt")
            if len(r.prompt) > limit and not truncate_prompts:
                raise ValueError(
                    f"request {i} prompt has {len(r.prompt)} tokens but "
                    f"the engine context is max_len={self.max_len} "
                    f"(prompts are capped at {limit} so at least one "
                    f"token can be generated); shorten the prompt or "
                    f"pass truncate_prompts=True")
        if truncate_prompts:
            requests = [dataclasses.replace(r, prompt=list(r.prompt)[-limit:])
                        for r in requests]
        rids = [self.submit(r) for r in requests]
        by_rid = {res.rid: res for res in self.run()}
        return [by_rid[rid] for rid in rids]

    # -- phases ------------------------------------------------------------

    def _prefill_work(self) -> int:
        """One prompt chunk per mid-prefill slot (chunked prefill: long
        prompts interleave with decode steps instead of stalling them);
        returns how many chunks (or whole prompts) were dispatched."""
        work = self.scheduler.in_state("prefill")
        for tr in work:
            prompt = list(tr.request.prompt)
            if self._extend_ok:
                chunk = self.prefill_chunk
                start = tr.prefill_pos
                piece = prompt[start:start + chunk]
                n_valid = len(piece)
                toks = np.zeros((1, chunk), np.int32)
                toks[0, :n_valid] = piece
                logits, self._caches, routed = self._prefill_chunk(
                    self.params, self._caches, jnp.int32(tr.slot),
                    jnp.asarray(toks), jnp.int32(start),
                    jnp.int32(n_valid))
                if routed is not None:
                    self._pending.append((self._clock, "prefill", routed))
                tr.prefill_pos = start + n_valid
                if tr.prefill_pos < len(prompt):
                    continue                      # more chunks to stream
            else:
                toks = np.asarray(prompt, np.int32)[None, :]
                logits, self._caches = self._classic_prefill(
                    self.params, self._caches, jnp.int32(tr.slot),
                    jnp.asarray(toks))
                tr.prefill_pos = len(prompt)
            with TraceAnnotation("repro.engine.first_token") as span:
                self._first_token(tr, logits)
                if span.is_enabled():
                    chunks = -(-len(prompt) // self.prefill_chunk) \
                        if self._extend_ok else 1
                    span.set_metadata(
                        queue_ms=1e3 * (tr.admit_wall - tr.submit_wall),
                        prefill_ms=1e3 * (time.perf_counter()
                                          - tr.admit_wall),
                        chunks=chunks)
        return len(work)

    def _first_token(self, tr: TrackedRequest, logits) -> None:
        """Prefill just completed: sample the request's first token from
        the last prompt position's logits."""
        req = tr.request
        custom, idv = self._key_id(tr)
        tok, lp = self._sample(
            self._base_key, logits,
            jnp.asarray([custom], jnp.int32), jnp.asarray([idv], jnp.int32),
            jnp.asarray([0], jnp.int32),
            jnp.asarray([max(req.temperature, 0.0)], jnp.float32))
        with TraceAnnotation("repro.engine.sync"):
            tok_np, lp_np = self._fetch(tok, lp)
            t = int(tok_np[0])
        self._lp_vals.append(lp_np)
        self._lp_ids.append(np.asarray([tr.rid - self._rid_base], np.int32))
        tr.out = list(req.prompt) + [t]
        tr.last_token = t
        tr.new_tokens = 1
        tr.state = "decode"
        self._maybe_retire(tr, t)

    def _decode_work(self) -> int:
        """One lock-step decode step across every decode-state slot; idle
        and mid-prefill slots ride along masked (fixed shapes => one
        compiled program, and per-row bitwise independence).  Returns
        how many slots decoded."""
        dec = self.scheduler.in_state("decode")
        if not dec:
            return 0
        b = self.max_batch
        toks = np.zeros((b, 1), np.int32)
        pos = np.zeros(b, np.int32)
        active = np.zeros(b, bool)
        custom = np.zeros(b, np.int32)
        idv = np.zeros(b, np.int32)
        steps = np.zeros(b, np.int32)
        temps = np.zeros(b, np.float32)
        for tr in dec:
            s = tr.slot
            active[s] = True
            toks[s, 0] = tr.last_token
            plen = len(tr.request.prompt)
            pos[s] = plen + tr.new_tokens - 1     # == the slot's cache len
            custom[s], idv[s] = self._key_id(tr)
            steps[s] = tr.new_tokens
            temps[s] = max(tr.request.temperature, 0.0)
        logits, self._caches, routed = self._decode(
            self.params, jnp.asarray(toks), self._caches, jnp.asarray(pos),
            jnp.asarray(active))
        if routed is not None:
            self._pending.append((self._clock, "decode", routed))
        tok, lp = self._sample(self._base_key, logits,
                               jnp.asarray(custom), jnp.asarray(idv),
                               jnp.asarray(steps), jnp.asarray(temps))
        with TraceAnnotation("repro.engine.sync"):
            tok_np, lp_np = self._fetch(tok, lp)
        ids = np.full(b, _reduce.OUT_OF_RANGE_LABEL, np.int32)
        for tr in dec:
            ids[tr.slot] = tr.rid - self._rid_base
        self._lp_vals.append(lp_np)
        self._lp_ids.append(ids)
        for tr in dec:
            t = int(tok_np[tr.slot])
            tr.out.append(t)
            tr.last_token = t
            tr.new_tokens += 1
            self._maybe_retire(tr, t)
        return len(dec)

    def _fetch(self, *arrays):
        """``arrays`` on the host, and every pending routing count with
        them, in one ``device_get``."""
        pending, self._pending = self._pending, []
        got, counts = jax.device_get((arrays, [c for _, _, c in pending]))
        for (clock, phase, _), c in zip(pending, counts):
            if self.on_routing is not None:
                self.on_routing(clock, phase, c)
            self._span_routed.append(c)
        return got

    def _routing_stats(self, span) -> None:
        """The routing counts read inside ``span`` as its stats: pairs
        routed, and (layer, held expert) pairs with at least one."""
        if self._span_routed and span.is_enabled():
            counts = np.stack(self._span_routed)
            span.set_metadata(routed_pairs=int(np.sum(counts)),  # detlint: ok[DET001] host int32 counts: exact
                              experts_hit=int(np.count_nonzero(counts)))
        self._span_routed = []

    def _maybe_retire(self, tr: TrackedRequest, last_tok: int) -> None:
        req = tr.request
        plen = len(req.prompt)
        reason = None
        if req.eos_id is not None and last_tok == req.eos_id:
            reason = "stop"
        elif tr.new_tokens >= req.max_new_tokens:
            reason = "length"
        elif plen + tr.new_tokens >= self.max_len:
            reason = "length"                     # context full
        if reason is not None:
            tr.finish_reason = reason
            self.scheduler.finish(tr, self._result_of(tr), reason=reason)

    # -- results -----------------------------------------------------------

    def _key_id(self, tr: TrackedRequest):
        """(custom-seed flag, id) feeding the per-request PRNG fold-in."""
        if tr.request.seed is not None:
            return 1, int(tr.request.seed)
        return 0, tr.rid

    def _result_of(self, tr: TrackedRequest) -> Result:
        lat = max(time.perf_counter() - tr.arrive_wall, 0.0) \
            if tr.arrive_wall else 0.0
        return Result(tokens=list(tr.out) or list(tr.request.prompt),
                      prompt_len=len(tr.request.prompt),
                      rid=tr.rid, finish_reason=tr.finish_reason,
                      latency_s=lat)

    def _finalize_logprobs(self, results: List[Result]) -> None:
        """One segmented mean over the whole run's (step x slot) logprob
        stream — requests are the variable-length sets; steps where a slot
        was idle / another request carry the sentinel and vanish from both
        sum and count.  ``logprob_policy`` selects the accuracy tier."""
        if not self._lp_vals:
            return
        nseg = max(r.rid for r in results) - self._rid_base + 1 \
            if results else 0
        if nseg <= 0:
            return
        mean = _reduce.reduce(
            jnp.asarray(np.concatenate(self._lp_vals)),
            segment_ids=jnp.asarray(np.concatenate(self._lp_ids)),
            num_segments=nseg, op="mean", policy=self.logprob_policy)
        mean_np = np.asarray(mean)
        for r in results:
            sampled = len(r.tokens) - r.prompt_len
            if sampled > 0:
                r.mean_logprob = float(mean_np[r.rid - self._rid_base])
        self._lp_vals, self._lp_ids = [], []
