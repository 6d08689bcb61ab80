"""Driver of the served-model cells.

Set-up makes the weights on the device from the seed (the
configuration's ``make_params``), builds the program's ``Engine`` with
the configuration's serving settings and warms up every program the
window runs: a prompt chunk, the first-token sample, the full-width
decode and its sample.

The window is open loop: the mix's requests fall due at fixed times
after the window opens, whatever the engine does.  The engine has no
wall-clock intake, so the client submits each request that has fallen
due through ``Engine.submit`` from the ``Engine.run(on_step=...)`` hook,
after every engine step, and waits for the next one there while the
engine is idle.  The client reads each request's progress through
``Scheduler.tracked(rid)`` after every step: a token counts as delivered
at the end of the step that made it, and every time is taken from when
the request was due.  The engine drains what is in flight after the
window closes; every request due in the window counts.

The check, after the window and with the engine freed: a sample of the
finished requests drawn from the seed, the longest among them, run
through the configuration's float32 reference over prompt and served
tokens.  ``token_gap_max`` is the widest gap by which a served token's
reference logit lies below the reference's best at that position;
``logprob_mean_err`` the widest gap between a request's ``mean_logprob``
and the mean of the reference's log-probabilities of its served tokens.
"""

from __future__ import annotations

import gc
import os
import sys
import time

import numpy as np

import generate
from window import TraceWindow, percentile, span

#: model keys the program's configuration takes, and the values of those
#: it cannot change
_FIXED = {"partial_rotary_factor": 1.0, "use_qkv_bias": False,
          "norm": "rmsnorm", "hidden_act": "silu",
          "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


def program_config(name: str, m: dict):
    """The program's ``ModelConfig`` for the file's ``model`` block."""
    from repro.models.config import ModelConfig
    for k, v in _FIXED.items():
        if m.get(k, v) != v:
            raise ValueError(f"the program cannot run {k}={m[k]!r}")
    return ModelConfig(name=name, family="dense",
                       n_layers=m["num_hidden_layers"],
                       d_model=m["hidden_size"],
                       n_heads=m["num_attention_heads"],
                       n_kv_heads=m["num_key_value_heads"],
                       d_ff=m["intermediate_size"], vocab=m["vocab_size"],
                       rope_theta=float(m["rope_theta"]),
                       norm_eps=float(m["layer_norm_eps"]),
                       dtype="bfloat16")


class Client:
    """The open-loop client: submits due requests, records when each
    token was delivered, and what each engine step did."""

    def __init__(self, engine, arrivals, t_open: float, tw: TraceWindow,
                 after_step=None):
        self.engine, self.arrivals = engine, arrivals
        self.t_open, self.tw = t_open, tw
        self.after_step = after_step
        n = len(arrivals)
        self.rid = [None] * n
        self.submitted = [0.0] * n
        self.times = [[] for _ in range(n)]
        self.seen_new, self.seen_pf = [0] * n, [0] * n
        self.inflight = {}
        self.next = 0
        self.steps = []     # (time, prompt tokens, decode slots, live KV, traced)

    def due(self, i: int) -> float:
        return self.t_open + self.arrivals[i].due_s

    def submit_due(self, now: float) -> None:
        from repro.serve.engine import Request
        while self.next < len(self.arrivals) and self.due(self.next) <= now:
            a = self.arrivals[self.next]
            rid = self.engine.submit(Request(
                prompt=a.prompt, max_new_tokens=a.max_new_tokens,
                temperature=0.0))
            self.rid[self.next] = rid
            self.submitted[self.next] = now
            self.inflight[rid] = self.next
            self.next += 1

    def wait_for_next(self) -> None:
        """Sleep until the next request is due, and submit it."""
        with span("wait", self.tw.on):
            pause = self.due(self.next) - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
        now = time.perf_counter()
        self.tw.poll(now)
        self.submit_due(now)

    def observe(self, now: float):
        prompt = decode = live = 0
        sched = self.engine.scheduler
        for rid, i in list(self.inflight.items()):
            tr = sched.tracked(rid)
            got = tr.new_tokens - self.seen_new[i]
            if got > 0:
                first = self.seen_new[i] == 0
                self.times[i].extend([now] * got)
                if got - first > 0:          # it was in the decode step
                    decode += 1
                    live += len(tr.request.prompt) + tr.new_tokens - 1
                self.seen_new[i] = tr.new_tokens
            prompt += tr.prefill_pos - self.seen_pf[i]
            self.seen_pf[i] = tr.prefill_pos
            if tr.state == "done":
                del self.inflight[rid]
        return prompt, decode, live

    def on_step(self, engine, step) -> None:
        now = time.perf_counter()
        self.tw.poll(now)
        with span("client", self.tw.on):
            prompt, decode, live = self.observe(now)
            self.steps.append((now, prompt, decode, live, self.tw.on))
            self.submit_due(now)
        if self.after_step is not None:
            self.after_step(engine)
        while not engine.scheduler.has_work() \
                and self.next < len(self.arrivals):
            self.wait_for_next()


def build_engine(cfg: dict, params, seed: int):
    """The program's ``Engine`` with the configuration's serving settings,
    every program the window runs warmed up: a prompt chunk, the
    first-token sample, the full-width decode and its sample."""
    from repro.serve.engine import Engine, Request
    m, sv = cfg["model"], cfg["serving"]
    engine = Engine(program_config(cfg["name"], m), params,
                    max_len=sv["max_len"], max_batch=sv["max_batch"],
                    page_size=sv["page_size"],
                    prefill_chunk=sv["prefill_chunk"],
                    logprob_policy=sv["logprob_policy"],
                    seed=generate.seed64(seed) % (1 << 31))
    engine.generate([Request(prompt=list(range(1, sv["prefill_chunk"] + 9)),
                             max_new_tokens=3, temperature=0.0)])
    return engine


def serve(engine, arrivals, t_open: float, tw: TraceWindow,
          after_step=None):
    """Offer ``arrivals`` from ``t_open`` on and serve until the engine
    drains: (the client, the engine's results)."""
    client = Client(engine, arrivals, t_open, tw, after_step)
    client.wait_for_next()
    return client, engine.run(on_step=client.on_step)


def run(ctx):
    import jax
    from harness import Outcome, memory_peak

    cfg, tr, ref = ctx.config, ctx.traffic, ctx.reference
    m = cfg["model"]
    params = ref.make_params(generate.jax_key(ctx.seed), m)
    jax.block_until_ready(params)
    engine = build_engine(cfg, params, ctx.seed)
    arrivals = generate.open_poisson(tr, ctx.seed, ctx.seconds,
                                     m["vocab_size"])

    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_start
    tw = TraceWindow(ctx.trace_dir, t_open, ctx.seconds)
    client, results = serve(engine, arrivals, t_open, tw)
    tw.close()
    t_close = t_open + ctx.seconds
    peak = memory_peak(ctx.devices)

    by_rid = {r.rid: r for r in results}
    done = [i for i, rid in enumerate(client.rid) if rid in by_rid]
    ttft = [client.times[i][0] - client.due(i) for i in done
            if client.times[i]]
    itl = [b - a for i in done
           for a, b in zip(client.times[i], client.times[i][1:])]
    tokens_in_window = sum(t <= t_close for ts in client.times for t in ts)
    last_token = max(ts[-1] for ts in client.times if ts)
    late = [client.submitted[i] - client.due(i) for i in done]
    print(f"serve: {len(arrivals)} requests due, {len(done)} finished, "
          f"{sum(len(ts) for ts in client.times)} tokens "
          f"({tokens_in_window} in the window), ttft p50 "
          f"{percentile(ttft, 50) * 1e3:.1f} ms p95 "
          f"{percentile(ttft, 95) * 1e3:.1f} ms, itl p50 "
          f"{percentile(itl, 50) * 1e3:.1f} ms p95 "
          f"{percentile(itl, 95) * 1e3:.1f} ms, submit lateness p95 "
          f"{percentile(late, 95) * 1e3:.2f} ms, {len(client.steps)} steps, "
          f"longest step {1e3 * longest_step(client.steps, t_close):.1f} ms, "
          f"drained {time.perf_counter() - t_close:.1f} s after the close, "
          f"host load {os.getloadavg()[0]:.2f}",
          file=sys.stderr, flush=True)

    del engine, client.engine
    gc.collect()
    numbers = check(ctx, params, arrivals, client, by_rid, done)
    limits = dict(cfg["limits"], unfinished=0.0, short_requests=0.0)
    checks = {k: (numbers[k], float(v)) for k, v in limits.items()}
    steps = [s[1:4] for s in client.steps if s[4]]
    facts = {"model": m, "steps_traced": steps}
    return Outcome(
        metrics={"setup_s": setup_s,
                 "serve_tokens_per_s": tokens_in_window / ctx.seconds,
                 "ttft_p95_ms": percentile(ttft, 95) * 1e3,
                 "itl_p95_ms": percentile(itl, 95) * 1e3},
        attempted=len(arrivals), failed=len(arrivals) - len(done),
        checks=checks, facts=facts, memory_peak_bytes=peak,
        window=(t_open, min(t_close, last_token)))


def sample(lengths: dict, seed: int, k: int) -> list:
    """The requests the check reads: the longest, and ``k - 1`` others
    drawn from the seed; ``lengths`` maps each finished request to its
    prompt and served tokens."""
    rng = np.random.default_rng(generate.seed64(seed) ^ 0x5EED)
    longest = max(lengths, key=lengths.get)
    rest = [i for i in lengths if i != longest]
    return [longest] + [int(i) for i in rng.choice(
        rest, min(len(rest), k - 1), replace=False)]


def longest_step(steps: list, t_close: float) -> float:
    """The longest gap between the ends of two engine steps in the
    window (a stall shows here)."""
    ends = [s[0] for s in steps if s[0] <= t_close]
    return max((b - a for a, b in zip(ends, ends[1:])), default=0.0)


def as_read(r, max_len: int):
    """A finished request as the reference reads it: its tokens padded to
    ``max_len``, the token after each position, and the positions whose
    next token was served."""
    toks = np.zeros(max_len, np.int32)
    toks[:len(r.tokens)] = r.tokens
    nxt = np.zeros(max_len, np.int32)
    nxt[:len(r.tokens) - 1] = r.tokens[1:]
    return toks, nxt, np.arange(r.prompt_len - 1, len(r.tokens) - 1)


def answer(ctx, params, r):
    """What the program served for one finished request, as the check
    reads it: (tokens, next token at each position, the positions whose
    next token was served, the engine's mean log-probability)."""
    toks, nxt, pos = as_read(r, ctx.config["serving"]["max_len"])
    return toks, nxt, pos, r.mean_logprob


def check(ctx, params, arrivals, client, by_rid, done) -> dict:
    """The compared numbers, from a sample of the finished requests."""
    import jax.numpy as jnp
    m = ctx.config["model"]
    served = {i: by_rid[client.rid[i]] for i in done}
    short = sum(len(r.tokens) - r.prompt_len != arrivals[i].max_new_tokens
                for i, r in served.items())
    pick = sample({i: len(r.tokens) for i, r in served.items()}, ctx.seed,
                  ctx.traffic["check_requests"])
    gap_max, lp_err, n_tok = 0.0, 0.0, 0
    for i in pick:
        toks, nxt, pos, mean_logprob = answer(ctx, params, served[i])
        best, picked, lse, _ = (np.asarray(a, np.float64) for a in
                                ctx.reference.next_token_stats(
                                    params, m, jnp.asarray(toks),
                                    jnp.asarray(nxt)))
        gap_max = max(gap_max, float(np.max(best[pos] - picked[pos])))
        ref_mean = float(np.mean(picked[pos] - lse[pos]))
        lp_err = max(lp_err, abs(mean_logprob - ref_mean))
        n_tok += len(pos)
    print(f"serve check: {len(pick)} requests, {n_tok} served tokens "
          f"against the float32 reference", file=sys.stderr, flush=True)
    numbers = {"token_gap_max": gap_max, "logprob_mean_err": lp_err,
               "unfinished": float(len(arrivals) - len(done)),
               "short_requests": float(short)}
    for k, v in numbers.items():
        print(f"reading {k} {v!r}", file=sys.stderr, flush=True)
    return numbers
