"""Driver of served cells whose configuration is a DeepSeek-V2 share of
expert parallelism (latent attention, leading dense layers, a share of
the routed experts): ``serve.py``'s client, serving loop, sampling and
check, with the program's ``ModelConfig`` built from the DeepSeek keys.

The configuration's file holds the published ``config.json`` keys at its
top level (``n_routed_experts`` is the experts held here) beside its
own: ``share`` (the first expert held and the router's width),
``serving``, ``limits``.  ``run`` puts the model keys under ``model`` in
``ctx.config``, where the reference and the controls read them.

Besides ``serve.py``'s facts, a traced run gives the held experts'
routing in each traced decode step (the engine's counters, read with the
sampled tokens) and the device time of the grouped expert matmul
(``jax.lax.ragged_dot``, named ``ragged-dot`` in the trace) inside the
decode program's runs, read from the run's own trace.
"""

from __future__ import annotations

import gc
import os
import sys
import time
from pathlib import Path

import numpy as np

import generate
import spec as bench_spec
from window import TraceWindow, percentile

BENCH = Path(__file__).resolve().parents[1]
base = bench_spec.load_module(BENCH / "drivers" / "serve.py")

#: the configuration file's keys that are not the model's
OWN_KEYS = {"driver", "source", "deployment", "published", "departures",
            "serving", "weights", "limits", "assumed", "name", "model"}

#: published keys whose value the program cannot change
_FIXED = {"scoring_func": "softmax", "topk_method": "greedy", "n_group": 1,
          "topk_group": 1, "q_lora_rank": None, "moe_layer_freq": 1,
          "routed_scaling_factor": 1,
          "attention_bias": False, "hidden_act": "silu",
          "tie_word_embeddings": False, "torch_dtype": "bfloat16"}

#: the grouped matmul's ops in the device trace
EXPERT_OP = "ragged-dot"
DECODE_PROGRAM = "_decode_fn"


def model_keys(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items() if k not in OWN_KEYS}


def program_config(name: str, m: dict):
    """The program's ``ModelConfig`` for the file's model keys."""
    from repro.models.config import BlockSpec, ModelConfig, MoECfg, YarnCfg
    for k, v in _FIXED.items():
        if m.get(k, v) != v:
            raise ValueError(f"the program cannot run {k}={m[k]!r}")
    y, share = m["rope_scaling"], m["share"]
    if y.get("type") != "yarn":
        raise ValueError(f"the program cannot run rope_scaling {y!r}")
    return ModelConfig(
        name=name, family="moe", n_layers=m["num_hidden_layers"],
        first_dense=m["first_k_dense_replace"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab=m["vocab_size"], period=(BlockSpec("attn", "moe"),),
        moe=MoECfg(num_experts=share["router_outputs"],
                   top_k=m["num_experts_per_tok"],
                   d_ff_expert=m["moe_intermediate_size"],
                   num_shared=m["n_shared_experts"],
                   d_ff_shared=m["moe_intermediate_size"],
                   router_norm_topk=bool(m["norm_topk_prob"]),
                   held_first=share["first_expert"],
                   held=m["n_routed_experts"]),
        attn_type="mla", kv_lora_rank=m["kv_lora_rank"],
        qk_nope_dim=m["qk_nope_head_dim"], qk_rope_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"], rope_theta=float(m["rope_theta"]),
        rope_scaling=YarnCfg(
            factor=float(y["factor"]),
            original_max_position=y["original_max_position_embeddings"],
            beta_fast=float(y["beta_fast"]), beta_slow=float(y["beta_slow"]),
            mscale=float(y["mscale"]),
            mscale_all_dim=float(y["mscale_all_dim"])),
        norm_eps=float(m["rms_norm_eps"]), dtype="bfloat16")


def build_engine(cfg: dict, params, seed: int):
    """The program's ``Engine`` with the configuration's serving settings,
    every program the window runs warmed up (``serve.build_engine``)."""
    from repro.serve.engine import Engine, Request
    sv = cfg["serving"]
    engine = Engine(program_config(cfg["name"], model_keys(cfg)), params,
                    max_len=sv["max_len"], max_batch=sv["max_batch"],
                    page_size=sv["page_size"],
                    prefill_chunk=sv["prefill_chunk"],
                    logprob_policy=sv["logprob_policy"],
                    seed=generate.seed64(seed) % (1 << 31))
    engine.generate([Request(prompt=list(range(1, sv["prefill_chunk"] + 9)),
                             max_new_tokens=3, temperature=0.0)])
    return engine


serve = base.serve


def expert_time(trace_dir: Path):
    """(device seconds of the grouped expert matmul inside the decode
    program's runs, runs), from the traced window, summed over devices."""
    tr = bench_spec.load_module(BENCH / "trace.py", "bench_trace")
    devices, spans = tr.read_planes(tr.find_xplane(trace_dir))
    if tr.WINDOW not in spans:
        return 0.0, 0
    lo, hi = spans[tr.WINDOW][0]
    secs, runs = 0.0, 0
    for dv in devices:
        progs = sorted((a, b) for n, a, b in dv.modules
                       if DECODE_PROGRAM in n and a >= lo and b <= hi)
        runs += len(progs)
        starts = [a for a, _ in progs]
        for label, a, b in dv.ops:
            if not tr.op_name(label).startswith(EXPERT_OP):
                continue
            i = np.searchsorted(starts, a, side="right") - 1
            if i >= 0 and a >= progs[i][0] and b <= progs[i][1]:
                secs += (b - a) * 1e-9
    return secs, runs


def run(ctx):
    import jax
    from harness import Outcome, memory_peak

    cfg = ctx.config = {**ctx.config, "model": model_keys(ctx.config)}
    tr, ref, m = ctx.traffic, ctx.reference, cfg["model"]
    program_config(cfg["name"], m)          # refuse early what cannot run
    params = ref.make_params(generate.jax_key(ctx.seed), m)
    jax.block_until_ready(params)
    engine = build_engine(cfg, params, ctx.seed)
    decode_counts = {}          # engine step -> its decode's routed pairs

    def on_routing(clock, phase, counts):
        if phase == "decode":
            decode_counts[clock] = counts

    engine.on_routing = on_routing
    arrivals = generate.open_poisson(tr, ctx.seed, ctx.seconds,
                                     m["vocab_size"])

    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_start
    tw = TraceWindow(ctx.trace_dir, t_open, ctx.seconds)
    clocks = []                         # engine step of each client step
    client, results = serve(engine, arrivals, t_open, tw,
                            lambda eng: clocks.append(eng._clock))
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
    decode_pairs = [c.sum() for c in decode_counts.values()]
    print(f"serve: {len(arrivals)} requests due, {len(done)} finished, "
          f"{sum(len(ts) for ts in client.times)} tokens "
          f"({tokens_in_window} in the window), ttft p50 "
          f"{percentile(ttft, 50) * 1e3:.1f} ms p95 "
          f"{percentile(ttft, 95) * 1e3:.1f} ms, itl p50 "
          f"{percentile(itl, 50) * 1e3:.1f} ms p95 "
          f"{percentile(itl, 95) * 1e3:.1f} ms, {len(client.steps)} steps, "
          f"longest step "
          f"{1e3 * base.longest_step(client.steps, t_close):.1f} ms, "
          f"held-expert pairs per decode step "
          f"{np.mean(decode_pairs) if decode_pairs else 0:.1f}, drained "
          f"{time.perf_counter() - t_close:.1f} s after the close, host "
          f"load {os.getloadavg()[0]:.2f}", file=sys.stderr, flush=True)

    facts = {"model": m, "steps_traced": [s[1:4] for s in client.steps
                                          if s[4]]}
    if ctx.trace:
        by_step = {(c, s[2], s[3]) for c, s in zip(clocks, client.steps)
                   if s[4] and s[2]}
        facts["moe_decode_traced"] = [
            (active, live, decode_counts[c].tolist())
            for c, active, live in sorted(by_step) if c in decode_counts]
        facts["moe_expert_s"], facts["moe_decode_runs"] = \
            expert_time(ctx.trace_dir)

    del engine, client.engine
    gc.collect()
    numbers = check(ctx, params, arrivals, client, by_rid, done)
    limits = dict(cfg["limits"], unfinished=0.0, short_requests=0.0)
    checks = {k: (numbers[k], float(v)) for k, v in limits.items()}
    return Outcome(
        metrics={"setup_s": setup_s,
                 "serve_tokens_per_s": tokens_in_window / ctx.seconds,
                 "ttft_p95_ms": percentile(ttft, 95) * 1e3,
                 "itl_p95_ms": percentile(itl, 95) * 1e3},
        attempted=len(arrivals), failed=len(arrivals) - len(done),
        checks=checks, facts=facts, memory_peak_bytes=peak,
        window=(t_open, min(t_close, last_token)))


def answer(ctx, params, r):
    """What the program served for one finished request, as the check
    reads it (``serve.answer``); the controls replace this function."""
    toks, nxt, pos = base.as_read(r, ctx.config["serving"]["max_len"])
    return toks, nxt, pos, r.mean_logprob


def check(ctx, params, arrivals, client, by_rid, done) -> dict:
    """``serve.check``, reading each request through this module's
    ``answer``."""
    real = base.answer
    base.answer = answer
    try:
        return base.check(ctx, params, arrivals, client, by_rid, done)
    finally:
        base.answer = real
