#!/usr/bin/env python3
"""Find the knee of a served cell: the highest offered rate the engine
sustains.  Not part of a benchmark run; its result is written into the
mix's file (``rate_per_s``) as a number.

    python3 bench/sweep.py --workload serve.stablelm-1.6b.poisson \\
        --rates 2,3,4,5,6 --seconds 20 --seed 1

One process, one set-up; then, for each rate, a window of ``--seconds``
of the cell's mix at that rate, drained before the next.  Per rate it
prints the requests due, tokens/s, TTFT and ITL percentiles, how many
requests were still queued (due but not admitted) when the window
closed, and how long the drain took: a rate the engine sustains ends its
window with an empty queue and a short drain.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import generate  # noqa: E402
import spec as bench_spec  # noqa: E402
from window import TraceWindow, percentile  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    bench = bench_spec.Benchmark(BENCH.parent)
    sys.path.insert(0, str(bench.root / "src"))
    import jax
    import harness
    harness.configure_cache(jax, bench.root)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return harness.NO_CHIP
    cell = bench.cell(args.workload)
    cfg, tr = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    drv = bench.driver(cfg["driver"])
    m = cfg["model"]
    params = bench.reference(cell["config"]).make_params(
        generate.jax_key(args.seed), m)
    engine = drv.build_engine(cfg, params, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        arrivals = generate.open_poisson(tr, args.seed, args.seconds,
                                         m["vocab_size"], rate=rate)
        t_open = time.perf_counter()
        queued = []

        def after_step(eng, queued=queued, t_open=t_open):
            if not queued and time.perf_counter() >= t_open + args.seconds:
                queued.append(len(eng.scheduler._queue))

        client, _ = drv.serve(engine, arrivals, t_open,
                              TraceWindow(None, t_open, args.seconds),
                              after_step)
        t_close = t_open + args.seconds
        ttft = [ts[0] - client.due(i) for i, ts in enumerate(client.times)]
        itl = [b - a for ts in client.times for a, b in zip(ts, ts[1:])]
        tokens = sum(t <= t_close for ts in client.times for t in ts)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(arrivals),
            "tokens_per_s": tokens / args.seconds,
            "offered_tokens_per_s": sum(a.max_new_tokens for a in arrivals)
            / args.seconds,
            "ttft_p50_ms": percentile(ttft, 50) * 1e3,
            "ttft_p95_ms": percentile(ttft, 95) * 1e3,
            "itl_p50_ms": percentile(itl, 50) * 1e3,
            "itl_p95_ms": percentile(itl, 95) * 1e3,
            "queued_at_close": queued[0] if queued else 0,
            "drain_s": time.perf_counter() - t_close,
            "steps": len(client.steps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
