#!/usr/bin/env python3
"""Each cell's control, put in the program's place, read by the harness's
own comparison.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 3] [--program]

Not part of a benchmark run.  For each seed it makes a whole run of the
cell (``harness.run_cell``: set-up, a window of ``--seconds``, the check)
with the control installed, and prints one JSON line: the seed,
``correct`` and every compared number beside its limit.  ``--program``
leaves the program in place, for the lower readings.  The control is the
step below the precision the configuration states:

  * ``exact2`` cells: the program's ``fast`` tier in its place;
  * ``fast`` cells (float32 at HIGHEST): each term rounded to what three
    bf16 passes keep (``Precision.HIGH`` against a one-hot: a bf16 high
    part plus a bf16 low part), then the program's ``fast`` sum;
  * served bfloat16 models: the float32 reference with every matrix
    product's inputs rounded to float8 (e4m3) in place of what the engine
    served: at each served position the token float8 puts first, and
    float8's mean log-probability of those tokens.

A limit lies between the largest program reading over a dozen seeds and
the smallest control reading (PERF.md gives both).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import spec as bench_spec  # noqa: E402


def high_terms(x):
    """``x`` as ``Precision.HIGH`` multiplies it by one: the sum of its
    bf16 high part and the bf16 high part of the rest."""
    import jax.numpy as jnp
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi + (x - hi).astype(jnp.bfloat16).astype(jnp.float32)


def install_reduce(policy: str, setattr_=setattr) -> None:
    """Put the control of a ``policy`` cell in ``repro.reduce``'s place."""
    import repro.reduce as R
    real = R.reduce

    def fast_in_place(*a, **k):
        return real(*a, **dict(k, policy="fast"))

    def high_in_place(values, *a, op="sum", **k):
        pre = values * values if op == "sumsq" else values
        if op not in ("sum", "sumsq"):
            raise ValueError(f"no HIGH control for op={op!r}")
        return real(high_terms(pre), *a, **dict(k, op="sum"))

    controls = {"exact2": fast_in_place, "fast": high_in_place}
    setattr_(R, "reduce", controls[policy])


def install_serve(driver, reference, setattr_=setattr) -> None:
    """Put the float8 reference's answers in the served tokens' place."""
    import jax.numpy as jnp
    real = driver.answer

    def fp8_answer(ctx, params, r):
        toks, nxt, pos, _ = real(ctx, params, r)
        m = ctx.config["model"]

        def stats(nx):
            return [np.asarray(a) for a in reference.next_token_stats(
                params, m, jnp.asarray(toks), jnp.asarray(nx),
                precision="fp8")]

        top8 = stats(nxt)[3]
        nxt = nxt.copy()
        nxt[pos] = top8[pos]
        _, picked8, lse8, _ = stats(nxt)
        mean8 = float(np.mean(picked8[pos].astype(np.float64)
                              - lse8[pos].astype(np.float64)))
        return toks, nxt, pos, mean8

    setattr_(driver, "answer", fp8_answer)


def install(bench, cell: dict, setattr_=setattr) -> None:
    """Install the control of ``cell``."""
    cfg = bench.config(cell["config"])
    if cfg["driver"] == "serve":
        install_serve(bench.driver("serve"), bench.reference(cell["config"]),
                      setattr_)
    else:
        install_reduce(bench.traffic(cell["traffic"])["policy"], setattr_)


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program", action="store_true",
                    help="leave the program in place")
    args = ap.parse_args(argv)
    bench = bench_spec.Benchmark(BENCH.parent)
    sys.path.insert(0, str(bench.root / "src"))
    import harness
    if not args.program:
        install(bench, bench.cell(args.workload))
    for seed in (int(s) for s in args.seeds.split(",")):
        run = SimpleNamespace(workload=args.workload, seed=seed,
                              seconds=args.seconds, trace=0)
        line = harness.run_cell(bench, run, t_start=t_start)
        if line is None:
            return harness.NO_CHIP
        print(json.dumps({"seed": seed, "control": not args.program,
                          "correct": line["correct"],
                          "checks": line["checks"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
