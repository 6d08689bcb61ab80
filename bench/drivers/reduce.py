"""Driver of the segmented-reduction cells.

Set-up makes the cell's stream on the device from the seed: the
segments the configuration's reference lays out (``layout(cfg)``: name,
values, rows per segment), through the one generator.  Then it warms the
call up.  The window is a closed loop with ``in_flight`` calls
outstanding: the front door ``repro.reduce`` called eagerly on the
device-resident arrays, as the README's quickstart calls it, and the
oldest call blocked on once that many are dispatched (1: each call is
blocked on before the next).  When the window's time is up nothing more
is sent, every call sent is waited for, and the clock is read after
that wait: all of that work counts, over all of that time.  A sample of
the window's results, drawn from the seed, is compared after the window
with the configuration's float64 reference.

Configuration keys: ``width``, ``block_size``, ``op``, ``values`` (the
generator's scales).  Traffic keys: ``policy``, ``keep_outputs`` (the
sample's size), ``in_flight`` (default 1) and ``limits`` (compared
number -> limit).
"""

from __future__ import annotations

import os
import sys
import time
from collections import deque

import numpy as np

import generate
from window import Reservoir, TraceWindow, span


def run(ctx):
    import jax
    import repro.reduce as R
    from harness import Outcome, memory_peak

    cfg, tr = ctx.config, ctx.traffic
    lay = ctx.reference.layout(cfg)
    rows = [r for _, _, r in lay]
    n, d, segs = sum(rows), int(cfg["width"]), len(lay)
    block = int(cfg["block_size"])
    x, ids = generate.segments(ctx.seed, rows, [v for _, v, _ in lay], d,
                               cfg["values"])

    def call():
        return R.reduce(x, segment_ids=ids, num_segments=segs, op=cfg["op"],
                        policy=tr["policy"], block_size=block)

    keep = Reservoir(int(tr["keep_outputs"]), generate.seed64(ctx.seed))
    depth = int(tr.get("in_flight", 1))
    dispatch, durations, pending = [], [], deque()
    for _ in range(2):
        jax.block_until_ready(call())
    t_open = time.perf_counter()
    setup_s = t_open - ctx.t_start
    tw = TraceWindow(ctx.trace_dir, t_open, ctx.seconds)
    calls, now = 0, t_open
    while now - t_open < ctx.seconds:
        tw.poll(now)
        traced = tw.on
        with span("call", traced):
            a = time.perf_counter()
            with span("dispatch", traced):
                pending.append(call())
            b = time.perf_counter()
            if len(pending) >= depth:
                with span("block", traced):
                    out = pending.popleft()
                    out.block_until_ready()
                keep.offer(out)
        last, now = now, time.perf_counter()
        calls += 1
        durations.append(now - last)
        if not traced:
            dispatch.append(b - a)
    tw.close()
    while pending:
        out = pending.popleft()
        out.block_until_ready()
        keep.offer(out)
    now = time.perf_counter()
    window_s = now - t_open
    peak = memory_peak(ctx.devices)
    durations.sort()
    print(f"reduce: {calls} calls ({depth} in flight) in {window_s:.3f} s, "
          f"call median "
          f"{1e3 * durations[len(durations) // 2]:.3f} ms, longest "
          f"{1e3 * durations[-1]:.3f} ms, host load {os.getloadavg()[0]:.2f}",
          file=sys.stderr, flush=True)

    # the check: every kept result against the float64 reference
    outs = [np.asarray(o) for o in keep.items]
    del keep
    ref, largest = ctx.reference.sums(np.asarray(x), np.asarray(ids), segs)
    del x, ids
    numbers = {
        "ulp_err_max": max(float(ctx.reference.ulps(o, ref).max())
                           for o in outs),
        "bound_err_max": max(float(ctx.reference.bound_units(
            o, ref, rows, largest).max()) for o in outs),
        "rel_err_max": max(float(ctx.reference.relative(o, ref).max())
                           for o in outs),
        "outputs_differing": float(sum(not np.array_equal(o, outs[0])
                                       for o in outs)),
        "nonfinite": float(sum(not np.all(np.isfinite(o)) for o in outs)),
    }
    for k, v in numbers.items():
        print(f"reading {k} {v!r}", file=sys.stderr, flush=True)
    limits = dict(tr["limits"], nonfinite=0.0)
    checks = {k: (numbers[k], float(lim)) for k, lim in limits.items()}
    facts = {"rows_per_chip": n, "width": d, "segments": segs,
             "calls": calls, "dispatch_s_untraced": dispatch,
             "kept": len(outs)}
    return Outcome(
        metrics={"setup_s": setup_s, "reduce_rows_per_s": calls * n / window_s},
        attempted=calls, failed=0, checks=checks, facts=facts,
        memory_peak_bytes=peak, window=(t_open, now))
