#!/usr/bin/env python3
"""The program's own profiler spans in a traced run.  Not part of a
benchmark run: ``trace.py`` reads the benchmark's ``bench.*`` spans only,
and this reads the program's, whose names start with ``repro.``, with
their stats, from the same ``.xplane.pb``.

    python3 bench/program_spans.py <trace dir or .xplane.pb>

prints one JSON object:

  * ``idle_gaps``: the idle seconds per device inside ``bench.window``,
    each gap put down to the innermost ``bench.*`` or ``repro.*`` span
    open on the host at its midpoint (``trace.attribute``);
    ``short_gap_idle_share``: the share of the idle time in gaps shorter
    than twice the device-host clock offset, where that attribution is
    uncertain;
  * ``engine_host_ms``: the mean ``repro.engine.step`` less the time in
    its ``repro.engine.sync`` spans: the engine's time per step outside
    the reads of sampled tokens (a dispatch that waits for the device
    counts here);
  * ``queue_wait_ms``, ``prefill_wait_ms``: the mean ``queue_ms`` and
    ``prefill_ms`` stats of the ``repro.engine.first_token`` spans, the
    two parts of a request's time to its first token inside the engine;
  * ``front_door_us``: the mean ``repro.reduce`` span, the front door
    from call to return;
  * ``spans``: how many of each program span, and their mean duration.

Each counts only spans wholly inside the window and is None where there
are none (a program without the spans).
"""

from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import spec as bench_spec  # noqa: E402

T = bench_spec.load_module(BENCH / "trace.py", "bench_trace")

PREFIX = "repro."
#: a v5e trace puts the device about this far from the host (PERF.md)
OFFSET_MS = 1.3

Span = Tuple[float, float, dict]                 # start ns, end ns, stats


def read(path: Path) -> Dict[str, List[Span]]:
    """The program's host spans, with their stats, by name."""
    from jax.profiler import ProfileData
    out: Dict[str, List[Span]] = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        out[e.name].append((e.start_ns,
                                            e.start_ns + e.duration_ns,
                                            dict(e.stats)))
    return dict(out)


def in_window(program: Dict[str, List[Span]], window: Tuple[float, float]
              ) -> Dict[str, List[Span]]:
    lo, hi = window
    return {n: [s for s in ss if s[0] >= lo and s[1] <= hi]
            for n, ss in program.items()}


def _mean(values: list) -> Optional[float]:
    return sum(values) / len(values) if values else None


def engine_host_ms(program: Dict[str, List[Span]]) -> Optional[float]:
    syncs = program.get("repro.engine.sync", [])
    own = [b - a - sum(d - c for c, d, _ in syncs if c >= a and d <= b)
           for a, b, _ in program.get("repro.engine.step", [])]
    mean = _mean(own)
    return None if mean is None else 1e-6 * mean


def queue_wait_ms(program: Dict[str, List[Span]]) -> Optional[float]:
    return _mean([st["queue_ms"] for _, _, st
                  in program.get("repro.engine.first_token", [])])


def prefill_wait_ms(program: Dict[str, List[Span]]) -> Optional[float]:
    return _mean([st["prefill_ms"] for _, _, st
                  in program.get("repro.engine.first_token", [])])


def front_door_us(program: Dict[str, List[Span]]) -> Optional[float]:
    mean = _mean([b - a for a, b, _ in program.get("repro.reduce", [])])
    return None if mean is None else 1e-3 * mean


def idle_gaps(devices, spans, program: Dict[str, List[Span]]
              ) -> List[Tuple[str, float]]:
    """(attributed to, seconds) of every idle gap of every device inside
    the window, with the program's spans beside the benchmark's."""
    lo, hi = spans[T.WINDOW][0]
    named = {**spans, **{n: [(a, b) for a, b, _ in ss]
                         for n, ss in program.items()}}
    out: List[Tuple[str, float]] = []
    for dv in devices:
        merged = T.clip(T.union([(a, b) for _, a, b in dv.ops]), lo, hi)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        out += T.attribute([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                            if b > a], named)
    return out


def report(path: Path) -> dict:
    devices, spans = T.read_planes(path)
    program = read(path)
    window = spans[T.WINDOW][0]
    mine = in_window(program, window)
    gaps = idle_gaps(devices, spans, program)
    by: Dict[str, float] = defaultdict(float)
    for who, s in gaps:
        by[who] += s / len(devices)
    idle = sum(by.values())
    short = sum(s for _, s in gaps if s < 2e-3 * OFFSET_MS) / len(devices)
    return {
        "window_s": (window[1] - window[0]) * 1e-9,
        "idle_s": idle,
        "idle_gaps": sorted(([n, s] for n, s in by.items()),
                            key=lambda kv: -kv[1]),
        "short_gap_idle_share": short / idle if idle else None,
        "engine_host_ms": engine_host_ms(mine),
        "queue_wait_ms": queue_wait_ms(mine),
        "prefill_wait_ms": prefill_wait_ms(mine),
        "front_door_us": front_door_us(mine),
        "spans": {n: [len(ss), 1e-3 * _mean([b - a for a, b, _ in ss])]
                  for n, ss in sorted(mine.items()) if ss},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    args = ap.parse_args(argv)
    path = Path(args.trace)
    if path.is_dir():
        path = T.find_xplane(path)
    print(json.dumps(report(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
