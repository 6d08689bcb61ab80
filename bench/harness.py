"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration, its traffic mix
and its per-layer metrics are found by name (``bench/spec.py``).  Set-up
(weights or data from the seed, compilation or a compile-cache load,
warm-up of the cell's own shapes) is timed as ``setup_s``; then the
cell's driver measures a window of ``--seconds`` and checks the outputs
of that window against the configuration's plain reference.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` the
per-layer ones, read from a profiler trace of part of the window.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``busy_s`` and
``window_s`` when traced), ``breakdown`` when traced, and ``checks`` last
(each compared number beside its limit); the checks are also the last
lines of standard error.  Without a TPU, or with fewer chips than the
cell asks for, it exits 3 and prints no result.  JAX's compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, Optional

import spec as bench_spec
import useful

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

NO_CHIP = 3


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell and its parts, the run's arguments."""
    cell: dict
    config: dict
    traffic: dict
    reference: Any
    seed: int
    seconds: float
    trace: bool
    t_start: float
    devices: list
    trace_dir: Optional[Path] = None


@dataclasses.dataclass
class Outcome:
    """What a driver returns."""
    metrics: Dict[str, float]               # end-to-end, setup_s included
    attempted: int
    failed: int
    checks: Dict[str, tuple]                # name -> (value, limit)
    facts: Dict[str, Any]                   # what per-layer readers read
    memory_peak_bytes: int
    window: tuple                           # (open, close), host clock


@dataclasses.dataclass
class LayerRun:
    """What a per-layer reader gets."""
    facts: Dict[str, Any]
    summary: Any                            # trace.Summary
    peaks: dict


class CompileCounter:
    """When each backend compilation JAX reports ended (host clock)."""

    def __init__(self, jax):
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.times.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t in self.times)


def configure_cache(jax, root: Path) -> str:
    """The compile cache: ``$JAX_COMPILATION_CACHE_DIR``, which JAX reads
    itself, else the fixed ``<checkout>/.jax_cache``; every program is
    cached, however quick its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(root / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak(devices) -> int:
    """The peak bytes on the fullest device, read after the window from
    what JAX reports: the larger of the arrays' peak and the arrays in
    use now plus the peak reserved for programs' own temporaries, which
    ``peak_bytes_in_use`` leaves out (on a v5e an exact2 call's
    12.25 GiB of temporaries show only as reserved)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(max(stats.get("peak_bytes_in_use", 0),
                         stats.get("bytes_in_use", 0)
                         + stats.get("peak_bytes_reserved", 0)))
    return int(max(peaks))


def layer_metrics(bench, cell_name: str, run: LayerRun) -> Dict[str, dict]:
    out = {}
    for m in bench.per_layer(cell_name):
        value = bench.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(bench, args, *, t_start: float,
             require_chip: bool = True) -> Optional[dict]:
    """Set up, measure and check one cell; the result line's object, or
    None where the chips the cell needs are not there."""
    cell = bench.cell(args.workload)
    import jax
    cache = configure_cache(jax, bench.root)
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu"
                         or len(devices) < cell["chips"]):
        print(f"bench: {args.workload} needs {cell['chips']} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    devices = devices[:cell["chips"]]
    print(f"bench: {args.workload} on {len(devices)} x "
          f"{devices[0].device_kind} (jax {jax.__version__}), compile cache "
          f"{cache}", file=sys.stderr, flush=True)
    config = bench.config(cell["config"])
    compiles = CompileCounter(jax)
    ctx = Context(cell=cell, config=config,
                  traffic=bench.traffic(cell["traffic"]),
                  reference=bench.reference(cell["config"]),
                  seed=args.seed, seconds=float(args.seconds),
                  trace=bool(args.trace), t_start=t_start, devices=devices,
                  trace_dir=Path(tempfile.mkdtemp(prefix="bench-trace-"))
                  if args.trace else None)
    tmp = ctx.trace_dir
    try:
        out = bench.driver(config["driver"]).run(ctx)
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": out.memory_peak_bytes}
        result: Dict[str, Any] = {}
        if ctx.trace:
            bench_trace = bench_spec.load_module(BENCH / "trace.py",
                                                 "bench_trace")
            summary = bench_trace.summarize(bench_trace.find_xplane(tmp))
            device.update(busy_s=summary.mean_busy_s,
                          window_s=summary.window_s)
            metrics = layer_metrics(bench, args.workload, LayerRun(
                facts=out.facts, summary=summary,
                peaks=useful.peaks(devices[0].device_kind)))
            result["breakdown"] = {"device_ops": summary.top_ops(),
                                   "idle_gaps": summary.top_gaps()}
        else:
            units = {m["name"]: m["unit"]
                     for m in bench.end_to_end(args.workload)}
            missing = set(units) - set(out.metrics)
            if missing:
                raise RuntimeError(f"driver gave no {sorted(missing)}")
            metrics = {k: {"value": float(out.metrics[k]), "unit": u}
                       for k, u in units.items()}
    finally:
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)
    correct = all(v <= lim for v, lim in out.checks.values())
    line = {"correct": bool(correct), "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    line.update(result)
    line["compiles_in_window"] = compiles.between(*out.window)
    line["checks"] = {k: {"value": float(v), "limit": float(lim)}
                      for k, (v, lim) in out.checks.items()}
    return line


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t_start: Optional[float] = None, root: Path = ROOT,
         require_chip: bool = True) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    bench = bench_spec.Benchmark(root)
    sys.path.insert(0, str(root / "src"))
    line = run_cell(bench, args, t_start=t_start,
                    require_chip=require_chip)
    if line is None:
        return NO_CHIP
    for name, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


