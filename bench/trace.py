"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

From the device planes (``/device:TPU:<i>``) it takes the ``XLA Ops``
line (one event per operation that ran) and the ``XLA Modules`` line (one
event per program run); from the host plane the benchmark's own
``TraceAnnotation`` spans, whose names start with ``bench.``.  The traced
window is the ``bench.window`` span.  It gives:

  * busy seconds per device: the union of operation intervals inside the
    window; the idle share is 1 - busy / window;
  * device seconds per operation name and per program name, and how many
    times each program ran, all inside the window;
  * the idle gaps of each device inside the window, each put down to the
    innermost benchmark span that was open on the host at its midpoint
    (``host:unannotated`` where none was but the window).

An operation is matched by a metric on its name and on the text of its
string stats, or on its own name alone.  Times in the trace are
nanoseconds; results are seconds.  Device and host events share one
clock to about a millisecond (a v5e trace put the device about 1.2 ms
before the host), so a gap's attribution is that coarse.
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Tuple[str, float, float]]          # (label, start, end) ns
    modules: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: List[float]                          # per device
    op_s: Dict[str, float]                       # summed over devices
    op_s_by_device: List[Dict[str, float]]
    module_s: Dict[str, float]                   # summed over devices
    module_runs: Dict[str, int]                  # summed over devices
    module_s_by_device: List[Dict[str, float]]
    module_runs_by_device: List[Dict[str, int]]
    gaps: List[Tuple[str, float]]                # (attributed to, s), all
    spans: Dict[str, List[Interval]]             # host spans, ns

    @property
    def devices(self) -> int:
        return len(self.busy_s)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s)

    def ops_matching(self, patterns) -> float:
        """Device seconds of every op whose text (name, HLO, stats)
        contains a pattern, summed over devices."""
        rx = [re.compile(p) for p in patterns]
        return sum(s for n, s in self.op_s.items()
                   if any(r.search(n) for r in rx))

    def ops_named(self, pattern: str, device: int) -> float:
        """Device seconds on one device of every op whose own name (not
        an operand's) starts with a match of ``pattern``."""
        rx = re.compile(pattern)
        return sum(s for n, s in self.op_s_by_device[device].items()
                   if rx.match(op_name(n)))

    def modules_matching(self, patterns, device=None) -> Tuple[float, int]:
        """(device seconds, runs) of every program whose name matches,
        summed over devices or on one."""
        rx = [re.compile(p) for p in patterns]
        secs = self.module_s if device is None \
            else self.module_s_by_device[device]
        runs = self.module_runs if device is None \
            else self.module_runs_by_device[device]
        hit = [n for n in secs if any(r.search(n) for r in rx)]
        return sum(secs[n] for n in hit), sum(runs[n] for n in hit)

    @property
    def idle_pct(self) -> float:
        """Percent of the window in which a device ran no op, mean over
        devices."""
        return 100.0 * (1.0 - self.mean_busy_s / self.window_s)

    def top_ops(self, k: int = 10) -> List[List]:
        """The ops that took most device time, seconds per device."""
        per: Dict[str, float] = defaultdict(float)
        for label, s in self.op_s.items():
            per[op_name(label)] += s
        top = sorted(per.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s / self.devices] for n, s in top]

    def top_gaps(self, k: int = 10) -> List[List]:
        """Idle seconds per device, by what the host was doing."""
        agg: Dict[str, float] = defaultdict(float)
        for who, s in self.gaps:
            agg[who] += s
        per = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s / self.devices] for n, s in per]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; returns them sorted and disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def _label(event) -> str:
    """An op's name, then the text of its string stats, ``|``-joined."""
    extra = [v for _, v in event.stats if isinstance(v, str)]
    return "|".join([event.name] + extra)


def op_name(label: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)|...`` -> ``fusion.3``: the trace
    names an op by its whole HLO text."""
    return label.split("|")[0].split(" = ")[0].lstrip("%")


def _module_name(name: str) -> str:
    """``jit__decode_fn(12)`` -> ``jit__decode_fn``."""
    return re.sub(r"\(\d+\)$", "", name)


def read_planes(path: Path):
    """(devices, host spans) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: List[Device] = []
    spans: Dict[str, List[Interval]] = defaultdict(list)
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(_label(e), e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(_module_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events]
            if ops or mods:
                devices.append(Device(plane.name, ops, mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans[e.name].append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    devices.sort(key=lambda dv: dv.name)
    return devices, dict(spans)


def attribute(gaps: List[Interval], spans: Dict[str, List[Interval]]
              ) -> List[Tuple[str, float]]:
    """(name, seconds) per gap: the innermost (shortest) benchmark span
    open on the host at the gap's midpoint, by one sweep over both."""
    flat = sorted((a, b, name) for name, ivs in spans.items()
                  if name != WINDOW for a, b in ivs)
    out, active, i = [], [], 0
    for a, b in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (a + b)
        while i < len(flat) and flat[i][0] <= mid:
            active.append(flat[i])
            i += 1
        active = [s for s in active if s[1] > mid]
        name = min(active, key=lambda s: s[1] - s[0])[2] if active \
            else "host:unannotated"
        out.append((name, (b - a) * 1e-9))
    return out


def summarize(path: Path) -> Summary:
    devices, spans = read_planes(path)
    if not devices:
        raise ValueError(f"{path}: no device plane with {OPS_LINE!r}")
    if WINDOW not in spans:
        raise ValueError(f"{path}: no {WINDOW!r} span on the host")
    lo, hi = spans[WINDOW][0]
    busy, gaps = [], []
    op_tot: Dict[str, float] = defaultdict(float)
    mod_tot: Dict[str, float] = defaultdict(float)
    run_tot: Dict[str, int] = defaultdict(int)
    op_dev, mod_dev, run_dev = [], [], []
    for dv in devices:
        merged = clip(union([(a, b) for _, a, b in dv.ops]), lo, hi)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        gaps += attribute([(a, b) for a, b in zip(edges[0::2], edges[1::2])
                           if b > a], spans)
        per_op: Dict[str, float] = defaultdict(float)
        for name, a, b in dv.ops:
            if b > lo and a < hi:
                per_op[name] += (min(b, hi) - max(a, lo)) * 1e-9
        per_mod: Dict[str, float] = defaultdict(float)
        per_run: Dict[str, int] = defaultdict(int)
        for name, a, b in dv.modules:
            if a >= lo and b <= hi:          # whole runs inside the window
                per_mod[name] += (b - a) * 1e-9
                per_run[name] += 1
        for src, dst in ((per_op, op_tot), (per_mod, mod_tot),
                         (per_run, run_tot)):
            for k, v in src.items():
                dst[k] += v
        op_dev.append(dict(per_op))
        mod_dev.append(dict(per_mod))
        run_dev.append(dict(per_run))
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy, op_s=dict(op_tot),
                   op_s_by_device=op_dev, module_s=dict(mod_tot),
                   module_runs=dict(run_tot), module_s_by_device=mod_dev,
                   module_runs_by_device=run_dev, gaps=gaps, spans=spans)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]
