"""The program-span reduction (``bench/program_spans.py``) on synthetic
spans, and on the small v5e trace, which carries no program span."""

from __future__ import annotations

import gzip
from pathlib import Path

import pytest

import spec as bench_spec

HERE = Path(__file__).resolve().parent
P = bench_spec.load_module(HERE.parent / "program_spans.py")
T = P.T

WINDOW = {"bench.window": [(0, 1000)]}


def unpack(tmp_path, name):
    path = tmp_path / name.replace(".gz", "")
    path.write_bytes(gzip.decompress((HERE / "data" / name).read_bytes()))
    return path


def test_small_trace_reads_as_before(tmp_path):
    path = unpack(tmp_path, "small.xplane.pb.gz")
    assert P.read(path) == {}
    rep = P.report(path)
    summary = T.summarize(path)
    assert rep["idle_gaps"] == summary.top_gaps()
    assert rep["window_s"] == summary.window_s
    for k in ("engine_host_ms", "queue_wait_ms", "prefill_wait_ms",
              "front_door_us"):
        assert rep[k] is None
    assert rep["spans"] == {}
    assert 0 < rep["short_gap_idle_share"] <= 1


def test_gaps_go_to_the_innermost_of_mixed_spans():
    # the device runs [100, 200) and [600, 700); the host is in a reduce
    # call during the first gap, in the engine's prefill phase and then
    # the benchmark's client during the others
    device = T.Device("/device:TPU:0", [("op", 100, 200), ("op", 600, 700)],
                      [])
    spans = {**WINDOW, "bench.call": [(0, 300)],
             "bench.client": [(800, 1000)]}
    program = {"repro.reduce": [(20, 290, {})],
               "repro.reduce.dispatch": [(40, 90, {})],
               "repro.engine.step": [(300, 790, {})],
               "repro.engine.prefill": [(350, 600, {})]}
    got = P.idle_gaps([device], spans, program)
    assert [n for n, _ in got] == ["repro.reduce.dispatch",
                                   "repro.engine.prefill", "bench.client"]
    assert [s for _, s in got] == pytest.approx([1e-7, 4e-7, 3e-7])
    # without the program's spans the same gaps read as before
    assert P.idle_gaps([device], spans, {}) == T.attribute(
        [(0, 100), (200, 600), (700, 1000)], spans)


def test_engine_readings():
    step = [(100, 200, {"prefill_chunks": 1, "decode_slots": 3}),
            (200, 400, {"prefill_chunks": 0, "decode_slots": 4})]
    program = {
        "repro.engine.step": step + [(950, 1100, {})],   # ends outside
        "repro.engine.sync": [(150, 190, {}), (300, 320, {}),
                              (330, 390, {}), (960, 990, {})],
        "repro.engine.first_token": [
            (140, 195, {"queue_ms": 2.0, "prefill_ms": 900.0, "chunks": 9}),
            (320, 395, {"queue_ms": 4.0, "prefill_ms": 1100.0,
                        "chunks": 11})],
    }
    mine = P.in_window(program, WINDOW["bench.window"][0])
    assert len(mine["repro.engine.step"]) == 2
    # (100 - 40) and (200 - 80) ns of host time, in ms
    assert P.engine_host_ms(mine) == pytest.approx(1e-6 * 90)
    assert P.queue_wait_ms(mine) == pytest.approx(3.0)
    assert P.prefill_wait_ms(mine) == pytest.approx(1000.0)
    assert P.front_door_us(mine) is None


def test_front_door_reading():
    program = {"repro.reduce": [(0, 600_000, {}), (700_000, 1_500_000, {}),
                                (-5, 10, {})],
               "repro.reduce.pre": [(100, 200, {})]}
    mine = P.in_window(program, (0, 2_000_000))
    assert P.front_door_us(mine) == pytest.approx(700.0)
    assert P.engine_host_ms(mine) is None
    assert P.queue_wait_ms({}) is None and P.prefill_wait_ms({}) is None


def test_serving_trace_names_the_idle_gaps(tmp_path):
    """0.4 s of the serving cell's traced window, recorded on a v5e with
    the program spans (seed 3130000011) and cut down to what the
    reductions read: the device's ``XLA Ops`` and ``XLA Modules`` lines,
    the host's ``bench.*`` and ``repro.*`` spans, ``bench.window`` set to
    the cut; the Python tracer's events left out."""
    path = unpack(tmp_path, "serve.xplane.pb.gz")
    rep = P.report(path)
    assert rep["window_s"] == pytest.approx(0.4)
    assert rep["engine_host_ms"] == pytest.approx(5.6064, abs=1e-3)
    assert rep["queue_wait_ms"] == pytest.approx(0.0437, abs=1e-3)
    assert rep["prefill_wait_ms"] == pytest.approx(545.503, abs=1e-2)
    assert rep["front_door_us"] is None
    assert rep["spans"]["repro.engine.step"][0] == 3
    gaps = dict(rep["idle_gaps"])
    assert all(n.startswith(("bench.", "repro.engine.")) for n in gaps)
    assert gaps.get("host:unannotated", 0.0) < 0.1 * rep["idle_s"]
    assert sum(gaps.values()) == pytest.approx(rep["idle_s"])
    # the benchmark's own reduction reads the same trace as before
    summary = T.summarize(path)
    assert summary.window_s == pytest.approx(rep["window_s"])
    assert summary.window_s - summary.busy_s[0] == pytest.approx(
        rep["idle_s"])
    assert summary.modules_matching([r"_decode_fn"])[1] >= 3
