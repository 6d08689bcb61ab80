"""The trace reduction, on a small trace recorded on a TPU v5e: three
eager exact2 ``repro.reduce`` calls (8192 x 128, S=8), each in
``bench.call`` > ``bench.dispatch`` / ``bench.block`` spans, each
followed by a 2 ms host sleep and a small jitted matmul, all inside
``bench.window``."""

from __future__ import annotations

import gzip
from pathlib import Path

import pytest

import spec as bench_spec

HERE = Path(__file__).resolve().parent
T = bench_spec.load_module(HERE.parent / "trace.py", "bench_trace")


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small.xplane.pb"
    path.write_bytes(gzip.decompress(
        (HERE / "data" / "small.xplane.pb.gz").read_bytes()))
    return T.summarize(path)


def test_busy_and_idle(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(0.014321239)
    assert 0 < summary.busy_s[0] < summary.window_s
    assert summary.busy_s[0] == pytest.approx(0.001074985)
    assert summary.idle_pct == pytest.approx(
        100 * (1 - 0.001074985 / 0.014321239))


def test_per_op_and_per_program_time(summary):
    # device events sit about 1.3 ms earlier than the host's here, so the
    # first call's program falls before the window opens
    assert summary.module_runs == {"jit__dispatch": 2, "jit__lambda": 3}
    secs, runs = summary.modules_matching([r"_dispatch"])
    assert runs == 2 and secs == pytest.approx(0.000733156)
    kernel = summary.ops_matching([r'custom_call_target="tpu_custom_call"'])
    assert 0 < kernel < secs
    assert sum(summary.op_s.values()) == pytest.approx(
        sum(s for _, s in summary.top_ops(1000)))
    names = [n for n, _ in summary.top_ops(3)]
    assert names[0] == "fusion" and all(" " not in n for n in names)
    assert summary.ops_named(r"all-reduce", 0) == 0


def test_gaps_are_put_down_to_host_spans(summary):
    gaps = dict(summary.top_gaps())
    assert set(gaps) <= {"bench.call", "bench.dispatch", "bench.block",
                         "host:unannotated"}
    assert sum(gaps.values()) == pytest.approx(
        summary.window_s - summary.busy_s[0])
    assert gaps["host:unannotated"] > 0.002        # the sleeps, at least


def test_interval_helpers():
    assert T.union([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert T.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
    spans = {"bench.window": [(0, 100)], "bench.call": [(0, 50)],
             "bench.block": [(10, 20)]}
    got = T.attribute([(12, 14), (30, 40), (60, 70)], spans)
    assert got == [("bench.block", 2e-9), ("bench.call", 1e-8),
                   ("host:unannotated", 1e-8)]
    assert T.op_name("%fusion.3 = f32[8] fusion(%all-reduce.1)|x") == \
        "fusion.3"
