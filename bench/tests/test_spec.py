"""``BENCHMARK.json`` keeps the contract, and its parts are found by
name: a new configuration, mix or metric is new files only."""

from __future__ import annotations

import json
import re

import pytest

from conftest import BENCH, REPO, make_tiny_root, run_tiny

import harness
import spec as bench_spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return bench_spec.Benchmark(REPO)


def test_top_level_keys_and_limits(bench):
    s = bench.spec
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["paths"] == ["bench"] and s["command"][1] == "bench/run.py"
    assert isinstance(s["run_seconds"], int) and 1 <= s["run_seconds"] <= 51
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    # a full check of 24 cells fits its 43200 s
    assert 2 + 14 * 24 * (s["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_entries_have_exactly_their_keys(bench):
    s = bench.spec
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/") and (REPO / c["file"]).exists()
        assert all(NAME.match(k) for k in c["reduced"])
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in s["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in s["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [e["name"] for k in ("configs", "workloads") for e in s[k]]
    metrics = [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert len(set(names)) == len(names) and len(set(metrics)) == len(metrics)
    assert all(NAME.match(n) for n in names)


def test_every_cell_reports_what_it_must(bench):
    s = bench.spec
    four = [w for w in s["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(s["workloads"]) // 2)
    used = {w["config"] for w in s["workloads"]}
    assert used == {c["name"] for c in s["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(set(pairs)) == len(pairs)
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        assert m["moves"] in e2e
    for w in s["workloads"]:
        reported = {m["name"] for m in bench.end_to_end(w["name"])}
        assert "setup_s" in reported and len(reported) >= 2
        layers = bench.per_layer(w["name"])
        assert layers
        assert all(m["moves"] in reported for m in layers)


def test_parts_are_found_by_name(bench):
    for w in bench.spec["workloads"]:
        cfg = bench.config(w["config"])
        assert (BENCH / "drivers" / f"{cfg['driver']}.py").exists()
        bench.traffic(w["traffic"])
        ref = bench.reference(w["config"])
        assert ref.__doc__
    for m in bench.spec["per_layer"]:
        assert callable(bench.reader(m["name"]))


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path):
    """A later change adds a configuration, a traffic mix and a per-layer
    metric as new files and entries; the harness runs the new cell and
    reads the new metric without an edit to any file that was there."""
    root = make_tiny_root(tmp_path)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    new_cfg = json.loads((root / "bench/configs/tiny-grads.json")
                         .read_text())
    new_cfg["width"] = 16
    (root / "bench/configs/wide-grads.json").write_text(json.dumps(new_cfg))
    (root / "bench/configs/wide-grads.py").write_text(
        (root / "bench/configs/tiny-grads.py").read_text())
    mix = json.loads((root / "bench/traffic/t-exact2.json").read_text())
    mix["keep_outputs"] = 2
    (root / "bench/traffic/two-kept.json").write_text(json.dumps(mix))
    (root / "bench/metrics/calls_seen.py").write_text(
        "def read(run):\n    return run.facts['calls']\n")
    spec["configs"].append({"name": "wide-grads", "source": "test",
                            "file": "bench/configs/wide-grads.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "t.new", "config": "wide-grads",
                              "traffic": "two-kept", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "calls_seen", "unit": "calls",
                              "better": "higher", "source": "host_clock",
                              "layer": "front door", "moves":
                              "reduce_rows_per_s", "workloads": ["t.new"]})
    for m in spec["end_to_end"]:
        if m["name"] in ("reduce_rows_per_s",):
            m["workloads"].append("t.new")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    line = run_tiny(root, "t.new", seconds=0.5)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"setup_s", "reduce_rows_per_s"}
    bench = bench_spec.Benchmark(root)
    assert [m["name"] for m in bench.per_layer("t.new")] == ["calls_seen"]
    run = harness.LayerRun(facts={"calls": 7}, summary=None, peaks={})
    assert harness.layer_metrics(bench, "t.new", run) == {
        "calls_seen": {"value": 7.0, "unit": "calls"}}


def test_a_split_metric_shares_its_quantitys_reader(tmp_path):
    """``<quantity>.<part>`` without a file of its own is read by
    ``metrics/<quantity>.py``; a file of its own wins."""
    root = make_tiny_root(tmp_path)
    bench = bench_spec.Benchmark(root)
    assert bench.reader("device_idle.train") is bench.reader("device_idle")
    (root / "bench/metrics/device_idle.own.py").write_text(
        "def read(run):\n    return 1.0\n")
    assert bench.reader("device_idle.own")(None) == 1.0
    with pytest.raises(FileNotFoundError):
        bench.reader("no_such_metric")
