"""Shared fixtures of the benchmark's own tests (``python -m pytest
bench/tests``; tier-1 collects only ``tests/``).

``tiny_root`` is a scratch checkout: a copy of ``bench/`` with a
``BENCHMARK.json`` of its own whose cells use small configurations,
written as new files, the way a later change adds a configuration, a
mix or a metric.  ``run_tiny`` drives a whole run there on the CPU,
past the harness's look for a chip.
"""

from __future__ import annotations

import io
import json
import shutil
import sys
from contextlib import redirect_stdout
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

TINY_GRADS = {
    "driver": "reduce", "source": "test", "deployment": "test",
    "model": {"hidden_size": 64, "intermediate_size": 96,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "num_hidden_layers": 2, "vocab_size": 512,
              "use_qkv_bias": True, "norm": "layernorm",
              "tie_word_embeddings": False},
    "shards": 4, "width": 8, "block_size": 256, "dtype": "float32",
    "op": "sumsq", "values": {"log10_scale_lo": -4.0, "log10_scale_hi": -1.0,
                              "scale_seed": 11},
}

TINY_MODEL = {
    "driver": "serve", "source": "test", "deployment": "test",
    "model": {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "num_hidden_layers": 2, "vocab_size": 256,
              "max_position_embeddings": 64, "rope_theta": 10000,
              "partial_rotary_factor": 1.0, "use_qkv_bias": False,
              "norm": "rmsnorm", "layer_norm_eps": 1e-5,
              "hidden_act": "silu", "tie_word_embeddings": False,
              "torch_dtype": "bfloat16"},
    "serving": {"max_batch": 4, "max_len": 64, "prefill_chunk": 8,
                "page_size": 8, "logprob_policy": "compensated"},
    "limits": {"token_gap_max": 0.01, "logprob_mean_err": 0.015},
}

TINY_POISSON = {
    "rate_per_s": 12.0, "base_seed": 7,
    "prompt_tokens": {"median": 12, "sigma": 0.6, "min": 4, "max": 40},
    "output_tokens": {"median": 4, "sigma": 0.5, "min": 2, "max": 12},
    "check_requests": 3,
}


def _traffic(policy, limits, **more):
    return {"policy": policy, "keep_outputs": 4, "limits": limits, **more}


def tiny_spec() -> dict:
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [
        {"name": "tiny-grads", "source": "test",
         "file": "bench/configs/tiny-grads.json", "reduced": [],
         "why": "test"},
        {"name": "tiny-lm", "source": "test",
         "file": "bench/configs/tiny-lm.json", "reduced": [],
         "why": "test"}]
    spec["workloads"] = [
        {"name": "t.exact2", "config": "tiny-grads", "traffic": "t-exact2",
         "chips": 1, "why": "test"},
        {"name": "t.fast", "config": "tiny-grads", "traffic": "t-fast",
         "chips": 1, "why": "test"},
        {"name": "t.serve", "config": "tiny-lm", "traffic": "t-poisson",
         "chips": 1, "why": "test"}]
    tiny = {"reduce.exact2.gradsq": "t.exact2", "reduce.fast.gradsq": "t.fast",
            "serve.stablelm-1.6b.poisson": "t.serve"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [tiny[w] for w in m["workloads"] if w in tiny]
    return spec


def make_tiny_root(tmp_path: Path) -> Path:
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    configs, traffic = root / "bench" / "configs", root / "bench" / "traffic"
    shutil.copy(configs / "stablelm-1.6b-gradsq.py", configs / "tiny-grads.py")
    shutil.copy(configs / "stablelm-1.6b.py", configs / "tiny-lm.py")
    files = {configs / "tiny-grads.json": TINY_GRADS,
             configs / "tiny-lm.json": TINY_MODEL,
             traffic / "t-exact2.json": _traffic(
                 "exact2", {"bound_err_max": 1.0, "outputs_differing": 0}),
             traffic / "t-fast.json": _traffic(
                 "fast", {"rel_err_max": 64.0, "outputs_differing": 0},
                 in_flight=3),
             traffic / "t-poisson.json": TINY_POISSON,
             root / "BENCHMARK.json": tiny_spec()}
    for path, data in files.items():
        path.write_text(json.dumps(data))
    return root


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    return make_tiny_root(tmp_path)


def run_tiny(root: Path, workload: str, *, seed: int = 12345,
             seconds: float = 1.0, trace: int = 0) -> dict:
    """One whole run of ``workload`` under ``root`` on the CPU; the
    result line's object."""
    import harness
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          root=root, require_chip=False)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
