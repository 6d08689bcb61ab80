"""The useful-work functions and the peaks table."""

from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import REPO, TINY_MODEL

import spec as bench_spec
import useful

STABLELM = json.loads((REPO / "bench/configs/stablelm-1.6b.json")
                      .read_text())["model"]


def test_reduce_bytes():
    # 2^20 x 128 f32 rows, their int32 labels, a 64 x 128 f32 result
    assert useful.reduce_bytes(1 << 20, 128, 64) == \
        (1 << 20) * 128 * 4 + (1 << 20) * 4 + 64 * 128 * 4
    assert useful.reduce_bytes(1 << 20, 128, 64) == 541097984


def test_matmul_params_count_the_reference_weights():
    bench = bench_spec.Benchmark(REPO)
    ref = bench.reference("stablelm-1.6b")
    import jax
    m = TINY_MODEL["model"]
    shapes = jax.eval_shape(lambda: ref.make_params(jax.random.key(0), m))
    blocks = shapes["blocks"][0]
    mats = [blocks["core"][k] for k in ("wq", "wk", "wv", "wo")] + \
        [blocks["mlp"][k] for k in ("wi", "wg", "wo")] + [shapes["lm_head"]]
    assert useful.matmul_params(m) == sum(int(np.prod(a.shape)) for a in mats)


def test_stablelm_sizes():
    # 24 layers x (4 x 2048^2 + 3 x 2048 x 5632) + 2048 x 100352
    p = useful.matmul_params(STABLELM)
    assert p == 24 * (4 * 2048 ** 2 + 3 * 2048 * 5632) + 2048 * 100352
    assert useful.kv_bytes_per_token(STABLELM) == 2 * 24 * 2048 * 2
    flops, nbytes = useful.decode_step(STABLELM, 16, 16 * 500)
    assert flops == 2 * p * 16
    assert nbytes == 2 * p + 16 * 500 * 196608
    assert useful.model_flops(STABLELM, 10) == 20 * p


def test_peaks_table():
    v5e = useful.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        useful.peaks("cpu")
