"""Each cell's control, put in the program's place, comes out as not
correct through the harness's own comparison, at a size the CPU holds:
the step below the stated precision fails what the program passes."""

from __future__ import annotations

import pytest

from conftest import run_tiny

import controls
import spec as bench_spec


@pytest.mark.parametrize("cell", ["t.exact2", "t.fast", "t.serve"])
@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_control_in_the_programs_place_is_not_correct(tiny_root, monkeypatch,
                                                      cell, seed):
    bench = bench_spec.Benchmark(tiny_root)
    controls.install(bench, bench.cell(cell), monkeypatch.setattr)
    line = run_tiny(tiny_root, cell, seed=seed, seconds=0.6)
    assert line["correct"] is False, line["checks"]


def test_high_terms_keep_sixteen_bits():
    import jax.numpy as jnp
    import numpy as np
    x = jnp.asarray(np.float32(1 / 3) * (1 + np.arange(64, dtype=np.float32)))
    err = np.abs(np.asarray(controls.high_terms(x), np.float64)
                 - np.asarray(x, np.float64)) / np.asarray(x, np.float64)
    assert 0 < err.max() <= 2.0 ** -17
