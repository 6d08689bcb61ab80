"""The plain references agree with straightforward arithmetic, and the
model reference computes what the program's model computes (both in
float32, at a small size, on the CPU)."""

from __future__ import annotations

import json
import math

import numpy as np
import pytest

from conftest import REPO, TINY_GRADS, TINY_MODEL

import generate
import spec as bench_spec

BENCH = bench_spec.Benchmark(REPO)


GRADS = json.loads((REPO / "bench/configs/stablelm-1.6b-gradsq.json")
                   .read_text())


def test_published_tensors_of_stablelm_2_1_6b():
    ref = BENCH.reference("stablelm-1.6b-gradsq")
    t = ref.tensors(GRADS["model"])
    # embedding, 24 x (2 LayerNorms of weight and bias, q/k/v weight and
    # bias, o, gate/up/down), final LayerNorm, untied head
    assert len(t) == 1 + 24 * 14 + 2 + 1
    assert sum(n for _, n in t) == 1644515328
    assert t[0] == ("embed_tokens", 100352 * 2048)
    assert t[-1] == ("lm_head", 100352 * 2048)
    lay = ref.layout(GRADS)
    assert sum(r for _, _, r in lay) == 1605972
    assert all(v == -(-n // 8) and r == -(-v // 128)
               for (_, n), (_, v, r) in zip(t, lay))


def test_sums_of_squares_are_exact_and_ulps():
    ref = BENCH.reference("stablelm-1.6b-gradsq")
    lay = ref.layout(TINY_GRADS)
    x, ids = generate.segments(99, [r for _, _, r in lay],
                               [v for _, v, _ in lay], TINY_GRADS["width"],
                               TINY_GRADS["values"])
    x, ids = np.asarray(x), np.asarray(ids)
    total, largest = ref.sums(x, ids, len(lay))
    assert largest == float(np.max(x * x))
    for s in (0, 1, len(lay) - 1):
        sq = (x[ids == s] * x[ids == s]).astype(np.float64)
        for j in range(x.shape[1]):
            assert total[s, j] == pytest.approx(math.fsum(sq[:, j]),
                                                rel=2 ** -40)
    r = np.array([[1.0, 0.0]])
    out = np.array([[1.0 + 2 * 2.0 ** -23, 0.0]], np.float32)
    assert np.array_equal(ref.ulps(out, r), [[2.0, 0.0]])
    assert np.array_equal(ref.relative(out, r), [[4.0, 0.0]])
    # the exact2 bound: 1 ulp, plus 2^-71 of the largest term per term
    assert ref.bound_units(out, r, [1], 0.0)[0, 0] == 2.0
    tiny = np.array([[2.0 ** -80]])
    assert ref.bound_units(np.zeros((1, 1), np.float32), tiny, [2],
                           2.0 ** -10)[0, 0] == pytest.approx(
        2.0 ** -80 / (np.spacing(np.float32(2.0 ** -80)) + 2 * 2.0 ** -81))


def test_model_reference_matches_the_program_in_float32():
    import jax
    import jax.numpy as jnp
    from repro.models import forward
    sys_drv = BENCH.driver("serve")
    ref = BENCH.reference("stablelm-1.6b")
    m = TINY_MODEL["model"]
    params = ref.make_params(generate.jax_key(5), m)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg = sys_drv.program_config("tiny", m).scaled(dtype="float32")
    toks = np.random.default_rng(0).integers(1, m["vocab_size"], 24)
    logits = np.asarray(forward(params32, cfg, tokens=jnp.asarray(toks)[None],
                                mode="train", moe_impl="dense")[0][0],
                        np.float64)
    nxt = np.roll(toks, -1)
    best, picked, lse, top = (np.asarray(a, np.float64) for a in
                              ref.next_token_stats(params, m,
                                                   jnp.asarray(toks),
                                                   jnp.asarray(nxt)))
    np.testing.assert_allclose(best, logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(picked, logits[np.arange(24), nxt],
                               atol=2e-4)
    np.testing.assert_allclose(
        lse, np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1))
        + logits.max(-1), atol=2e-4)
    assert np.array_equal(top, logits.argmax(-1))


def test_fp8_control_is_coarser():
    import jax.numpy as jnp
    ref = BENCH.reference("stablelm-1.6b")
    m = TINY_MODEL["model"]
    params = ref.make_params(generate.jax_key(5), m)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 256, 32))
    full = np.asarray(ref.next_token_stats(params, m, toks, toks)[0])
    low = np.asarray(ref.next_token_stats(params, m, toks, toks,
                                          precision="fp8")[0])
    assert 1e-3 < np.max(np.abs(full - low)) < 1.0
