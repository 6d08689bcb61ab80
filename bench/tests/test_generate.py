"""The traffic generator: the same seed gives the same inputs, and every
seed offers the same work."""

from __future__ import annotations

import numpy as np

from conftest import REPO, TINY_GRADS, TINY_POISSON

import generate
import spec as bench_spec

BIG_SEED = 2 ** 31 + 12345
REF = bench_spec.Benchmark(REPO).reference("stablelm-1.6b-gradsq")


def _stream(seed):
    lay = REF.layout(TINY_GRADS)
    x, ids = generate.segments(seed, [r for _, _, r in lay],
                               [v for _, v, _ in lay], TINY_GRADS["width"],
                               TINY_GRADS["values"])
    return np.asarray(x), np.asarray(ids), lay


def test_segments_are_a_function_of_the_seed():
    a, ia, _ = _stream(BIG_SEED)
    b, ib, _ = _stream(BIG_SEED)
    c, ic, _ = _stream(BIG_SEED + 1)
    assert np.array_equal(a, b) and np.array_equal(ia, ib)
    assert not np.array_equal(a, c) and np.array_equal(ia, ic)
    assert a.dtype == np.float32 and ia.dtype == np.int32


def test_segments_sizes_and_padding():
    x, ids, lay = _stream(7)
    d = TINY_GRADS["width"]
    assert x.shape == (sum(r for _, _, r in lay), d)
    # back-to-back segments in order, each its rows long
    assert np.array_equal(ids, np.repeat(np.arange(len(lay)),
                                         [r for _, _, r in lay]))
    for s, (_, values, _) in enumerate(lay):
        flat = x[ids == s].ravel()
        assert np.all(flat[:values] != 0) and np.all(flat[values:] == 0)
        # one scale per segment, inside the configured range
        rms = np.sqrt(np.mean(flat[:values].astype(np.float64) ** 2))
        assert 10 ** -4.6 < rms < 10 ** -0.4


def test_open_poisson_same_work_for_every_seed():
    seconds = 10.0
    a = generate.open_poisson(TINY_POISSON, BIG_SEED, seconds, 256)
    b = generate.open_poisson(TINY_POISSON, BIG_SEED, seconds, 256)
    c = generate.open_poisson(TINY_POISSON, 3, seconds, 256)
    assert len(a) == round(TINY_POISSON["rate_per_s"] * seconds) == len(c)
    assert [(r.due_s, r.prompt, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.prompt, r.max_new_tokens) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]
    # the same arrivals and sizes, other token ids
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in c]
    assert 0 < a[0].due_s and a[-1].due_s < seconds
    p = TINY_POISSON["prompt_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in a)
    assert all(1 <= t < 256 for r in a for t in r.prompt)
