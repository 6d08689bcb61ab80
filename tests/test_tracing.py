"""The program's profiler spans: ``repro.engine.*`` in the serving loop
and ``repro.reduce*`` in the reduce front door, recorded with
``jax.profiler.trace`` and read back with ``ProfileData``."""

import glob
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro
from repro.configs import get_smoke_config
from repro.models import init_params
from repro.serve.engine import Engine, Request

REQUESTS = [Request(prompt=list(range(1, 20)), max_new_tokens=3),
            Request(prompt=[5, 6, 7], max_new_tokens=2),
            Request(prompt=list(range(3, 12)), max_new_tokens=4)]


def record(tmp_path, fn):
    """(fn's result, the ``repro.*`` spans it recorded: name -> sorted
    [(start_ns, end_ns, stats)])."""
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
        jax.block_until_ready(out)
    spans = defaultdict(list)
    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans[e.name].append((e.start_ns,
                                          e.start_ns + e.duration_ns,
                                          dict(e.stats)))
    return out, {k: sorted(v, key=lambda s: s[0]) for k, v in spans.items()}


def inside(child, parents):
    return any(a <= child[0] and child[1] <= b for a, b, _ in parents)


@pytest.fixture(scope="module")
def engine():
    cfg = get_smoke_config("stablelm-1.6b")
    eng = Engine(cfg, init_params(jax.random.PRNGKey(0), cfg), max_len=64,
                 max_batch=2, prefill_chunk=8)
    eng.generate(REQUESTS)                       # compile outside the trace
    return eng


def _serve(engine, steps):
    for r in REQUESTS:
        engine.submit(r)
    return engine.run(on_step=lambda eng, clock: steps.append(clock))


def test_engine_step_span_per_loop_iteration(engine, tmp_path):
    steps = []
    _, spans = record(tmp_path, lambda: _serve(engine, steps))
    step = spans["repro.engine.step"]
    assert len(step) == len(steps) > 0
    for name in ("admit", "prefill", "decode"):
        phase = spans[f"repro.engine.{name}"]
        assert len(phase) == len(step)
        assert all(inside(s, step) for s in phase)
    assert spans["repro.engine.sync"]
    assert all(inside(s, step) for s in spans["repro.engine.sync"])
    assert sum(st["prefill_chunks"] for _, _, st in step) == 3 + 1 + 2
    assert sum(st["decode_slots"] for _, _, st in step) == 2 + 1 + 3
    # the first token is sampled inside the prefill phase
    assert all(inside(s, spans["repro.engine.prefill"])
               for s in spans["repro.engine.first_token"])


def test_first_token_span_per_request(engine, tmp_path):
    _, spans = record(tmp_path, lambda: _serve(engine, []))
    first = spans["repro.engine.first_token"]
    assert len(first) == len(REQUESTS)
    assert sorted(st["chunks"] for _, _, st in first) == [1, 2, 3]
    for a, b, st in first:
        assert st["queue_ms"] >= 0 and st["prefill_ms"] >= 0
        # the prefill wait ends inside the span
        assert st["prefill_ms"] * 1e6 >= b - a - 1e6
    # each first token waits on the device once
    assert sum(inside(s, first) for s in spans["repro.engine.sync"]) == \
        len(REQUESTS)


def test_engine_outputs_unchanged_by_profiler(engine, tmp_path):
    plain = engine.generate(REQUESTS)
    traced, spans = record(tmp_path, lambda: engine.generate(REQUESTS))
    assert spans["repro.engine.step"]
    assert [r.tokens for r in traced] == [r.tokens for r in plain]
    assert [r.mean_logprob for r in traced] == \
        [r.mean_logprob for r in plain]


@pytest.mark.parametrize("on_overflow", ["raise", "degrade"])
def test_reduce_span_with_children(tmp_path, on_overflow):
    x = jnp.arange(24.0).reshape(12, 2)
    ids = jnp.asarray([0, 0, 1, 1, 1, 2, 2, 2, 2, 0, 1, 2], jnp.int32)

    def call():
        return repro.reduce(x, segment_ids=ids, num_segments=3, op="sumsq",
                            policy="exact2", on_overflow=on_overflow)

    plain = call()
    out, spans = record(tmp_path, call)
    assert np.array_equal(np.asarray(out), np.asarray(plain))
    (top,) = spans["repro.reduce"]
    assert top[2] == {"rows": 12, "width": 2, "segments": 3,
                      "policy": "exact2", "op": "sumsq"}
    for name in ("repro.reduce.pre", "repro.reduce.dispatch"):
        (child,) = spans[name]
        assert inside(child, [top])


def test_reduce_span_under_jit_reads_no_device_value(tmp_path):
    # reading a tracer's value raises, so a stat that read a device value
    # would fail here
    f = jax.jit(lambda v: repro.reduce(v, op="sum", policy="exact2"))
    out, spans = record(tmp_path, lambda: f(jnp.ones((8, 3))))
    assert np.array_equal(np.asarray(out), np.full(3, 8.0, np.float32))
    (top,) = spans["repro.reduce"]
    assert top[2]["rows"] == 8 and top[2]["segments"] == 1


@pytest.mark.parametrize("policy, backend, domain", [
    ("exact2", "pallas", "block"), ("exact2", "blocked", "block"),
    ("fast", "blocked", "stream")])
def test_dispatch_span_says_where_the_domain_is_mapped(tmp_path, policy,
                                                       backend, domain):
    """The integer tiers map each block into their domain inside the
    executor; fast maps (casts) the whole stream before it."""
    x = jnp.arange(24.0).reshape(12, 2)

    def call():
        return repro.reduce(x, policy=policy, backend=backend)

    plain = call()
    out, spans = record(tmp_path, call)
    assert np.array_equal(np.asarray(out), np.asarray(plain))
    (dispatch,) = spans["repro.reduce.dispatch"]
    assert dispatch[2] == {"domain": domain}
