"""The engine's cache-update contract (docs/serving.md): a decode step
appends one row per active slot to the attention caches and leaves every
other row, and every idle slot's length, bitwise as it was; recurrent
states of idle slots are kept by a select.  Checked on the smoke
configurations of a dense GQA model, a sliding-window (ring cache) model,
the MLA expert share and an SSM-bearing model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import forward, init_params
from repro.models.attention import KVCache, MLACache
from repro.serve.engine import Engine, Request

ARCHS = ["stablelm-1.6b", "mixtral-8x22b", "deepseek-v2-lite-16b",
         "jamba-v0.1-52b"]
MAX_LEN = 24
SLOTS = 4
PROMPTS = {0: [5, 6, 7, 8, 9, 10], 2: [11, 12, 13]}    # slot -> prompt
ACTIVE = np.array([True, False, True, False])
I32 = jnp.int32


@pytest.fixture(scope="module", params=ARCHS)
def served(request):
    cfg = get_smoke_config(request.param)
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def stepped(served):
    """(cfg, params, caches before, caches after) one decode step."""
    return (*served, *_decode_once(*served))


def _prefill(eng, slot, prompt):
    """The slot's prompt through the engine's own prefill program; the
    first token is not sampled."""
    if eng._extend_ok:
        c = eng.prefill_chunk
        for start in range(0, len(prompt), c):
            piece = prompt[start:start + c]
            toks = np.zeros((1, c), np.int32)
            toks[0, :len(piece)] = piece
            _, eng._caches, _ = eng._prefill_chunk(
                eng.params, eng._caches, I32(slot), jnp.asarray(toks),
                I32(start), I32(len(piece)))
    else:
        _, eng._caches = eng._classic_prefill(
            eng.params, eng._caches, I32(slot),
            jnp.asarray([prompt], np.int32))


def _decode_once(cfg, params):
    """(caches before, caches after) one decode step in which slots 0
    and 2 decode and slots 1 and 3 are idle."""
    eng = Engine(cfg, params, max_len=MAX_LEN, max_batch=SLOTS,
                 prefill_chunk=4)
    for slot, prompt in PROMPTS.items():
        _prefill(eng, slot, prompt)
    before = jax.tree.map(np.asarray, eng._caches)
    tok = np.zeros((SLOTS, 1), np.int32)
    pos = np.zeros(SLOTS, np.int32)
    for slot, prompt in PROMPTS.items():
        tok[slot, 0], pos[slot] = 3 + slot, len(prompt)
    _, eng._caches, _ = eng._decode(eng.params, jnp.asarray(tok),
                                    eng._caches, jnp.asarray(pos),
                                    jnp.asarray(ACTIVE))
    after = jax.tree.map(np.asarray, eng._caches)
    return before, after


def test_idle_slots_keep_their_rows_and_length(stepped):
    """Every leaf of an idle slot — attention rows, length, recurrent
    state — is bitwise what it was before the step."""
    _, _, before, after = stepped
    idle = ~ACTIVE
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after),
                    strict=True):
        np.testing.assert_array_equal(a[:, idle], b[:, idle])


def test_active_slots_append_one_row_in_place(stepped):
    """An active slot's attention cache grows by one row at its length
    (its ring index on a sliding-window cache) and by one in length;
    every other row is bitwise unchanged, and the new row is the one a
    prefill of prompt plus token puts there."""
    cfg, params, before, after = stepped
    impl = "held" if cfg.moe is not None and cfg.moe.is_share else "dense"
    refs = {slot: forward(params, cfg,
                          tokens=jnp.asarray([prompt + [3 + slot]], I32),
                          mode="prefill", moe_impl=impl)[1]
            for slot, prompt in PROMPTS.items()}
    checked = 0
    for i, (cb, ca) in enumerate(zip(before, after, strict=True)):
        core_b, core_a = cb["core"], ca["core"]
        if not isinstance(core_b, (KVCache, MLACache)):
            continue
        for slot, prompt in PROMPTS.items():
            n = len(prompt)
            np.testing.assert_array_equal(core_a.length[:, slot],
                                          core_b.length[:, slot] + 1)
            for arr_b, arr_a, arr_ref in zip(core_b[:-1], core_a[:-1],
                                             refs[slot][i]["core"][:-1]):
                t = arr_b.shape[2]
                row = n % t
                others = np.arange(t) != row
                np.testing.assert_array_equal(arr_a[:, slot, others],
                                              arr_b[:, slot, others])
                want = np.asarray(arr_ref)[:, 0, row]
                np.testing.assert_allclose(arr_a[:, slot, row], want,
                                           rtol=2e-3, atol=2e-3)
                checked += 1
    assert checked


def test_batched_greedy_tokens_match_alone(served):
    """Requests admitted mid-stream beside others give the greedy tokens
    each gives alone through the same engine (the sequential oracle)."""
    cfg, params = served
    eng = Engine(cfg, params, max_len=MAX_LEN, max_batch=SLOTS,
                 prefill_chunk=4)
    reqs = [Request(prompt=[7 + i, 3, 9, 4 + i][:2 + i % 3],
                    max_new_tokens=4 + i % 3) for i in range(5)]
    rids = [eng.submit(r, arrival=float(2 * i)) for i, r in enumerate(reqs)]
    results = eng.run()
    assert [r.rid for r in results] == rids
    for req, res in zip(reqs, results):
        assert res.tokens == eng.generate([req])[0].tokens
