"""A whole run, past the harness's look for a chip, with the timed path
broken underneath: ``correct`` comes out false once for each fault a
cell can have, and true on the sound path.

  * an answer altered where it is produced (a sum off by a few ulps; a
    served token replaced by its neighbour in the vocabulary);
  * half of the rows left out of a reduction.

No cell runs on more than one chip, so none can leave out an exchange
between chips.
"""

from __future__ import annotations

import pytest

from conftest import run_tiny


@pytest.fixture
def root(tiny_root):
    return tiny_root


def test_sound_runs_are_correct(root):
    for cell in ("t.exact2", "t.fast", "t.serve"):
        line = run_tiny(root, cell, seconds=0.6)
        assert line["correct"] is True, (cell, line["checks"])
        assert line["failed"] == 0 and line["attempted"] > 0
        assert line["compiles_in_window"] == 0
        assert list(line)[-1] == "checks"


def _reduce_patched(monkeypatch, broken):
    import repro.reduce as R
    real = R.reduce
    monkeypatch.setattr(R, "reduce", lambda *a, **k: broken(real, *a, **k))


@pytest.mark.parametrize("cell", ["t.exact2", "t.fast"])
def test_altered_answer_fails(root, monkeypatch, cell):
    _reduce_patched(monkeypatch,
                    lambda real, *a, **k: real(*a, **k) * (1 + 2.0 ** -12))
    line = run_tiny(root, cell, seconds=0.5)
    assert line["correct"] is False, line["checks"]


@pytest.mark.parametrize("cell", ["t.exact2", "t.fast"])
def test_half_the_rows_left_out_fails(root, monkeypatch, cell):
    import jax.numpy as jnp

    def half(real, values, segment_ids, **k):
        keep = jnp.arange(segment_ids.shape[0]) % 2 == 0
        return real(values, segment_ids=jnp.where(keep, segment_ids, -1),
                    **k)

    _reduce_patched(monkeypatch, half)
    line = run_tiny(root, cell, seconds=0.5)
    assert line["correct"] is False, line["checks"]


def test_altered_token_fails(root, monkeypatch):
    from repro.serve import engine as E
    real_init = E.Engine.__init__

    def init(self, cfg, *a, **k):
        real_init(self, cfg, *a, **k)
        sample = self._sample

        def shifted(*args):
            tok, lp = sample(*args)
            return (tok + 1) % cfg.vocab, lp

        self._sample = shifted

    monkeypatch.setattr(E.Engine, "__init__", init)
    line = run_tiny(root, "t.serve", seconds=0.6)
    assert line["correct"] is False, line["checks"]
