"""The one traffic generator: every mix is a JSON file of parameters.

Two kinds of traffic, each made from ``--seed`` alone:

  * ``segments``: the (N, D) f32 value stream and its (N,) labels of a
    segmented reduction, made on the device in one jitted call.  The
    segments' sizes and scales come from the configuration (the data a
    deployment holds); the seed draws the values.
  * ``open_poisson``: the requests of a served model, made on the host
    (a few hundred small lists).  The arrival times and the prompt and
    output lengths are drawn once from the mix's ``base_seed``; ``--seed``
    draws only the token ids, so every seed offers the same work at the
    same times.  (Putting the same sizes in another order per seed moved
    the 95th-percentile TTFT by 20% between seeds on a v5e.)

Seeds are any whole number: they are folded to 64 bits.
"""

from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


def seed64(seed: int) -> int:
    return int(seed) % (1 << 64)


def jax_key(seed: int):
    import jax
    s = seed64(seed)
    return jax.random.fold_in(jax.random.key(s & 0xFFFFFFFF), s >> 32)


# ---------------------------------------------------------------------------
# segmented-reduction streams
# ---------------------------------------------------------------------------


def segments(seed: int, rows: list, values: list, width: int, scale: dict):
    """(x (N, width) f32, ids (N,) int32) on the device, from ``seed``.

    Segment s is ``rows[s]`` back-to-back rows labeled s, holding
    ``values[s]`` values row-major, the rest of its last row zero.  The
    values are normal, scaled per segment by 10^u with u uniform in
    [``log10_scale_lo``, ``log10_scale_hi``) drawn once from
    ``scale["scale_seed"]``: the sizes and the scales are the same for
    every seed, which draws only the normal values.  (Scales drawn from
    the seed moved the fast tier's call time by 5% between seeds on a
    v5e, the same on every run of one seed.)
    """
    import jax
    import jax.numpy as jnp

    segs, n = len(rows), int(sum(rows))
    u = np.random.default_rng(int(scale["scale_seed"])).uniform(
        float(scale["log10_scale_lo"]), float(scale["log10_scale_hi"]), segs)
    rows_a = np.asarray(rows, np.int64)
    starts = np.concatenate([[0], np.cumsum(rows_a)[:-1]]).astype(np.int32)
    counts = np.asarray(values, np.int64)

    def make(key):
        ids = jnp.repeat(jnp.arange(segs, dtype=jnp.int32),
                         jnp.asarray(rows_a), total_repeat_length=n)
        pos = (jnp.arange(n, dtype=jnp.int32) - jnp.asarray(starts)[ids])
        col = jnp.arange(width, dtype=jnp.int32)
        valid = pos[:, None] * width + col[None, :] < \
            jnp.asarray(counts, jnp.int32)[ids][:, None]
        x = jax.random.normal(key, (n, width), jnp.float32) \
            * jnp.asarray(10.0 ** u, jnp.float32)[ids][:, None]
        return jnp.where(valid, x, 0.0), ids

    return jax.jit(make)(jax_key(seed))


# ---------------------------------------------------------------------------
# served requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Arrival:
    due_s: float                 # seconds after the window opens
    prompt: List[int]
    max_new_tokens: int


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    v = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(v), spec["min"], spec["max"]).astype(np.int64)


def open_poisson(traffic: dict, seed: int, seconds: float, vocab: int,
                 rate: float = None) -> List[Arrival]:
    """``round(rate * seconds)`` requests due inside the window.

    The exponential gaps and the lognormal prompt and output lengths are
    drawn from ``traffic["base_seed"]`` and the gaps scaled so the last
    request falls half a mean gap before the window closes; ``seed``
    draws the prompt token ids uniformly from [1, vocab).
    """
    rate = float(traffic["rate_per_s"] if rate is None else rate)
    count = max(1, int(round(rate * seconds)))
    base = np.random.default_rng(int(traffic["base_seed"]))
    gaps = base.exponential(1.0 / rate, count)
    gaps *= (seconds - 0.5 / rate) / gaps.sum()
    plen = _lengths(base, traffic["prompt_tokens"], count)
    olen = _lengths(base, traffic["output_tokens"], count)
    rng = np.random.default_rng(seed64(seed))
    due = np.cumsum(gaps)
    return [Arrival(float(t), rng.integers(1, vocab, int(p)).tolist(), int(o))
            for t, p, o in zip(due, plen, olen)]
