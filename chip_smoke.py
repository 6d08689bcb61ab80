#!/usr/bin/env python3
"""Chip smoke run: drive the main paths once on a TPU and check them.

    python3 chip_smoke.py [--seed S]      # one chip: phases (a) and (b)
    python3 chip_smoke.py --chips 4       # four chips: phase (c) only

(a) reduce: ``repro.reduce(..., backend="pallas")``, compiled, over
    N=2^20 x 128 f32 rows of adversarial values (1/3 plus ulp-scale
    noise; cancellation pairs around an off-grid 1/3) in back-to-back
    Zipf-length segments, at S=8 and S=64.  Every tier is held to its
    documented bound against a float64 numpy reference; pallas and
    blocked must give the same bits for the integer tiers, and whether
    the float tiers do is reported.
(b) serving: stablelm-1.6b at its published widths (bf16, weights made
    from the seed) through ``Engine`` with max_batch=8, max_len=1024: 8
    greedy requests, prompts of 64-512 tokens, 32 new tokens each.  The
    results come back in submission order, complete, with finite mean
    logprobs, and each first token is the argmax of a whole-prompt
    ``forward`` on the same chip.
(c) --chips 4: the shard_map reduce of ``exact2`` and ``fast`` over a
    4-device mesh against the one-chip pallas result (``exact2`` bitwise),
    the shard placement, auto-selection under ``jax.set_mesh``, and
    ``collective_mean("exact2")``.

Earlier lines report the device, compile seconds per phase and every
check.  The last line is ``{"ok": true, "device": {...}}`` only when every
check passed; otherwise the script exits 1 without it, and with 2 when
JAX finds no TPU.  One process holds the chip; nothing is started.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: the reduce stream of phases (a) and (c)
N_ROWS, WIDTH, SEGMENT_COUNTS = 1 << 20, 128, (8, 64)
BLOCK = 512
TIERS = ("fast", "compensated", "exact", "exact2", "procrastinate")
#: the serving cell of phase (b)
ARCH, MAX_BATCH, MAX_LEN, REQUESTS, NEW_TOKENS = \
    "stablelm-1.6b", 8, 1024, 8, 32
PROMPT_LENS = (64, 512)
#: bf16 resolution of a whole-prompt logit: a first token whose reference
#: logit sits this close (relative to the logit range) to the reference
#: argmax is a near-tie the two bf16 paths may break either way
NEAR_TIE = 2.0 ** -8


class Checks:
    """Prints one line per check and remembers whether all passed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"check {'PASS' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


class CompileClock:
    """Sums the backend compile seconds JAX reports, per phase."""

    def __init__(self, jax):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.endswith("backend_compile_duration"):
            self.secs += secs

    def lap(self) -> float:
        s, self.secs = self.secs, 0.0
        return s


# ---------------------------------------------------------------------------
# the reduce stream and its float64 reference
# ---------------------------------------------------------------------------


def adversarial_stream(rng, n: int, d: int) -> np.ndarray:
    """(n, d) f32: the first half of the columns 1/3 plus ulp-scale noise,
    the rest catastrophic-cancellation quads (+b, -b, b' + 1/3, -b')."""
    x = np.empty((n, d), np.float32)
    h = d // 2
    x[:, :h] = 1 / 3 + rng.standard_normal((n, h)) * 1e-9
    big = rng.uniform(100.0, 1000.0, (n // 2, d - h)).astype(np.float32)
    x[0::4, h:] = big[0::2]
    x[1::4, h:] = -big[0::2]
    x[2::4, h:] = big[1::2] + np.float32(1 / 3)
    x[3::4, h:] = -big[1::2]
    return x


def zipf_runs(rng, n: int, num_segments: int, min_len: int = 16,
              max_len: int = 1 << 16):
    """Back-to-back runs of Zipf-distributed length; run r carries label
    r mod S.  Returns (ids (n,), run starts, run labels)."""
    lens = min_len * np.minimum(rng.zipf(1.2, size=n // min_len + 1),
                                max_len // min_len)
    starts = np.concatenate([[0], np.cumsum(lens)])
    starts = starts[starts < n]
    labels = np.arange(len(starts)) % num_segments
    ids = np.repeat(labels, np.diff(np.append(starts, n))).astype(np.int32)
    return ids, starts, labels


def reference(x: np.ndarray, starts, labels, num_segments: int):
    """Per-label float64 sums, sums of |x| and row counts."""
    def per_label(v):
        out = np.zeros((num_segments,) + v.shape[1:])
        np.add.at(out, labels, np.add.reduceat(v, starts, axis=0))
        return out
    x64 = x.astype(np.float64)
    ones = np.ones((len(x), 1))
    return per_label(x64), per_label(np.abs(x64)), per_label(ones)


def tier_bound(tier: str, ref, abs_sum, count, *, n: int, max_abs: float,
               block: int = BLOCK):
    """Each tier's documented error bound, elementwise over (S, D)."""
    import jax.numpy as jnp
    from repro import reduce as R
    u = 2.0 ** -24
    ulp = np.spacing(np.abs(ref).astype(np.float32)).astype(np.float64)
    if tier == "fast":       # recursive summation: B in-block + nb carries
        return (block + -(-n // block)) * u * abs_sum + ulp
    if tier == "compensated":  # f32 in-block dot, compensated across blocks
        return block * u * abs_sum + 2 * ulp
    if tier == "exact":      # half a quantum per row, then one rounding
        scale = float(R.get_policy("exact").prepare_ctx(
            jnp.float32(max_abs), n))
        return 0.5 * count / scale + ulp
    if tier == "exact2":     # <= 1 ulp of the f64 sum
        return ulp
    if tier == "procrastinate":  # 1 ulp, absolute under cancellation
        return ulp + count * 2.0 ** -49 * max_abs
    raise ValueError(tier)


def _within(tier, out, ref, bound) -> tuple:
    err = np.abs(out.astype(np.float64) - ref)
    ratio = float(np.max(err / np.maximum(bound, 1e-300)))
    return bool(np.all(err <= bound)), f"max err/bound {ratio:.3g}"


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_reduce(check, *, seed: int, n: int = N_ROWS, d: int = WIDTH,
                 segment_counts=SEGMENT_COUNTS, tiers=TIERS):
    """(a) every tier through the front door, pallas compiled."""
    import jax
    from repro import reduce as R
    from repro.reduce.backends import interpret_default

    for tier in tiers:
        check(f"auto-selects pallas [{tier}]",
              R.select_backend(R.get_policy(tier)).name == "pallas")
    check("kernels compile (interpret resolves to False)",
          interpret_default() is False)
    rng = np.random.default_rng(seed)
    x = adversarial_stream(rng, n, d)
    xd = jax.device_put(x)
    max_abs = float(np.max(np.abs(x)))
    for s in segment_counts:
        ids, starts, labels = zipf_runs(rng, n, s)
        ref, abs_sum, count = reference(x, starts, labels, s)
        idd = jax.device_put(ids)
        print(f"reduce S={s}: {len(starts)} runs, "
              f"shortest {int(np.diff(np.append(starts, n)).min())}, "
              f"longest {int(np.diff(np.append(starts, n)).max())}",
              flush=True)
        for tier in tiers:
            bound = tier_bound(tier, ref, abs_sum, count, n=n,
                               max_abs=max_abs)
            outs = {}
            for backend in ("pallas", "blocked"):
                def f(v, i, tier=tier, backend=backend):
                    return R.reduce(v, segment_ids=i, num_segments=s,
                                    policy=tier, backend=backend,
                                    block_size=BLOCK)
                t0 = time.perf_counter()
                compiled = jax.jit(f).lower(xd, idd).compile()
                tc = time.perf_counter() - t0
                t0 = time.perf_counter()
                outs[backend] = np.asarray(
                    jax.block_until_ready(compiled(xd, idd)))
                tr = time.perf_counter() - t0
                print(f"  {tier:13s} S={s:<3d} {backend:7s} compile "
                      f"{tc:.3f}s first run {tr:.4f}s", flush=True)
                if backend == "pallas":
                    check(f"mosaic kernel [{tier} S={s}]",
                          "tpu_custom_call" in compiled.as_text())
            for backend, out in outs.items():
                ok, detail = _within(tier, out, ref, bound)
                check(f"bound [{tier} S={s} {backend}]",
                      ok and bool(np.all(np.isfinite(out))), detail)
            same = bool(np.array_equal(outs["pallas"], outs["blocked"]))
            diff = float(np.max(np.abs(outs["pallas"] - outs["blocked"])))
            if tier in ("fast", "compensated"):
                print(f"  info {tier} S={s}: pallas and blocked bits "
                      f"{'equal' if same else 'differ'} (max |diff| "
                      f"{diff:.3g})", flush=True)
            else:
                check(f"pallas bits == blocked bits [{tier} S={s}]", same,
                      f"max |diff| {diff:.3g}")


def phase_serve(check, *, seed: int, cfg=None, max_batch: int = MAX_BATCH,
                max_len: int = MAX_LEN, requests: int = REQUESTS,
                new_tokens: int = NEW_TOKENS, prompt_lens=PROMPT_LENS):
    """(b) a published config through Engine, first tokens vs forward."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import forward, init_params
    from repro.serve.engine import Engine, Request

    cfg = cfg or get_config(ARCH)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        init_params(jax.random.PRNGKey(seed), cfg))
    nparam = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    print(f"serve {cfg.name}: {nparam / 1e9:.3f}B params ({cfg.dtype}), "
          f"init {time.perf_counter() - t0:.1f}s", flush=True)
    lo, hi = prompt_lens
    reqs = [Request(prompt=[int(t) for t in rng.integers(
                        1, cfg.vocab, size=int(rng.integers(lo, hi + 1)))],
                    max_new_tokens=new_tokens, temperature=0.0)
            for _ in range(requests)]
    engine = Engine(cfg, params, max_len=max_len, max_batch=max_batch,
                    seed=seed)
    t0 = time.perf_counter()
    results = engine.generate(reqs)
    dt = time.perf_counter() - t0
    print(f"  generate: {len(results)} requests, prompts "
          f"{[len(r.prompt) for r in reqs]}, {dt:.2f}s (compiles "
          f"included)", flush=True)
    check("results in submission order",
          len(results) == len(reqs) and all(
              r.prompt_len == len(q.prompt)
              and r.tokens[:r.prompt_len] == list(q.prompt)
              for r, q in zip(results, reqs))
          and [r.rid for r in results] == sorted(r.rid for r in results))
    check(f"{new_tokens} new tokens each",
          all(len(r.tokens) - r.prompt_len == new_tokens for r in results),
          str([len(r.tokens) - r.prompt_len for r in results]))
    check("mean_logprob finite",
          all(r.mean_logprob is not None and np.isfinite(r.mean_logprob)
              for r in results),
          str([None if r.mean_logprob is None else round(r.mean_logprob, 3)
               for r in results]))

    # the whole-prompt reference: one compiled shape, prompts right-padded
    # (causal attention: the pad never reaches the last prompt position)
    ref_fn = jax.jit(lambda p, t: forward(p, cfg, tokens=t, mode="train",
                                          moe_impl="dense")[0])
    equal, ties, worst = 0, 0, []
    for r, q in zip(results, reqs):
        toks = np.zeros((1, hi), np.int32)
        toks[0, :len(q.prompt)] = q.prompt
        logits = np.asarray(ref_fn(params, jnp.asarray(toks))[
            0, len(q.prompt) - 1, :cfg.vocab], np.float64)
        first = r.tokens[r.prompt_len]
        top = int(np.argmax(logits))
        gap = (logits[top] - logits[first]) / max(np.ptp(logits), 1e-30)
        worst.append(round(float(gap), 5))
        equal += first == top
        ties += first != top and gap <= NEAR_TIE
    check("first token == whole-prompt forward argmax",
          equal + ties == len(results),
          f"{equal}/{len(results)} equal, {ties} bf16 near-ties, "
          f"gaps/range {worst}")


def phase_shards(check, *, seed: int, n: int = N_ROWS, d: int = WIDTH,
                 num_segments: int = SEGMENT_COUNTS[-1], chips: int = 4):
    """(c) the shard_map path across chips against one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro import reduce as R

    devs = jax.devices()[:chips]
    mesh = Mesh(np.asarray(devs), ("shards",))
    rng = np.random.default_rng(seed)
    x = adversarial_stream(rng, n, d)
    ids, starts, labels = zipf_runs(rng, n, num_segments)
    ref, abs_sum, count = reference(x, starts, labels, num_segments)
    max_abs = float(np.max(np.abs(x)))

    xs = jax.device_put(x, NamedSharding(mesh, P("shards", None)))
    ids_s = jax.device_put(ids, NamedSharding(mesh, P("shards")))
    rows = n // chips
    placed = sorted((sh.index[0].start or 0, sh.device.id,
                     sh.data.shape[0]) for sh in xs.addressable_shards)
    check("each device holds its own shard of rows",
          [p[0] for p in placed] == [k * rows for k in range(chips)]
          and len({p[1] for p in placed}) == chips
          and all(p[2] == rows for p in placed),
          f"{[(p[0], p[1]) for p in placed]}")
    x1 = jax.device_put(x, devs[0])
    ids1 = jax.device_put(ids, devs[0])
    one_chip = {}

    for tier in ("exact2", "fast"):
        kw = dict(num_segments=num_segments, policy=tier, block_size=BLOCK)
        t0 = time.perf_counter()
        sharded = jax.jit(lambda v, i: R.reduce(
            v, segment_ids=i, backend="shard_map", mesh=mesh, **kw)
        ).lower(xs, ids_s).compile()
        single = jax.jit(lambda v, i: R.reduce(
            v, segment_ids=i, backend="pallas", **kw)).lower(x1, ids1).compile()
        print(f"  {tier}: compile {time.perf_counter() - t0:.3f}s",
              flush=True)
        check(f"per-shard mosaic kernel [{tier}]",
              "tpu_custom_call" in sharded.as_text())
        out4 = np.asarray(sharded(xs, ids_s))
        out1 = one_chip[tier] = np.asarray(single(x1, ids1))
        bound = tier_bound(tier, ref, abs_sum, count, n=n, max_abs=max_abs)
        for name, out in ((f"{chips} chips", out4), ("1 chip", out1)):
            ok, detail = _within(tier, out, ref, bound)
            check(f"bound [{tier} {name}]", ok, detail)
        same = bool(np.array_equal(out4, out1))
        diff = float(np.max(np.abs(out4 - out1)))
        if tier == "exact2":
            check(f"exact2 {chips} chips bitwise == 1 chip", same,
                  f"max |diff| {diff:.3g}")
        else:
            print(f"  info fast: {chips}-chip and 1-chip bits "
                  f"{'equal' if same else 'differ'} (max |diff| "
                  f"{diff:.3g})", flush=True)

    with jax.set_mesh(mesh):
        picked = R.select_backend(R.get_policy("exact2")).name
        auto = np.asarray(R.reduce(xs, segment_ids=ids_s,
                                   num_segments=num_segments,
                                   policy="exact2", block_size=BLOCK))
    check("auto-selects shard_map under jax.set_mesh", picked == "shard_map",
          picked)
    check("auto-selected result bitwise == 1 chip exact2",
          bool(np.array_equal(auto, one_chip["exact2"])))

    # collective_mean("exact2"): a per-device (m, d) block, mean over
    # devices; topology-invariant bits and <= 1 ulp of the f64 mean
    m = 256
    stack = x[:chips * m].reshape(chips, m, d)
    ref_mean = stack.astype(np.float64).mean(axis=0)
    outs = []
    for order in (devs, devs[::-1]):
        mesh_o = Mesh(np.asarray(order), ("shards",))
        f = jax.jit(jax.shard_map(
            lambda g: R.collective_mean(g[0], ("shards",),
                                        policy="exact2")[0],
            mesh=mesh_o, in_specs=P("shards", None, None), out_specs=P(),
            check_vma=False))
        outs.append(np.asarray(f(jax.device_put(
            stack, NamedSharding(mesh_o, P("shards", None, None))))))
    ulp = np.spacing(np.abs(ref_mean).astype(np.float32))
    check("collective_mean(exact2) within 1 ulp of the f64 mean",
          bool(np.all(np.abs(outs[0] - ref_mean) <= ulp)))
    check("collective_mean(exact2) bits invariant to device order",
          bool(np.array_equal(outs[0], outs[1])))


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip shard_map phase")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 2
    print(f"device: {devs[0].device_kind} x{len(devs)} "
          f"(jax {jax.__version__}), compile cache {cache}", flush=True)
    check, clock = Checks(), CompileClock(jax)
    phases = ([("c shards", phase_shards)] if args.chips == 4 else
              [("a reduce", phase_reduce), ("b serve", phase_serve)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(check, seed=args.seed)
        print(f"phase ({name}): {time.perf_counter() - t0:.1f}s wall, "
              f"{clock.lap():.1f}s compiling", flush=True)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{check.failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
