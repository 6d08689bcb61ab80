"""``deepseek-v2-lite-ep8``: its plain reference agrees with straightforward
arithmetic and with the program's float32 forward, a whole run of its
driver on the CPU comes out ``correct``, and the float8 control in the
program's place comes out not ``correct`` (a tiny configuration with the
same keys, one chip's share of its experts)."""

from __future__ import annotations

import json
import math

import numpy as np
import pytest

from conftest import REPO, make_tiny_root, run_tiny

import controls
import generate
import spec as bench_spec

BENCH = bench_spec.Benchmark(REPO)
CFG = json.loads((REPO / "bench/configs/deepseek-v2-lite-ep8.json")
                 .read_text())

TINY_MODEL = {k: CFG[k] for k in (
    "attention_bias", "hidden_act", "model_type", "moe_layer_freq",
    "n_group", "norm_topk_prob", "q_lora_rank", "rms_norm_eps", "rope_theta",
    "routed_scaling_factor", "scoring_func", "seq_aux", "tie_word_embeddings",
    "topk_group", "topk_method", "torch_dtype")}
TINY_MODEL.update({
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 16, "max_position_embeddings": 64,
    "moe_intermediate_size": 32, "n_routed_experts": 4, "n_shared_experts": 1,
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "vocab_size": 256,
    "rope_scaling": dict(CFG["rope_scaling"],
                         original_max_position_embeddings=32),
    "share": {"chips": 2, "chip": 1, "first_expert": 4, "router_outputs": 8}})
TINY = {"driver": "serve_mla_moe", "source": "test", "deployment": "test",
        **TINY_MODEL,
        "serving": {"max_batch": 4, "max_len": 64, "prefill_chunk": 8,
                    "page_size": 8, "logprob_policy": "compensated"},
        "limits": {"token_gap_max": 0.02, "logprob_mean_err": 0.045}}
TINY_DECODE = {"rate_per_s": 12.0, "base_seed": 7,
               "prompt_tokens": {"median": 12, "sigma": 0.6, "min": 4,
                                 "max": 40},
               "output_tokens": {"median": 6, "sigma": 0.5, "min": 2,
                                 "max": 16},
               "check_requests": 3}


@pytest.fixture
def root(tmp_path):
    return make_ds_root(tmp_path)


def make_ds_root(tmp_path):
    """``make_tiny_root`` with the tiny DeepSeek share as cell ``t.ds``."""
    root = make_tiny_root(tmp_path)
    configs = root / "bench" / "configs"
    (configs / "tiny-ds.json").write_text(json.dumps(TINY))
    (configs / "tiny-ds.py").write_text(
        (configs / "deepseek-v2-lite-ep8.py").read_text())
    (root / "bench/traffic/t-decode.json").write_text(json.dumps(TINY_DECODE))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-ds", "source": "test",
                            "file": "bench/configs/tiny-ds.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "t.ds", "config": "tiny-ds",
                              "traffic": "t-decode", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "serve.deepseek-v2-lite-ep8.decode" in REPO_CELLS.get(
                m["name"], ()):
            m["workloads"].append("t.ds")
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


REPO_CELLS = {m["name"]: m.get("workloads", ())
              for m in BENCH.spec["end_to_end"] + BENCH.spec["per_layer"]}


def test_yarn_by_hand():
    ref = BENCH.reference("deepseek-v2-lite-ep8")
    inv = ref.yarn_inv_freq(64, 1e4, 40, 4096, 32, 1)
    base = 1.0 / 1e4 ** (np.arange(32) / 32)
    # correction dimensions: 64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4) = 10.47
    # (floor 10) and 64 ln(4096 / (2 pi)) / (2 ln 1e4) = 22.51 (ceil 23)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-12)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-12)
    ramp = (16 - 10) / (23 - 10)
    assert inv[16] == pytest.approx(base[16] * (1 - ramp)
                                    + base[16] / 40 * ramp, rel=1e-12)
    assert ref.yarn_mscale(40, 0.707) == pytest.approx(
        0.1 * 0.707 * math.log(40) + 1, rel=1e-12)
    assert abs(ref.yarn_mscale(40, 0.707) - 1.2608) < 1e-4


def test_reference_matches_the_program_in_float32():
    import jax
    import jax.numpy as jnp
    from repro.models import forward
    drv = BENCH.driver("serve_mla_moe")
    ref = BENCH.reference("deepseek-v2-lite-ep8")
    params = ref.make_params(generate.jax_key(5), TINY_MODEL)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    cfg = drv.program_config("tiny", TINY_MODEL).scaled(dtype="float32")
    toks = np.random.default_rng(0).integers(1, 256, 24)
    logits = np.asarray(forward(params32, cfg, tokens=jnp.asarray(toks)[None],
                                moe_impl="held")[0][0, :, :256], np.float64)
    nxt = np.roll(toks, -1)
    best, picked, lse, top = (np.asarray(a, np.float64) for a in
                              ref.next_token_stats(params, TINY_MODEL,
                                                   jnp.asarray(toks),
                                                   jnp.asarray(nxt)))
    np.testing.assert_allclose(best, logits.max(-1), atol=2e-4)
    np.testing.assert_allclose(picked, logits[np.arange(24), nxt],
                               atol=2e-4)
    np.testing.assert_allclose(
        lse, np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1))
        + logits.max(-1), atol=2e-4)
    assert np.array_equal(top, logits.argmax(-1))


def test_one_moe_layer_by_hand():
    """The reference's MoE layer on one token, against NumPy: softmax
    over every router output, top 3 not renormalised, the held experts'
    SwiGLU weighted by their gates, the shared expert added."""
    import jax.numpy as jnp
    ref = BENCH.reference("deepseek-v2-lite-ep8")
    m = dict(TINY_MODEL, num_hidden_layers=2)
    params = ref.make_params(generate.jax_key(9), m)
    p = {k: np.asarray(v, np.float64)[0] if not isinstance(v, dict) else
         {n: np.asarray(w, np.float64)[0] for n, w in v.items()}
         for k, v in params["blocks"][0]["mlp"].items()}
    h = np.random.default_rng(3).normal(size=64)
    lg = h @ p["router"]
    probs = np.exp(lg - lg.max()) / np.exp(lg - lg.max()).sum()
    top = np.argsort(-probs)[:3]

    def swiglu(wi, wg, wo):
        g = h @ wg
        return (g / (1 + np.exp(-g)) * (h @ wi)) @ wo

    want = swiglu(p["shared"]["wi"], p["shared"]["wg"], p["shared"]["wo"])
    for e in top:
        if 4 <= e < 8:
            want = want + probs[e] * swiglu(p["wi"][e - 4], p["wg"][e - 4],
                                            p["wo"][e - 4])
    # run the reference's layer alone: a one-token sequence through a
    # model whose attention output is zeroed and norms are identities
    del jnp
    got = _reference_moe(ref, params, m, h)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def _reference_moe(ref, params, m, h):
    import jax
    import jax.numpy as jnp
    blk = jax.tree.map(lambda a: a[0], params["blocks"][0])
    hn = jnp.asarray(h, jnp.float32)[None]
    out = jax.jit(lambda p, x: _experts(ref, p, x, m))(blk["mlp"], hn)
    return np.asarray(out, np.float64)[0]


def _experts(ref, p, hn, m):
    """The reference's expert body, lifted out of its jitted forward."""
    import jax
    import jax.numpy as jnp

    def mm(a, w):
        return ref._mm(a, w, "float32")

    def swiglu(q, x):
        return mm(jax.nn.silu(mm(x, q["wg"])) * mm(x, q["wi"]), q["wo"])

    probs = jax.nn.softmax(mm(hn, p["router"]), axis=-1)
    w, idx = jax.lax.top_k(probs, m["num_experts_per_tok"])
    out = swiglu(p["shared"], hn)
    for j in range(m["n_routed_experts"]):
        gate = jnp.sum(jnp.where(idx == m["share"]["first_expert"] + j, w,
                                 0.0), -1)
        out = out + gate[:, None] * swiglu(
            {n: p[n][j] for n in ("wi", "wg", "wo")}, hn)
    return out


def test_fp8_control_is_coarser():
    import jax.numpy as jnp
    ref = BENCH.reference("deepseek-v2-lite-ep8")
    params = ref.make_params(generate.jax_key(5), TINY_MODEL)
    toks = jnp.asarray(np.random.default_rng(1).integers(1, 256, 32))
    full = np.asarray(ref.next_token_stats(params, TINY_MODEL, toks, toks)[0])
    low = np.asarray(ref.next_token_stats(params, TINY_MODEL, toks, toks,
                                          precision="fp8")[0])
    assert 1e-3 < np.max(np.abs(full - low)) < 1.0


def test_a_tiny_run_is_correct(root):
    line = run_tiny(root, "t.ds", seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["compiles_in_window"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s",
                                    "ttft_p95_ms", "itl_p95_ms"}


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 9])
def test_the_float8_control_is_not_correct(root, monkeypatch, seed):
    bench = bench_spec.Benchmark(root)
    controls.install_serve(bench.driver("serve_mla_moe"),
                           bench.reference("tiny-ds"), monkeypatch.setattr)
    line = run_tiny(root, "t.ds", seed=seed, seconds=0.6)
    assert line["correct"] is False, line["checks"]


def test_the_parent_program_is_refused_at_once():
    """A program without the share's fields fails before any weight is
    made: ``program_config`` is the first thing ``run`` builds."""
    drv = BENCH.driver("serve_mla_moe")
    with pytest.raises(ValueError, match="cannot run"):
        drv.program_config("x", dict(TINY_MODEL, scoring_func="sigmoid"))
