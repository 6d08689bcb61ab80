"""The useful work of a DeepSeek-V2 share's decode step
(``bench/moe_useful.py``) against hand arithmetic at the published
widths of ``deepseek-v2-lite-ep8``."""

from __future__ import annotations

import json

from conftest import REPO

import moe_useful

CFG = json.loads((REPO / "bench/configs/deepseek-v2-lite-ep8.json")
                 .read_text())


def test_weights_at_published_widths():
    assert moe_useful.expert_params(CFG) == 3 * 2048 * 1408 == 8650752
    # q 2048 x 16 x 192, latent and rope key 2048 x 576, up-projections
    # 512 x 16 x 256, output 2048 x 2048
    assert moe_useful.attention_params(CFG) == (
        6291456 + 1179648 + 2097152 + 4194304)
    bf16, router = moe_useful.other_params(CFG)
    assert bf16 == (27 * 13762560 + 3 * 2048 * 10944 + 26 * 3 * 2048 * 2816
                    + 2048 * 102400)
    assert router == 26 * 2048 * 64
    # 31,104 bytes of latent cache per token over the 27 layers
    assert moe_useful.latent_bytes_per_token(CFG) == 27 * 576 * 2 == 31104
    # the 8 held experts of 26 layers: 3.6 GB of bf16 weights
    assert 26 * 8 * moe_useful.expert_params(CFG) * 2 == 3598712832


def test_experts_step_counts_each_hit_expert_once():
    counts = [[2, 0, 3], [0, 0, 1]]
    fl, by = moe_useful.experts_step(CFG, counts)
    assert fl == 2 * 8650752 * 6
    assert by == 3 * 8650752 * 2 + 6 * 2 * 2048 * 2
    assert moe_useful.experts_step(CFG, [[0, 0]]) == (0, 0)


def test_decode_step_adds_weights_cache_and_experts():
    counts = [[1, 1]]
    bf16, router = moe_useful.other_params(CFG)
    fl, by = moe_useful.decode_step(CFG, 3, 1000, counts)
    efl, eby = moe_useful.experts_step(CFG, counts)
    assert fl == 2 * (bf16 + router) * 3 + efl
    assert by == bf16 * 2 + router * 4 + eby + 1000 * 31104
