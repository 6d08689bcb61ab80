"""The useful work of a DeepSeek-V2 share's decode step, from its shapes
and the held experts' routing counts (the model keys of the
configuration's file; ``n_routed_experts`` is the experts held here).

As in ``useful.py``, the counts are of what the task needs: each held
expert that some pair reached has its weights read once, each pair is
computed once, and the embedding table is a row lookup.
"""

from __future__ import annotations


def expert_params(model: dict) -> int:
    """Weights of one routed expert: its gate, up and down projections."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def experts_step(model: dict, counts, itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) the held experts need in one program run, from
    ``counts`` (MoE layers x held experts) of the (token, choice) pairs
    each computed: 2 FLOPs per weight per pair; the weights of every
    expert hit once, plus each pair's input row read and output row
    written."""
    pairs = sum(int(c) for layer in counts for c in layer)
    hit = sum(int(c) > 0 for layer in counts for c in layer)
    p = expert_params(model)
    return (2 * p * pairs,
            hit * p * itemsize + pairs * 2 * model["hidden_size"] * itemsize)


def attention_params(model: dict) -> int:
    """Matrix weights of one MLA block without q LoRA: q, the latent and
    rope-key projections, the latent's up-projections to keys and values,
    and the output."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    r, nd = model["kv_lora_rank"], model["qk_nope_head_dim"]
    rd, vd = model["qk_rope_head_dim"], model["v_head_dim"]
    return d * h * (nd + rd) + d * (r + rd) + r * h * (nd + vd) + h * vd * d


def other_params(model: dict) -> tuple:
    """(bf16 matrix weights outside the routed experts, float32 router
    weights): every attention block, the leading dense layers' MLPs, the
    shared experts, the output head; the routers' full width."""
    d = model["hidden_size"]
    lead = model["first_k_dense_replace"]
    moe = model["num_hidden_layers"] - lead
    shared = 3 * d * model["n_shared_experts"] \
        * model["moe_intermediate_size"]
    bf16 = (model["num_hidden_layers"] * attention_params(model)
            + lead * 3 * d * model["intermediate_size"] + moe * shared
            + d * model["vocab_size"])
    return bf16, moe * d * model["share"]["router_outputs"]


def latent_bytes_per_token(model: dict, itemsize: int = 2) -> int:
    """MLA's cache of one token over every layer: the latent and the
    rope key."""
    return model["num_hidden_layers"] * (
        model["kv_lora_rank"] + model["qk_rope_head_dim"]) * itemsize


def decode_step(model: dict, active: int, live_tokens: int, counts,
                itemsize: int = 2) -> tuple:
    """(FLOPs, bytes) one decode step needs: 2 x the weights outside the
    routed experts x the active slots, plus the held experts' work
    (``experts_step``); those weights once, the held experts hit, and the
    latent cache of the live tokens of the active slots."""
    bf16, router = other_params(model)
    fl, by = experts_step(model, counts, itemsize)
    return (2 * (bf16 + router) * active + fl,
            bf16 * itemsize + router * 4 + by
            + live_tokens * latent_bytes_per_token(model, itemsize))
