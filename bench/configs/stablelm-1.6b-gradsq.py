"""Plain reference of ``stablelm-1.6b-gradsq``: per-tensor float64 sums
of the float32 squares of one chip's gradient slice (a sum of squares
squares each float32 value in float32, then sums).

``layout`` lists the parameter tensors of the published model
(StableLmForCausalLM: embedding; per layer a LayerNorm before attention
and before the MLP, weight and bias each, q/k/v projections with bias,
the output projection, the gated MLP; the final LayerNorm; the untied
output head), each with the values this chip holds (1/shards of the
tensor) and the rows of ``width`` they fill, the last row zero-padded.

No tensor slice here sums more than 2^18 float32 squares per column, so
the float64 sums below are within 2^-35 of the exact sum of those
squares: far below one float32 ulp.  Imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

U32 = 2.0 ** -24
CHUNK = 1 << 18
#: the exact2 tier's stated truncation: what quantization rounds away is
#: kept to 2^-71 of the call's largest term, per term (Exact2Policy)
EXACT2_TRUNC = 2.0 ** -71


def tensors(model: dict) -> list:
    """(name, number of values) of every parameter tensor, in order."""
    d, f = model["hidden_size"], model["intermediate_size"]
    hd = d // model["num_attention_heads"]
    kv = model["num_key_value_heads"] * hd
    bias = bool(model["use_qkv_bias"])
    norm = ["weight", "bias"] if model["norm"] == "layernorm" else ["weight"]
    out = [("embed_tokens", model["vocab_size"] * d)]
    for i in range(model["num_hidden_layers"]):
        p = f"layers.{i}."
        out += [(p + "input_layernorm." + w, d) for w in norm]
        for name, n_out in (("q_proj", d), ("k_proj", kv), ("v_proj", kv)):
            out.append((p + name + ".weight", d * n_out))
            if bias:
                out.append((p + name + ".bias", n_out))
        out.append((p + "o_proj.weight", d * d))
        out += [(p + "post_attention_layernorm." + w, d) for w in norm]
        out += [(p + "mlp." + w + ".weight", d * f)
                for w in ("gate_proj", "up_proj", "down_proj")]
    out += [("norm." + w, d) for w in norm]
    if not model["tie_word_embeddings"]:
        out.append(("lm_head", model["vocab_size"] * d))
    return out


def layout(cfg: dict) -> list:
    """(name, values on this chip, rows of ``width``) per tensor."""
    shards, width = int(cfg["shards"]), int(cfg["width"])
    out = []
    for name, n in tensors(cfg["model"]):
        share = -(-n // shards)
        out.append((name, share, -(-share // width)))
    return out


def sums(x: np.ndarray, ids: np.ndarray, num_segments: int):
    """Sum of the float32 squares per label and column, float64 (S, D),
    and the largest square.  Rows labeled outside [0, S) are dropped."""
    n, d = x.shape
    total = np.zeros((num_segments, d))
    largest = 0.0
    for lo in range(0, n, CHUNK):
        xc = x[lo:lo + CHUNK].astype(np.float32)
        sq = (xc * xc).astype(np.float64)
        largest = max(largest, float(sq.max(initial=0.0)))
        ic = ids[lo:lo + CHUNK]
        starts = np.concatenate([[0], np.flatnonzero(np.diff(ic)) + 1])
        labels = ic[starts]
        keep = (labels >= 0) & (labels < num_segments)
        np.add.at(total, labels[keep],
                  np.add.reduceat(sq, starts, axis=0)[keep])
    return total, largest


def ulps(out: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|out - ref| in ulps of the f32 nearest ``ref`` (0 where equal)."""
    err = np.abs(out.astype(np.float64) - ref)
    ulp = np.spacing(np.abs(ref).astype(np.float32)).astype(np.float64)
    return np.where(err == 0, 0.0, err / ulp)


def bound_units(out: np.ndarray, ref: np.ndarray, rows, largest: float):
    """|out - ref| in units of the exact2 tier's bound: 1 ulp of ``ref``
    plus the truncation of each of the label's ``rows`` terms."""
    err = np.abs(out.astype(np.float64) - ref)
    ulp = np.spacing(np.abs(ref).astype(np.float32)).astype(np.float64)
    terms = np.asarray(rows, np.float64)[:, None]
    return err / (ulp + terms * largest * EXACT2_TRUNC)


def relative(out: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """|out - ref| in units of 2^-24 * ref (every term is positive, so
    ``ref`` is also the sum of the terms' magnitudes)."""
    err = np.abs(out.astype(np.float64) - ref)
    return np.where(err == 0, 0.0, err / np.maximum(U32 * ref, 1e-300))
