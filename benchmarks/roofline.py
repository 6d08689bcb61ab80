"""Roofline analysis from the dry-run's compiled artifacts.

Per (arch × shape) on the single-pod mesh (256 × TPU v5e):

  compute term    = HLO_FLOPs / peak_FLOPs          (per device, 197 TF bf16)
  memory term     = HLO_bytes / HBM_bw              (per device, 819 GB/s)
  collective term = collective_bytes / link_bw      (per device, ~50 GB/s)

HLO numbers come from ``cost_extrapolated`` (depth-1/2 unrolled variants,
linearly extrapolated to full depth — XLA counts while bodies once, see
launch/dryrun.py).  The sLSTM per-timestep scan cannot be unrolled; its
missing flops/bytes are added analytically (documented below).

MODEL_FLOPS (the "useful" flop count):
  train:   6 * N_active * tokens   (fwd 2ND + bwd 4ND)
  prefill: 2 * N_active * tokens
  decode:  2 * N_active * batch    (+ attention cache read, in bytes)
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Optional

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
LINK_BW = 50e9               # bytes/s / link (ICI)
CHIPS = 256


def _slstm_correction(cfg, shape, devices: int) -> dict:
    """Analytic correction for the sequential sLSTM scan (counted once by
    XLA): per step the cell does the recurrent matmul (B, d) @ (d, 4d)
    => 8*B*d^2 flops; (S-1) steps are missing; backward ~2x forward."""
    n_slstm = sum(1 for b in cfg.period if b.kind == "slstm") \
        * cfg.n_periods
    if n_slstm == 0:
        return {"flops": 0.0, "bytes": 0.0}
    d = cfg.d_model
    if shape.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}      # decode is one step anyway
    # xlstm trains pure-DP over the whole mesh (mesh_plan): batch/256 per
    # device; prefill keeps batch on the 16-way dp axis.
    b_loc = shape.global_batch / (256 if shape.kind == "train" else 16)
    mult = 3.0 if shape.kind == "train" else 1.0  # bwd ~ 2x fwd
    flops = n_slstm * b_loc * (shape.seq_len - 1) * 8 * d * d * mult
    # bytes: optimistic — recurrent weights stay VMEM-resident across steps
    return {"flops": flops, "bytes": 0.0}


def cache_bytes_total(cfg, shape) -> float:
    """Global KV/state cache bytes for a decode/prefill shape."""
    b, s = shape.global_batch, shape.seq_len
    per_layer = 0.0
    n_attn = sum(1 for sp in cfg.period if sp.kind == "attn") * cfg.n_periods
    if cfg.attn_type == "mla":
        per_tok = (cfg.kv_lora_rank + cfg.qk_rope_dim) * 2
    else:
        s_eff = min(cfg.window, s) if cfg.window else s
        per_tok = 2 * cfg.n_kv_heads * cfg.hdim * 2
        return n_attn * b * s_eff * per_tok
    return n_attn * b * s * per_tok


def analytic_bytes_floor(cfg, shape, devices: int = CHIPS,
                         model: int = 16) -> float:
    """Per-device HBM-traffic floor (perfect fusion): weights + optimizer +
    saved activations + caches + logits.  The HLO 'bytes accessed' number is
    the no-fusion *upper* bound; real TPU traffic lies between."""
    pc = cfg.param_counts()
    n_tot = pc["total"]
    d = cfg.d_model
    dp = devices // model
    if shape.kind == "train":
        b_loc = shape.global_batch / dp
        s_sp = shape.seq_len / model          # SP residual stream
        w = 3 * n_tot * 2 / model             # fwd + remat + bwd reads
        opt = 20 * n_tot / devices            # f32 m,v,p rw + grad
        act = 2 * cfg.n_layers * b_loc * s_sp * d * 2 * 2
        loss = b_loc * shape.seq_len * cfg.padded_vocab / model * 4 * 2
        return w + opt + act + loss
    if shape.kind == "prefill":
        b_loc = shape.global_batch / dp
        w = n_tot * 2 / model
        cache = cache_bytes_total(cfg, shape) / devices
        act = 2 * cfg.n_layers * b_loc * shape.seq_len * d * 2
        return w + cache + act
    # decode: weights once + full cache read
    w = n_tot * 2 / model
    cache = cache_bytes_total(cfg, shape) / devices
    return w + cache


def model_flops_per_device(cfg, shape, devices: int = CHIPS) -> float:
    n_active = cfg.param_counts()["active"]
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens / devices
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens / devices
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch / devices


def analyze_cell(rec: dict, cfg, shape) -> Optional[dict]:
    if rec.get("status") != "ok":
        return None
    cost = rec.get("cost_extrapolated") or {}
    flops = cost.get("flops", rec["cost_raw"].get("flops", 0.0))
    byts = cost.get("bytes", rec["cost_raw"].get("bytes accessed", 0.0))
    coll = (cost.get("collective_bytes") or
            rec.get("collectives_raw", {})).get("total", 0.0)
    corr = _slstm_correction(cfg, shape, rec.get("devices", CHIPS))
    flops += corr["flops"]
    byts += corr["bytes"]
    # microbatched train steps scan over microbatches: the body is counted
    # once by XLA, so per-step costs scale by the microbatch count
    # (optimizer/overhead slightly overcounted; <1% at these sizes).
    mb = rec.get("microbatches", 1)
    if mb > 1:
        flops *= mb
        byts *= mb
        coll = coll * mb

    t_c = flops / PEAK_FLOPS
    t_m_hlo = byts / HBM_BW               # no-fusion upper bound
    bytes_floor = analytic_bytes_floor(cfg, shape,
                                       rec.get("devices", CHIPS))
    t_m = bytes_floor / HBM_BW            # perfect-fusion floor
    t_x = coll / LINK_BW
    dominant = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
    mf = model_flops_per_device(cfg, shape)
    total_t = max(t_c, t_m, t_x)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "kind": shape.kind,
        "flops": flops, "bytes_hlo": byts, "bytes_floor": bytes_floor,
        "collective_bytes": coll,
        "compute_s": t_c, "memory_s": t_m, "memory_hlo_s": t_m_hlo,
        "collective_s": t_x,
        "dominant": dominant[1],
        "model_flops": mf,
        "useful_ratio": mf / flops if flops else 0.0,
        "roofline_fraction": (mf / PEAK_FLOPS) / total_t if total_t else 0.0,
        "temp_gb": rec.get("memory", {}).get("temp_size_in_bytes", 0) / 1e9,
        "slstm_corr_flops": corr["flops"],
    }


def build_table(dryrun_dir: str, mesh_tag: str = "single_pod_16x16"):
    from repro.configs import get_config
    from repro.models.config import SHAPES_BY_NAME

    rows = []
    for f in sorted(Path(dryrun_dir).glob(f"*__{mesh_tag}.json")):
        rec = json.loads(f.read_text())
        if rec.get("status") == "skipped":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "dominant": "skipped",
                         "note": rec.get("reason", "")})
            continue
        cfg = get_config(rec["arch"])
        shape = SHAPES_BY_NAME[rec["shape"]]
        row = analyze_cell(rec, cfg, shape)
        if row:
            rows.append(row)
    return rows


def to_markdown(rows) -> str:
    hdr = ("| arch | shape | compute s | memory s (floor..hlo) | "
           "collective s | bottleneck | useful/HLO | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|\n")
    out = [hdr]
    for r in rows:
        if r.get("dominant") == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | "
                       f"skipped | — | — |\n")
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f}..{r['memory_hlo_s']:.3f} | "
            f"{r['collective_s']:.4f} | "
            f"{r['dominant']} | {r['useful_ratio']:.2f} | "
            f"{r['roofline_fraction']:.3f} |\n")
    return "".join(out)


def reduce_program_table(shapes=((512, 128, 64), (512, 128, 1024))):
    """Analytic roofline of the staged reduce block-program.

    For each (block_size, d, num_segments) shape and every registered
    accuracy policy, plan the staged program (``repro.reduce
    .plan_program``) and turn its declared per-block stage costs into
    roofline times: a stage takes ``max(bytes / HBM_BW, flops /
    PEAK_FLOPS)``.  Two derived columns quantify the two pipeline
    decisions this repo makes:

      * ``overlap_speedup`` — serial stage sum over max stage time: what
        double-buffering the gather against the carry update is worth
        when the stages are balanced (the JugglePAC overlap, at block
        granularity);
      * ``contrib`` — the gather form planned for the TPU kernel: the
        one-hot dot at every ``num_segments`` (the compiled kernel has no
        scatter-add), so at large S the dot's B*S*W flops show up as a
        compute-bound gather stage.

    Pure analysis — no arrays move; safe in any CI job.  The smoke
    harness (benchmarks/run.py --smoke) writes this table to
    ``experiments/roofline/reduce_smoke.json``.
    """
    from repro.reduce import get_backend, get_policy, plan_program
    from repro.reduce.policy import POLICIES

    kernel = get_backend("pallas")

    rows = []
    for block_size, d, s in shapes:
        for name in sorted(POLICIES):
            pol = get_policy(name)
            w = pol.domain_width(d)
            prog = plan_program(pol, num_segments=s, domain_width=w,
                                block_size=block_size,
                                plans_lanes=kernel.plans_lanes)
            stages = {}
            for st in prog.stages:
                stages[st.name] = {
                    "bytes": st.bytes, "flops": st.flops,
                    "bound": st.bound,
                    "s": max(st.bytes / HBM_BW, st.flops / PEAK_FLOPS)}
            serial = sum(v["s"] for v in stages.values())
            pipelined = max(v["s"] for v in stages.values())
            rows.append({
                "policy": name, "contrib": prog.contrib,
                "block_size": block_size, "d": d, "num_segments": s,
                "domain_width": w, "stages": stages,
                "serial_s": serial, "pipelined_s": pipelined,
                "overlap_speedup": serial / pipelined if pipelined else 1.0,
            })
    return rows


def to_csv(rows) -> str:
    cols = ("arch", "shape", "kind", "flops", "bytes_floor", "bytes_hlo",
            "collective_bytes", "compute_s", "memory_s", "memory_hlo_s",
            "collective_s", "dominant", "model_flops", "useful_ratio",
            "roofline_fraction", "temp_gb")
    lines = [",".join(cols)]
    for r in rows:
        if r.get("dominant") == "skipped":
            continue
        lines.append(",".join(str(r.get(c, "")) for c in cols))
    return "\n".join(lines) + "\n"


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-dir", default="experiments/dryrun")
    ap.add_argument("--out", default="experiments/roofline")
    args = ap.parse_args()
    rows = build_table(args.dryrun_dir)
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    (out / "roofline.md").write_text(to_markdown(rows))
    (out / "roofline.csv").write_text(to_csv(rows))
    print(to_markdown(rows))


if __name__ == "__main__":
    main()
