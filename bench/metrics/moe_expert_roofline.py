"""Share of the roofline reached by the held experts' grouped matmul in
decode: per traced decode step, the least time the chip could take,
max(FLOPs / peak FLOP/s, bytes / HBM bandwidth) with the useful work of
``moe_useful.experts_step`` (2 FLOPs per expert weight per routed pair;
the weights of each held expert hit read once, plus the pairs' rows),
averaged over the steps, divided by the op's device time per decode
run (``moe_expert_ms``)."""

import moe_useful


def read(run):
    f, p = run.facts, run.peaks
    steps = f.get("moe_decode_traced")
    runs, secs = f.get("moe_decode_runs"), f.get("moe_expert_s")
    if not steps or not runs or not secs:
        return None
    floor = [max(fl / p["bf16_flops_per_s"], by / p["hbm_bytes_per_s"])
             for fl, by in (moe_useful.experts_step(f["model"], counts)
                            for _, _, counts in steps)]
    return 100.0 * (sum(floor) / len(floor)) / (secs / runs)
