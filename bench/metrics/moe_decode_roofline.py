"""Share of the roofline reached by the decode program of a DeepSeek-V2
share: per traced decode step, max(FLOPs / peak FLOP/s, bytes / HBM
bandwidth) with the useful work of ``moe_useful.decode_step`` (the
weights outside the routed experts once and 2 FLOPs per weight per
active slot; the held experts as ``moe_expert_roofline`` counts them;
the latent cache of the live tokens), averaged over the steps, divided
by the mean device time of a decode run."""

import moe_useful

PROGRAM = [r"_decode_fn"]


def read(run):
    f, p = run.facts, run.peaks
    steps = f.get("moe_decode_traced")
    secs, runs = run.summary.modules_matching(PROGRAM)
    if not steps or not runs:
        return None
    floor = [max(fl / p["bf16_flops_per_s"], by / p["hbm_bytes_per_s"])
             for fl, by in (moe_useful.decode_step(f["model"], a, live, c)
                            for a, live, c in steps)]
    return 100.0 * (sum(floor) / len(floor)) / (secs / runs)
