"""Model FLOP/s utilization of the whole engine step: the forward FLOPs
of the tokens the traced steps processed (prompt tokens and decoded
tokens, 2 x params each, ``useful.model_flops``) over the device's busy
time in the traced window and the chip's bf16 peak."""

import useful


def read(run):
    steps = run.facts["steps_traced"]
    tokens = sum(p + a for p, a, _ in steps)
    busy = run.summary.mean_busy_s
    if not tokens or not busy:
        return None
    flops = useful.model_flops(run.facts["model"], tokens)
    return 100.0 * flops / busy / run.peaks["bf16_flops_per_s"]
