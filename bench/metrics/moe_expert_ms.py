"""Device time of the held experts' grouped matmul (``ragged-dot`` ops,
``models/moe.py`` ``moe_apply_held``) per decode program run, over every
MoE layer, in the traced window; the driver reads it from the trace."""


def read(run):
    runs = run.facts.get("moe_decode_runs")
    secs = run.facts.get("moe_expert_s")
    return 1e3 * secs / runs if runs and secs else None
