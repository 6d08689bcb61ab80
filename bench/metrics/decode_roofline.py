"""Share of the roofline reached by the decode program: per decode step
of the traced window, the least time the chip could take,
max(FLOPs / peak FLOP/s, bytes / HBM bandwidth) with the useful FLOPs
and bytes of ``useful.decode_step`` (2 x params x active slots; the
bf16 weights once plus the KV of the live tokens of the active slots),
averaged over the steps, divided by the mean device time of a decode
run."""

import useful

PROGRAM = [r"_decode_fn"]


def read(run):
    secs, runs = run.summary.modules_matching(PROGRAM)
    steps = [(a, live) for _, a, live in run.facts["steps_traced"] if a]
    if not runs or not steps:
        return None
    p, m = run.peaks, run.facts["model"]
    floor = [max(fl / p["bf16_flops_per_s"], by / p["hbm_bytes_per_s"])
             for fl, by in (useful.decode_step(m, a, live)
                            for a, live in steps)]
    return 100.0 * (sum(floor) / len(floor)) / (secs / runs)
