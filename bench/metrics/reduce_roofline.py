"""Share of the HBM roofline reached by one ``repro.reduce`` call, per
device: the useful bytes of the call (``useful.reduce_bytes``: the rows
and labels read once, the result written once) over the chip's HBM
bandwidth, divided by the device time of the call's program.  The same
work whatever tier or implementation runs it."""

import useful

#: the eager front door's one jitted program
PROGRAM = [r"_dispatch"]


def read(run):
    f, s = run.facts, run.summary
    floor = useful.reduce_bytes(f["rows_per_chip"], f["width"],
                                f["segments"]) / run.peaks["hbm_bytes_per_s"]
    shares = []
    for dev in range(s.devices):
        secs, runs = s.modules_matching(PROGRAM, device=dev)
        if runs:
            shares.append(floor / (secs / runs))
    return 100.0 * sum(shares) / len(shares) if shares else None
