"""Device time of the JugglePAC Pallas kernel (``jugglepac_segsum``)
per call, mean over devices.  The kernel's op carries no name of its own
in the trace (its ``pallas_call`` sets none); it is the call's Mosaic
custom calls (one per tile of labels)."""

#: the kernel's op: the call program's Mosaic custom call
KERNEL = [r'custom_call_target="tpu_custom_call"']
PROGRAM = [r"_dispatch"]


def read(run):
    s = run.summary
    secs = s.ops_matching(KERNEL)
    _, runs = s.modules_matching(PROGRAM)
    return 1e3 * secs / runs if secs and runs else None
