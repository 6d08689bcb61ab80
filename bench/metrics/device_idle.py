"""Percent of the traced window in which the device ran no operation,
mean over devices: 1 - busy / window.  Read for every cell that names
``device_idle.<part>``: the split names share this reader."""


def read(run):
    return run.summary.idle_pct
