"""Device time of one decode program run (``Engine._decode`` ->
``models.decode_step``), mean over the traced window."""

PROGRAM = [r"_decode_fn"]


def read(run):
    secs, runs = run.summary.modules_matching(PROGRAM)
    return 1e3 * secs / runs if runs else None
