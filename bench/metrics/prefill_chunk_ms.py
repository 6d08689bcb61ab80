"""Device time of one chunked-prefill program run
(``Engine._prefill_chunk``), mean over the traced window."""

PROGRAM = [r"_prefill_chunk_fn"]


def read(run):
    secs, runs = run.summary.modules_matching(PROGRAM)
    return 1e3 * secs / runs if runs else None
