#!/usr/bin/env python3
"""Run one benchmark cell: ``python3 bench/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>`` (see ``bench/harness.py``)."""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
