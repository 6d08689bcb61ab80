"""Benchmark harness: one function per paper table + the roofline summary.

Prints ``name,value,derived`` CSV.  Cycle-level numbers come from the
cycle-accurate simulators (the paper's own metrics); wall-clock numbers are
CPU-host timings of the production JAX layer (relative comparisons only —
TPU roofline projections live in benchmarks/roofline.py).

    PYTHONPATH=src python -m benchmarks.run [--with-roofline] [--smoke]

The multi-device scaling table (table7) shards over however many devices
are visible; on CPU simulate a fleet first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --smoke

Regression tracking (the ROADMAP "tracked regression table"): the smoke
numbers are pinned in ``benchmarks/baseline.json``.  CI runs

    ... python -m benchmarks.run --smoke --check-baseline

and fails on a >20% regression in any machine-independent row (schedule
cycle counts, the ``*_err`` accuracy rows, invariant flags — these are
bit-deterministic, so 20% is pure slack).  Wall-clock ``*_us`` rows are
first normalized by the host-speed factor (the median current/baseline
ratio across all ``*_us`` rows) and then held to a deliberately wide
noise band (``TIME_NOISE_FACTOR``): at smoke sizes, sharded dispatch on
simulated CPU devices jitters several-fold run to run, so the time gate
catches order-of-magnitude hot-path regressions, not 20% ones.  After an
intentional change, refresh the file with ``--write-baseline`` and
commit it.

``--check-baseline`` additionally enforces host-speed-independent
*ordering* invariants (``sanity_checks``): the fast tier strictly
cheaper than exact2 in table6, and table7 shard scaling not inverse
(shardN <= shard1 x ``SHARD_MONOTONE_TOL``).  Every ``--smoke`` run also
emits the staged block-program's analytic roofline to
``experiments/roofline/reduce_smoke.json`` (see
``roofline.reduce_program_table``).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

from benchmarks import paper_tables
from repro.launch.compile_cache import enable_compile_cache

BASELINE_PATH = Path(__file__).resolve().parent / "baseline.json"
#: fail threshold for machine-independent rows: >20% worse than baseline
REGRESSION_FACTOR = 1.2
#: absolute slack for near-zero deterministic rows (exact-tier errors)
REGRESSION_ATOL = 1e-12
#: wall-clock noise band (after host-speed normalization): smoke-size
#: timings on simulated devices jitter several-fold, so the time gate is
#: an order-of-magnitude tripwire, not a 20% one
TIME_NOISE_FACTOR = 4.0
#: table7 shard-scaling ratchet: time at N shards may exceed the 1-shard
#: time by at most this factor.  The real claim is "adding shards must
#: not make the reduction slower" — before inputs were pre-sharded and
#: carry merges fused, shard8 ran ~9x shard1; what remains at smoke
#: sizes is the per-device dispatch floor of simulating 8 devices on one
#: CPU core (~1.7x on the fast tier, whose whole reduction is sub-ms),
#: so the gate sits above that floor but far below the old pathology
SHARD_MONOTONE_TOL = 2.5


def sanity_checks(rows) -> list:
    """Relative-ordering invariants the baseline's per-row gates cannot
    see; return failure strings.

    These are *shape* claims about the current run, independent of host
    speed: the fast tier must actually be the cheap one (a fast tier
    slower than the all-int32 exact2 carry means the timing harness or
    the fast path itself regressed — the old async-dispatch mean once
    reported exactly that, 6421us vs 224us), and shard scaling must not
    be inverse (shardN beyond ``SHARD_MONOTONE_TOL`` x shard1 means
    per-call resharding or per-component collective overhead crept back
    into the distributed path).
    """
    current = {name: val for name, val, _ in rows}
    failures = []
    # every table6 family — the plain sum and the algebra ops riding the
    # same stream — must keep the fast tier cheaper than exact2
    for family in ("reduce", "weighted_sum", "moments"):
        fast = current.get(f"table6_{family}_fast_us")
        ex2 = current.get(f"table6_{family}_exact2_us")
        if fast is not None and ex2 is not None and fast >= ex2:
            failures.append(
                f"table6_{family}_fast_us ({fast:.1f}us) >= "
                f"table6_{family}_exact2_us ({ex2:.1f}us): the fast tier "
                f"must be cheaper than the 4-component integer carry")
    for pol in ("fast", "exact2"):
        s1 = current.get(f"table7_{pol}_shard1_us")
        if s1 is None:
            continue
        prefix = f"table7_{pol}_shard"
        for name, val in current.items():
            if (name.startswith(prefix) and name.endswith("_us")
                    and name != f"{prefix}1_us"
                    and val > s1 * SHARD_MONOTONE_TOL):
                failures.append(
                    f"{name}: {val:.1f}us > shard1 {s1:.1f}us x "
                    f"{SHARD_MONOTONE_TOL} (inverse shard scaling)")
    return failures


def check_baseline(rows, baseline: dict) -> list:
    """Compare ``rows`` against a baseline mapping; return failure strings.

    ``*_us`` rows are host-speed-normalized before the 20% gate; every
    other row (cycle counts, ``*_err`` accuracy rows, invariant flags) is
    machine-independent and gated directly.  Rows missing on either side
    are reported as failures too — the baseline must be refreshed
    (``--write-baseline``) in the same change that renames a benchmark.
    """
    current = {name: val for name, val, _ in rows}
    failures = [f"row {name!r} missing from baseline; refresh with "
                f"--write-baseline" for name in current
                if name not in baseline]
    failures += [f"baseline row {name!r} no longer produced; refresh "
                 f"with --write-baseline" for name in baseline
                 if name not in current]

    shared = [n for n in current if n in baseline]
    time_rows = [n for n in shared if n.endswith("_us")]
    ratios = [current[n] / baseline[n] for n in time_rows
              if baseline[n] > 0]
    speed = statistics.median(ratios) if ratios else 1.0

    for name in shared:
        cur, base = current[name], baseline[name]
        if name.endswith("_us"):
            limit = base * speed * TIME_NOISE_FACTOR
            if cur > limit:
                failures.append(
                    f"{name}: {cur:.1f}us > {limit:.1f}us "
                    f"(baseline {base:.1f}us x host-speed {speed:.2f} "
                    f"x {TIME_NOISE_FACTOR})")
        elif cur > base * REGRESSION_FACTOR + REGRESSION_ATOL:
            failures.append(f"{name}: {cur:.6g} > {base:.6g} "
                            f"x {REGRESSION_FACTOR}")
    return failures


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--with-roofline", action="store_true",
                    help="also rebuild the roofline table from "
                         "experiments/dryrun")
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI subset: the schedule table and the "
                         "full five-policy sweep at reduced sizes")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the smoke numbers to benchmarks/"
                         "baseline.json (commit it)")
    ap.add_argument("--check-baseline", action="store_true",
                    help="fail (exit 1) on a >20% regression vs the "
                         "tracked benchmarks/baseline.json")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if (args.write_baseline or args.check_baseline) and not args.smoke:
        ap.error("--write-baseline/--check-baseline track the --smoke "
                 "subset; pass --smoke too")

    rows = []
    if args.smoke:
        paper_tables.table1_schedule(rows)
        paper_tables.table6_reduce_policies(rows, smoke=True)
        paper_tables.table6c_algebra_ops(rows, smoke=True)
        paper_tables.table6b_large_n_resolution(rows, smoke=True)
        paper_tables.table7_shard_scaling(rows, smoke=True)
        paper_tables.table8_serving(rows, smoke=True)
        paper_tables.table9_fault_overhead(rows, smoke=True)
    else:
        paper_tables.table1_schedule(rows)
        paper_tables.table2_pis_registers(rows)
        paper_tables.table3_accumulator_comparison(rows)
        paper_tables.table5_intac(rows)
        paper_tables.table6_reduce_policies(rows)
        paper_tables.table6c_algebra_ops(rows)
        paper_tables.table6b_large_n_resolution(rows)
        paper_tables.table7_shard_scaling(rows)
        paper_tables.table8_serving(rows)
        paper_tables.table9_fault_overhead(rows)

    print("name,value,derived")
    for name, val, derived in rows:
        print(f"{name},{val:.6g},{derived}")

    if args.smoke:
        # the staged block-program's analytic roofline rides along with
        # every smoke run as a JSON artifact (pure analysis, no arrays)
        from benchmarks import roofline
        art_dir = Path("experiments/roofline")
        art_dir.mkdir(parents=True, exist_ok=True)
        rrows = roofline.reduce_program_table()
        art = art_dir / "reduce_smoke.json"
        art.write_text(json.dumps(rrows, indent=2) + "\n")
        print(f"roofline: wrote {len(rrows)} reduce-program rows to {art}")

    if args.write_baseline:
        BASELINE_PATH.write_text(json.dumps(
            {name: val for name, val, _ in rows}, indent=2,
            sort_keys=True) + "\n")
        print(f"baseline: wrote {len(rows)} rows to {BASELINE_PATH}")
    if args.check_baseline:
        if not BASELINE_PATH.exists():
            print(f"baseline: {BASELINE_PATH} missing; run with "
                  f"--write-baseline and commit it")
            sys.exit(1)
        baseline = json.loads(BASELINE_PATH.read_text())
        failures = check_baseline(rows, baseline) + sanity_checks(rows)
        if failures:
            print(f"baseline: {len(failures)} regression(s) vs "
                  f"{BASELINE_PATH.name}:")
            for f in failures:
                print(f"  {f}")
            sys.exit(1)
        print(f"baseline: {len(baseline)} rows within "
              f"{REGRESSION_FACTOR}x of {BASELINE_PATH.name}")

    if args.with_roofline and Path("experiments/dryrun").exists():
        from benchmarks import roofline
        rl = roofline.build_table("experiments/dryrun")
        print()
        print(roofline.to_markdown(rl))


if __name__ == "__main__":
    main()
