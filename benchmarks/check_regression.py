"""CI bench regression gate: diff bench-out/*.json against baseline.json.

Benches emit rows whose ``value`` field is a machine-readable metric
(:func:`benchmarks.common.emit`).  This script compares every metric named
in the committed baseline against the freshly-measured rows and fails on:

* ``*tok_per_s*``  — throughput more than ``--tol`` (default 15%) BELOW the
  baseline (timing metrics; slack absorbs runner jitter, a real fused-path
  or scheduler regression is far larger);
* ``*_over_*``     — relative ratios (e.g. fused-vs-XLA attend), same
  ``--tol`` floor; both sides are measured in the same run, so these are
  machine-independent and catch a path regression even when absolute tok/s
  baselines were recorded on different hardware;
* ``*nbytes*``     — ANY growth (byte accounting is deterministic: cache
  growth means the compressed layout regressed, so zero tolerance);
* ``*peak_bytes*`` — growth beyond ``--mem-tol`` (default 5%): these come
  from XLA's compiled memory analysis (bench_prefill's streaming-vs-
  monolithic peak), which is deterministic per jax version but may shift a
  few percent across compiler releases — a real peak-memory regression
  (e.g. the streaming pipeline re-materializing FP16 history) is far
  larger;
* ``*hit_rate*`` / ``*toks_saved*`` — ANY drop (the canned shared-prefix
  workload of bench_prefix is deterministic: fewer trie hits means the
  prefix cache stopped matching or admission broke, so zero tolerance);
* ``*ok_rate*`` — ANY drop (bench_throughput ``--chaos``: the fault-FREE
  path with the resilience layer armed must keep every request ``OK`` —
  a drop means retries/valve/quarantine fired on healthy traffic);
* ``*overhead_frac*`` — growth ABOVE the committed ceiling (bench_throughput
  ``--obs`` / bench_prefix ``--obs``: fractional tok/s lost to telemetry;
  the baseline is a ceiling, not a floor — lower is better, and exceeding
  it means the observability layer started costing real throughput);
* ``*concurrent_over*`` — bench_paged's fixed-byte packing ratio: pure page
  arithmetic from the engine's own byte accounting, so ANY drop fails, plus
  an absolute >= 3x floor (the paged layout's headline capacity claim);
* metrics missing from the bench output (a silently-dropped bench row must
  fail loudly, not skip the gate).

Refresh the baseline after an intentional change with::

    python -m benchmarks.bench_throughput --smoke --json bench-out/throughput.json
    python -m benchmarks.check_regression bench-out --write-baseline --derate 0.6

``--derate`` scales the recorded *absolute* tok/s floors (ratios and byte
counts stay exact) so a baseline measured on a fast dev machine does not
false-fail on slower CI runners.  The committed baseline keeps the absolute
floors aggressively derated (~0.4) as a catastrophic-collapse backstop; the
``*_over_*`` ratio rows are the sensitive, machine-independent guard.

Exit status: 0 clean, 1 on any regression (CI fails the step).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")
CONCURRENCY_FLOOR = 3.0      # bench_paged: min concurrent-contexts ratio


def load_rows(bench_dir: str) -> dict[str, float]:
    """name -> value for every row (in any bench-out JSON) carrying a value."""
    rows: dict[str, float] = {}
    paths = sorted(glob.glob(os.path.join(bench_dir, "*.json")))
    if not paths:
        sys.exit(f"check_regression: no *.json under {bench_dir!r}")
    for path in paths:
        with open(path) as f:
            for row in json.load(f):
                if row.get("value") is not None:
                    rows[row["name"]] = float(row["value"])
    return rows


def governed(name: str) -> bool:
    return ("tok_per_s" in name or "nbytes" in name or "peak_bytes" in name
            or "_over_" in name or "hit_rate" in name or "toks_saved" in name
            or "ok_rate" in name or "overhead_frac" in name)


def check(baseline: dict[str, float], rows: dict[str, float],
          tol: float, mem_tol: float = 0.05) -> list[str]:
    failures = []
    for name, ref in sorted(baseline.items()):
        new = rows.get(name)
        if new is None:
            failures.append(f"{name}: missing from bench output (baseline {ref:g})")
        elif "nbytes" in name and new > ref:
            failures.append(f"{name}: {new:g} bytes > baseline {ref:g} (any growth fails)")
        elif "overhead_frac" in name:
            # telemetry cost ceiling: the committed value is the MAXIMUM
            # tolerable fraction of tok/s lost with observability enabled
            if new > ref + 1e-9:
                failures.append(
                    f"{name}: {new:g} > ceiling {ref:g} (telemetry overhead "
                    "budget exceeded)")
            else:
                print(f"ok   {name}: {new:g} (ceiling {ref:g})")
        elif (("hit_rate" in name or "toks_saved" in name
               or "ok_rate" in name) and new < ref - 1e-9):
            failures.append(
                f"{name}: {new:g} < baseline {ref:g} (deterministic canned "
                "workload: any drop fails)")
        elif "peak_bytes" in name:
            if new > ref * (1.0 + mem_tol):
                failures.append(
                    f"{name}: {new:g} bytes > {ref * (1.0 + mem_tol):g} "
                    f"(baseline {ref:g} + {mem_tol:.0%} compiler headroom)")
            else:
                print(f"ok   {name}: {new:g} (baseline {ref:g})")
        elif "concurrent_over" in name:
            # bench_paged's packing ratio is pure byte math (page counts from
            # the engine's own accounting) — deterministic, so any drop fails,
            # and the paper-level claim keeps an absolute >= 3x floor
            if new < CONCURRENCY_FLOOR - 1e-9:
                failures.append(
                    f"{name}: {new:g}x below the {CONCURRENCY_FLOOR:g}x "
                    "concurrency floor (paged packing broke)")
            elif new < ref - 1e-9:
                failures.append(
                    f"{name}: {new:g} < baseline {ref:g} (deterministic page "
                    "math: any drop fails)")
            else:
                print(f"ok   {name}: {new:g} (baseline {ref:g})")
        elif "nbytes" not in name and new < ref * (1.0 - tol):
            failures.append(
                f"{name}: {new:g} < {ref * (1.0 - tol):g} "
                f"(baseline {ref:g} - {tol:.0%} tolerance)")
        else:
            print(f"ok   {name}: {new:g} (baseline {ref:g})")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("bench_dir", help="directory of bench *.json row dumps")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE)
    ap.add_argument("--tol", type=float, default=0.15,
                    help="allowed fractional tok_per_s drop (default 0.15)")
    ap.add_argument("--mem-tol", type=float, default=0.05,
                    help="allowed fractional *peak_bytes* growth (default 0.05)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the governed metrics of this run as the new baseline")
    ap.add_argument("--derate", type=float, default=1.0,
                    help="scale recorded absolute tok_per_s floors at "
                         "--write-baseline time (cross-machine headroom)")
    args = ap.parse_args(argv)

    rows = load_rows(args.bench_dir)
    if args.write_baseline:
        # derate only ABSOLUTE throughput floors; *_over_* ratio rows are
        # measured within one run and must stay exact even when their name
        # contains tok_per_s (e.g. prefill_tok_per_s/streaming_over_monolithic)
        base = {n: v * (args.derate if "tok_per_s" in n and "_over_" not in n
                        else 1.0)
                for n, v in sorted(rows.items()) if governed(n)}
        if not base:
            sys.exit("check_regression: no governed (*tok_per_s* / *nbytes* / "
                     "*peak_bytes* / *_over_*) rows to baseline")
        with open(args.baseline, "w") as f:
            json.dump(base, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {len(base)} baseline metrics to {args.baseline}")
        return 0

    with open(args.baseline) as f:
        baseline = json.load(f)
    failures = check(baseline, rows, args.tol, args.mem_tol)
    for msg in failures:
        print(f"FAIL {msg}", file=sys.stderr)
    if failures:
        print(f"check_regression: {len(failures)} regression(s) vs {args.baseline}",
              file=sys.stderr)
        return 1
    print(f"check_regression: {len(baseline)} metrics within tolerance")
    return 0


if __name__ == "__main__":
    sys.exit(main())
