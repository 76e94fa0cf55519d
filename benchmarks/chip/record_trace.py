"""Record the chip trace that ``tests/test_programs.py`` reads; not part of
a benchmark run.

    python3 benchmarks/chip/record_trace.py --workload <cell> --seed <n> \\
        --seconds <s> --out <file.json.gz> [--rehearse]

One process serves one traced window of the cell, as a ``--trace 1`` run
does, and writes the trace in ``harness.programs.load``'s form, plus
``phases``: ``[t0, t1, name]`` (``perf_counter`` seconds) of the phases the
program recorded (``hub.phases``) wholly inside the traced part, and the
device it ran on.  The module table is logged to standard error.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from harness import programs, spec  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    _, info = run.init_jax(args.rehearse, cell.chips)
    S = run.setup(cell, args.seed, args.rehearse)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        hub, _, _ = run.serve_window(S, args.seed, args.seconds, trace_dir)
        tr = programs.load(trace_dir, "cpu" if args.rehearse
                           else "/device:TPU:0")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    lo, hi = hub.trace_t[0], hub.trace_t[1]
    tr["phases"] = [[t0, t1, name] for name, t0, t1, _ in (hub.phases or ())
                    if lo <= t0 and t1 <= hi]
    tr["device"] = info
    for mod, (dev, busy, n) in sorted(programs.by_module(tr).items(),
                                      key=lambda kv: -kv[1][0]):
        run.log(f"{mod}: {int(n)} runs, {dev:.6f} s, busy {busy:.6f} s")
    run.log(f"{len(tr['modules'])} module runs, {len(tr['host'])} "
            f"annotations, {len(tr['phases'])} recorded phases")
    with gzip.open(args.out, "wt") as f:
        json.dump(tr, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
