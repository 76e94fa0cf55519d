"""Readings that set a cell's limit; not part of a benchmark run.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control-seeds 1,2,3] [--seconds 20] [--rehearse]

One process builds the cell's engine once, then for each seed swaps in that
seed's weights (``Engine.set_params``), serves one window at the cell's
load, and reads the reference logit gaps of the served tokens, widest and
mean (the program's readings; the mean is the number compared).  For the
control seeds it also reads the gaps of the tokens the float8 control puts
first at the same positions (the control's readings, which the limit must
fail).  One JSON line per reading on
standard output, with the window's end-to-end numbers, its mean decode step
and the process's peak device memory.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402
from harness import spec  # noqa: E402


def ints(s: str) -> list[int]:
    return [int(x) for x in s.split(",") if x]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    jax, info = run.init_jax(args.rehearse, cell.chips)
    from harness import weights

    seeds = args.seeds or args.control_seeds
    S = run.setup(cell, seeds[0] if seeds else 0, args.rehearse)
    emit = lambda d: print(json.dumps(dict(d, device=info["kind"])), flush=True)

    for seed in sorted(set(args.seeds) | set(args.control_seeds)):
        if seed != seeds[0]:
            S.params = None
            S.engine.set_params(None)           # one set of weights at a time
            S.params = weights.make_weights(S.model.init_abstract(), seed)
            S.engine.set_params(S.params)
        hub, results, reqs = run.serve_window(S, seed, args.seconds, None)
        w = run.window_numbers(hub)
        fin = run.finished_requests(hub, results, reqs)
        t0 = time.perf_counter()
        g, n_req, n_tok, mean = run.logit_gaps(S, S.params, fin, seed)
        step_s = [s for end, s, _ in hub.steps if hub.t_open <= end <= hub.t_close]
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
        row = {"seed": seed, "program_gap": g, "program_mean": mean,
               "requests": n_req, "tokens": n_tok, "slots": S.slots,
               "decode_step_ms": 1e3 * sum(step_s) / max(len(step_s), 1),
               "memory_peak_bytes": peak, **w}
        if seed in args.control_seeds:
            cg = run.logit_gaps(S, S.params, fin, seed, control=True)
            row.update(control_gap=cg[0], control_mean=cg[3])
        row["reference_s"] = time.perf_counter() - t0
        emit(row)
    return 0


if __name__ == "__main__":
    sys.exit(main())
