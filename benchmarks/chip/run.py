"""Chip benchmark: one cell of ``BENCHMARK.json``, one run, one result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--rehearse]

One process loads the served model (random bf16 weights from ``--seed``),
warms up every shape the cell's traffic uses, measures for ``--seconds``
through ``Scheduler.run_continuous`` over one paged, streaming-prefill GEAR
engine, then checks a sample of the requests it served against a plain
float32 reference.  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics, read from a profiler
trace of part of the window), ``device``, ``breakdown`` (traced runs) and,
last, ``checks``: each number compared for ``correct`` with its limit.
Logs go to standard error.

This file holds no model-specific code.  The configuration file names its
architecture in ``"arch_module"``, and ``archs/<arch_module>.py``
(``harness/arch.py``) maps it onto the program's ``ModelConfig``, holds its
reference (built on ``harness/reference.py``) and counts its FLOPs.

The run needs a TPU: without one (or with fewer chips than the cell asks
for) it exits 1 and prints no result.  ``--rehearse`` runs the same control
flow on the CPU at a tiny size with the kernels in interpret mode; its
device says ``cpu`` and it is never a cell result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))

from harness import spec  # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on the CPU, kernels in interpret mode")
    ap.add_argument("--keep-trace", default="",
                    help="also write the trace, as the reducer reads it, to "
                         "this .json.gz file")
    return ap.parse_args(argv)


def device_info(jax, rehearse: bool, chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if rehearse:
        if d.platform != "cpu":
            raise SystemExit("--rehearse runs on the CPU (JAX_PLATFORMS=cpu)")
    elif d.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {d.platform!r} devices; the "
                         "benchmark runs only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


class CompileCounter:
    """Backend compilations (and persistent-cache reads), from JAX's own
    monitoring events, with the time each was seen."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.seen: list[tuple[float, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == self.EVENT:
            self.seen.append((time.perf_counter(), secs))

    def between(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t, _ in self.seen)


def sized(cell, rehearse: bool) -> tuple[dict, dict, dict]:
    """(configuration, mix, serving) as run: the files' own, or with their
    ``rehearse`` blocks laid over them."""
    cfg = {k: v for k, v in cell.config.items() if k != "rehearse"}
    mix = {k: v for k, v in cell.traffic.items() if k != "rehearse"}
    if rehearse:
        cfg.update(cell.config.get("rehearse", {}))
        mix.update(cell.traffic.get("rehearse", {}))
    return cfg, mix, cfg["serving"]


def load_reader(name: str):
    """``metrics/<name>.py``: one per-layer metric's reader."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def sample_for_check(rng, finished: list, want_tokens: int) -> list:
    """The longest finished request, then others drawn from the seed, until
    ``want_tokens`` served tokens are in the sample."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: (len(r[2]), len(r[1]), r[0]))
    picked = [by_len[-1]]
    rest = [by_len[i] for i in rng.permutation(len(by_len) - 1)]
    while rest and sum(len(r[2]) for r in picked) < want_tokens:
        picked.append(rest.pop())
    return picked


def setup(cell, seed: int, rehearse: bool):
    """Configuration, weights from ``seed`` and a warmed engine."""
    import jax

    from harness import arch, traffic, warmup, weights
    from repro.core.policy import named_policy
    from repro.models.model import build_model
    from repro.serving import Engine, EngineConfig

    cfg, mix, serving = sized(cell, rehearse)
    A = arch.of(cfg)
    mcfg = A.model_config(cfg)
    pol = named_policy(serving["policy"])
    nb = pol.buffer_size
    cap = -(-(traffic.max_context(mix) + 1) // nb) * nb
    slots = serving["slots"]
    log(f"{cell.name}: {mcfg.name} {mcfg.num_layers} layers, d_model "
        f"{mcfg.d_model}, {mcfg.num_heads}/{mcfg.num_kv_heads} heads x "
        f"{mcfg.head_dim}, vocab {mcfg.vocab_size}; {slots} slots x {cap} "
        f"tokens, pool_bytes {serving['pool_bytes']}")
    model = build_model(mcfg)
    t0 = time.perf_counter()
    params = weights.make_weights(model.init_abstract(), seed)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t0
    engine = Engine(model, params, EngineConfig(
        batch=slots, capacity=cap, policy=pol, eos_id=-1, temperature=0.0,
        fused="interpret" if rehearse else "auto",
        prefill_mode="streaming", layout="paged",
        pool_bytes=serving["pool_bytes"]))
    t0 = time.perf_counter()
    warmup.warm(engine, mix, nb, mcfg.vocab_size, log)
    return types.SimpleNamespace(
        cell=cell, cfg=cfg, arch=A, mix=mix, mcfg=mcfg, pol=pol, nb=nb, cap=cap,
        slots=slots, model=model, params=params, engine=engine,
        t_weights=t_weights, t_warm=time.perf_counter() - t0)


def serve_window(S, seed: int, seconds: float, trace_dir: str | None):
    """One measured window at the cell's load; returns (hub, results, reqs)."""
    from harness import traffic
    from harness.window import Hub
    from repro.serving import Scheduler

    reqs = traffic.generate(S.mix, seed, S.mcfg.vocab_size, S.nb, S.slots)
    hub = Hub(reqs, S.slots, seconds, trace_dir=trace_dir)
    S.engine.obs = hub
    sched = Scheduler(S.engine, clock=hub.sched_clock)
    hub.sched = sched
    t0 = time.perf_counter()
    hub.start_clients()
    results = sched.run_continuous()
    hub.stop_trace()
    hub.sched = None
    S.engine.obs = None
    hub.t_fill = hub.t_open - t0
    return hub, results, reqs


def window_numbers(hub) -> dict:
    """End-to-end numbers of a window, over all of its samples."""
    import numpy as np

    from harness import stats
    lo, hi = hub.t_open, hub.t_close
    recs = list(hub.recs.values())
    times = {r.rid: r.tok for r in recs}
    n_tok = sum(int(np.sum((np.asarray(t) >= lo) & (np.asarray(t) <= hi)))
                for t in times.values())
    itl = stats.token_gaps(times, lo, hi)
    due_in = [r for r in recs if lo <= r.due < hi]
    ended = [r for r in due_in if r.end is not None and r.end <= hi]
    failed = [r for r in ended if r.status != "ok"]
    log(f"window {hi - lo:.3f} s: {n_tok} tokens, {len(itl)} gaps, "
        f"{len(due_in)} requests due, {len(ended)} ended ({len(failed)} not "
        f"ok)")
    return {"attempted": len(ended), "failed": len(failed), "e2e": {
        "output_tokens_per_s": n_tok / (hi - lo),
        "itl_p95_ms": None if not itl else 1e3 * stats.percentile(itl, 95),
    }}


def finished_requests(hub, results, reqs) -> list:
    """(rid, prompt, served tokens) of every request that completed in the
    window with all of its tokens."""
    import numpy as np
    served = {r.rid: np.asarray(r.tokens) for r in results}
    return [(r.rid, reqs[r.rid].prompt, served[r.rid])
            for r in hub.recs.values()
            if r.status == "ok" and r.end is not None and r.end <= hub.t_close
            and len(served.get(r.rid, ())) == r.output]


def logit_gaps(S, params, finished: list, seed: int, control: bool = False):
    """Reference logit gaps of the served tokens of a sample of ``finished``
    drawn from ``seed`` (or, with ``control``, of the tokens the float8
    control puts first at the same positions).  Returns (widest, requests,
    tokens, mean gap); the mean is the number compared for ``correct``."""
    import numpy as np

    from harness import reference, traffic
    sample = sample_for_check(traffic.rng_for(seed + 1), finished,
                              S.cell.limits["check_tokens"])
    pad = -(-S.cap // reference.Q_BLOCK) * reference.Q_BLOCK
    g = [S.arch.gaps(S.cfg, params, p, s, pad, control)
         for _, p, s in sample]
    if not g:
        return None, 0, 0, None
    g = np.concatenate(g)
    return float(g.max()), len(sample), int(g.size), float(g.mean())


def per_layer(S, hub, info: dict, trace_dir: str, rehearse: bool,
              keep: str = ""):
    """Per-layer metrics of a traced window, the device's busy and window
    seconds, and the breakdown."""
    from harness import flops, layers
    from harness import trace as trace_lib
    cell = S.cell
    readers = {m["name"]: load_reader(m["name"]) for m in cell.per_layer}
    marks = {r.KERNEL: r.KERNEL for r in readers.values() if hasattr(r, "KERNEL")}
    raw = trace_lib.load(trace_dir, "cpu" if rehearse else "/device:TPU:0")
    if keep:
        import gzip
        with gzip.open(keep, "wt") as f:
            json.dump(raw, f)
    tr = trace_lib.reduce(raw, marks)
    t_tr = hub.trace_t
    ctx = types.SimpleNamespace(
        hub=hub, lo=hub.t_open, hi=hub.t_close, trace=tr,
        traced=(t_tr[0], t_tr[1]), stall=(t_tr[1], t_tr[2]),
        peaks=None if rehearse else spec.peaks(info["kind"]),
        model=S.arch.flop_model(S.cfg),
        gear=flops.Gear(head_dim=S.mcfg.head_dim, chunk=S.nb, bits=S.pol.bits,
                        rank=S.pol.rank, sparsity=S.pol.sparsity),
        slots=S.slots, kv_heads=S.mcfg.num_kv_heads,
        group=S.mcfg.num_heads // S.mcfg.num_kv_heads)
    metrics = {}
    for m in cell.per_layer:
        if rehearse and m["source"] == "device_trace":
            continue                # a CPU number is never a device metric
        v = readers[m["name"]].read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    log(f"trace: window {tr['window_s']:.6f} s, busy {tr['busy_s']:.6f} s, "
        f"kernels {tr['kernel_s']} calls {tr['kernel_calls']}; "
        f"{len(layers.traced_steps(ctx))} decode steps traced")
    dev = {} if rehearse else {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
    return metrics, dev, {"device_ops": tr["top_ops"], "idle_gaps": tr["idle_gaps"]}


def init_jax(rehearse: bool, chips: int):
    import jax
    info = device_info(jax, rehearse, chips)
    jax.config.update("jax_compilation_cache_dir", str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no LRU eviction: it reads every entry's access-time file and fails a
    # write when one is missing
    jax.config.update("jax_compilation_cache_max_size", -1)
    log(f"device {info}")
    return jax, info


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = spec.load_cell(args.workload)
    jax, info = init_jax(args.rehearse, cell.chips)
    compiles = CompileCounter(jax)
    S = setup(cell, args.seed, args.rehearse)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    hub, results, reqs = serve_window(S, args.seed, args.seconds, trace_dir)
    setup_s = hub.t_open - T_START
    log(f"set-up {setup_s:.3f} s: weights {S.t_weights:.3f} s, warm-up "
        f"{S.t_warm:.3f} s, slot fill {hub.t_fill:.3f} s; compilations inside "
        f"the window: {compiles.between(hub.t_open, hub.t_close)}")
    w = window_numbers(hub)
    w["e2e"]["setup_s"] = setup_s
    mem = jax.devices()[0].memory_stats() or {}
    device = dict(info, memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)))
    breakdown = None
    if args.trace:
        metrics, dev, breakdown = per_layer(S, hub, info, trace_dir,
                                            args.rehearse, args.keep_trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(dev)
    else:
        metrics = {m["name"]: {"value": float(w["e2e"][m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end if w["e2e"].get(m["name"]) is not None}

    # -- correct: served tokens against the plain reference, once the
    # program's state is freed
    finished = finished_requests(hub, results, reqs)
    params = S.params
    S.engine = None
    del hub, results
    gc.collect()
    t0 = time.perf_counter()
    widest, n_req, n_tok, mean = logit_gaps(S, params, finished, args.seed)
    limit = float(cell.limits["mean_logit_gap"])
    correct = mean is not None and mean <= limit and w["failed"] == 0
    log(f"reference: {n_req} requests, {n_tok} served tokens, "
        f"{time.perf_counter() - t0:.3f} s; widest gap {widest!r}")

    out = {"correct": correct, "attempted": w["attempted"],
           "failed": w["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if args.rehearse:
        out["rehearsal"] = True
    out["checks"] = {"mean_logit_gap": {"value": mean, "limit": limit}}
    log(f"check mean_logit_gap {mean!r} limit {limit!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
