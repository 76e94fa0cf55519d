"""Serving launcher: mixed-length requests through a GEAR-compressed cache.

  PYTHONPATH=src python -m repro.launch.serve --arch llama2-7b --smoke \
      --policy gear_kcvt4 --batch 4 --prompt 64 --gen 32

Built entirely on the public :mod:`repro.serving` API: submits per-prompt
:class:`Request` objects to :meth:`Scheduler.run_continuous` (slot-level
continuous batching over ``--batch`` slots).  ``--layout paged`` serves
from the pooled compressed-chunk page layout (pages are reserved per
request, so concurrency is pool-bytes-limited instead of
slot-count-limited).
"""

from __future__ import annotations

import argparse
import dataclasses
import os
from pathlib import Path

import jax
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.policy import named_policy
from repro.launch.mesh import make_test_mesh
from repro.models.model import build_model
from repro.serving import (CacheLayout, Engine, EngineConfig, ObsConfig,
                           Request, Scheduler)


# the checkout root: src/repro/launch/serve.py -> <checkout>
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is used as is (JAX reads it
    itself).  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: a fixed path is what lets the next process
    that runs from this checkout find what this one compiled.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--policy", default="gear_kcvt4")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--buffer", type=int, default=0, help="override n_b")
    ap.add_argument("--mesh", default="")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--layout", default="dense", choices=["dense", "paged"])
    ap.add_argument("--pool-bytes", type=int, default=0,
                    help="paged: pool device-byte budget (default: dense-"
                         "equivalent batch*n_chunks pages)")
    ap.add_argument("--requests", type=int, default=0,
                    help="queued requests (default 2*batch)")
    ap.add_argument("--obs", action="store_true",
                    help="enable serving telemetry (metrics + traces)")
    ap.add_argument("--fidelity-every", type=int, default=0,
                    help="obs: probe every Nth closed chunk (0 = off)")
    ap.add_argument("--metrics-json", default="",
                    help="write the metrics registry snapshot here (JSON; "
                         "implies --obs)")
    ap.add_argument("--trace-out", default="",
                    help="write Chrome trace_event JSON here (implies --obs)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.metrics_json or args.trace_out or args.fidelity_every:
        args.obs = True

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    pol = named_policy(args.policy)
    if args.buffer:
        pol = dataclasses.replace(pol, buffer_size=args.buffer,
                                  group=min(pol.group, args.buffer))
    layout = CacheLayout(args.layout)
    mesh = None
    if args.mesh:
        dims = [int(v) for v in args.mesh.split("x")]
        mesh = make_test_mesh(*dims)

    params = model.init(jax.random.PRNGKey(0))
    cap = args.prompt + args.gen
    obs_cfg = (ObsConfig(fidelity_every_n=max(args.fidelity_every, 0))
               if args.obs else None)
    eng = Engine(model, params,
                 EngineConfig(batch=args.batch, capacity=cap, policy=pol,
                              temperature=args.temperature, layout=layout,
                              pool_bytes=args.pool_bytes, obs=obs_cfg),
                 mesh=mesh)
    key = jax.random.PRNGKey(1)

    sched = Scheduler(eng)
    n_req = args.requests or 2 * args.batch
    # genuinely mixed-length raw prompts (--prompt is the longest): the
    # engine length-buckets each one internally, no scheduler padding
    lo = max(1, args.prompt // 2)
    for rid in range(n_req):
        plen = lo + rid % (args.prompt - lo + 1)
        toks = np.asarray(jax.random.randint(
            jax.random.fold_in(key, rid), (plen,), 0, cfg.vocab_size))
        sched.submit(Request(rid=rid, tokens=toks, max_new_tokens=args.gen))
    results = sched.run_continuous()
    st = sched.last_stats
    line = (f"served {len(results)} requests ({st['tokens']} tokens) in "
            f"{st['wall_s']:.2f}s; attend={st['attend_path']} "
            f"layout={st['layout']}")
    if "pool" in st:
        p = st["pool"]
        line += (f"; pool {p['used_pages']}/{p['used_pages'] + p['free_pages']}"
                 f" pages used, {p['shared_pages']} shared")
    print(line)
    if eng.obs is not None:
        cov = eng.obs.tracer.coverage([r.rid for r in results])
        line = (f"obs: traces {len(cov['statuses'])}/{len(results)} rids"
                f" complete={cov['complete']}")
        if eng.obs.fidelity is not None:
            line += f", fidelity probes {len(eng.obs.fidelity.reports)}"
        print(line)
        if args.metrics_json:
            eng.obs.write_metrics_json(args.metrics_json)
            print(f"obs: metrics snapshot -> {args.metrics_json}")
        if args.trace_out:
            eng.obs.write_trace(args.trace_out)
            print(f"obs: chrome trace -> {args.trace_out}")


if __name__ == "__main__":
    main()
