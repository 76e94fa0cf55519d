"""Bring-up check: serve minicpm-2b at full width on a TPU through the GEAR
Pallas kernels, and hold the served logits to a plain XLA reference.

    python chip_smoke.py                # one chip: phases A and B
    python chip_smoke.py --chips 4      # four chips: the (data=1, model=4)
                                        # mesh engine against device 0 alone
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]

One process drives everything (a chip belongs to one process).  Weights are
random from ``--seed``, held in bf16 (the engine casts every weight to bf16
at use, so the numbers are the ones an f32 tree would give).

* Phase A, the default path: dense cache, monolithic prefill, ``fused="auto"``
  (``flash_prefill`` + ``gear_decode``), driven by ``Scheduler.run_continuous``.
* Phase B, the rest of the serving stack: paged cache (``gear_decode_paged``
  in decode), streaming prefill (``gear_compress``, ``gear_decode`` for
  history, ``flash_prefill_block``).
* Phase K (one chip): ``gear_decode``, ``flash_prefill``,
  ``flash_prefill_block`` and ``gear_compress`` (every output) against
  their jnp oracles on identical random inputs at the model's attention
  widths, with ragged per-row extents, held to ``KERNEL_BOUND``.
* Each phase's served tokens are fed, teacher-forced, to a reference engine:
  ``fused="off"`` (the XLA/jnp path; phase B's reference keeps the dense
  layout, which decodes bit-identically to the paged one), or for
  ``--chips 4`` the same engine on device 0 alone.  Logits at the last
  prefill position and the first decode steps of every request are
  compared by ``||delta|| / ||ref||`` against the comparison's bound.

Times printed are set-up and sanity figures, not speed results: compile
seconds are XLA's backend compile time (persistent-cache reads included),
the rest is host wall time.  The last line of stdout is one JSON object; it
is printed only when every check passed on a TPU.  ``--rehearse`` runs a
tiny configuration on the CPU with the kernels in interpret mode, to check
the control flow; it never reports a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config, smoke_config  # noqa: E402
from repro.core.cache import (CacheConfig, init_layer_cache,  # noqa: E402
                              prefill_layer_cache)
from repro.core.outlier import outlier_count  # noqa: E402
from repro.core.policy import named_policy  # noqa: E402
from repro.kernels import ref as oracle  # noqa: E402
from repro.kernels.flash_prefill import flash_prefill, flash_prefill_block  # noqa: E402
from repro.kernels.gear_compress import gear_compress  # noqa: E402
from repro.kernels.gear_decode import gear_decode  # noqa: E402
from repro.launch.mesh import make_test_mesh  # noqa: E402
from repro.launch.serve import enable_compile_cache  # noqa: E402
from repro.models.model import build_model  # noqa: E402
from repro.serving import Engine, EngineConfig, Request, Scheduler  # noqa: E402

ARCH = "minicpm-2b"
SLOTS = 8
REQUESTS = 12
LENGTHS = (256, 512, 1024)      # prompt lengths, cycled over the requests
GEN = 32                        # new tokens per request, greedy
FORCED_STEPS = 8                # decode steps compared teacher-forced
# Kernel vs its jnp oracle on identical inputs: the tight check.
KERNEL_BOUND = 1e-2
# Engine logits vs the reference engine, one bound per comparison, each set
# from its own readings on a v5e (PERF.md).  Two correct attention paths
# differ at the f32-ulp level; bf16 activations round that up to a bf16 ulp,
# 40 layers of a random-weight model grow it to ~2% of the logits norm, and
# the 4-bit cache turns it into flipped codes in later layers.
BOUND_DENSE = 4e-2      # phase A and the mesh: read 2.41e-2 and 2.43e-2
# phase B: read 5.44e-2, and 5.31e-2 with the compression residual repaired;
# its prefill attends the 4-bit history, so flipped codes feed every layer
BOUND_STREAMING = 8e-2


def parse_args():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny config on the CPU, kernels in interpret mode")
    return ap.parse_args()


def log(msg: str) -> None:
    print(msg, flush=True)


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


class CompileClock:
    """Seconds of XLA backend compilation (persistent-cache reads included),
    summed from JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration_secs: float, **_) -> None:
        if event == self.EVENT:
            self.total += duration_secs


def init_params(model, seed: int):
    """Random weights from ``seed``, cast to bf16 inside the same program
    so the f32 tree never sits whole in device memory."""
    cast = jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), model.init(key)))
    return cast(jax.random.PRNGKey(seed))


def make_requests(seed: int, lengths, n: int, gen: int, vocab: int):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, tokens=rng.integers(0, vocab, lengths[i % len(lengths)],
                                               dtype=np.int32),
                    max_new_tokens=gen)
            for i in range(n)]


def serve(engine: Engine, requests: list[Request]) -> tuple[dict, dict]:
    """Serve ``requests`` through ``Scheduler.run_continuous``.

    A first pass over one request per distinct prompt length compiles every
    program the timed pass uses (prefill per length, decode, splice, reset);
    it is reported as set-up.  Returns (rid -> generated tokens, figures)."""
    warm = list({len(r.tokens): r for r in requests}.values())
    t0 = time.time()
    sched = Scheduler(engine)
    for r in warm:
        sched.submit(Request(rid=-1 - r.rid, tokens=r.tokens,
                             max_new_tokens=r.max_new_tokens))
    sched.run_continuous()
    setup_s = time.time() - t0

    sched = Scheduler(engine)
    for r in requests:
        sched.submit(r)
    t0 = time.time()
    results = sched.run_continuous()
    serve_s = time.time() - t0
    bad = [(r.rid, str(r.status), r.error) for r in results
           if str(r.status) != "ok"]
    if bad or len(results) != len(requests):
        raise SystemExit(f"serving failed: {bad or len(results)}")
    st = sched.last_stats
    figures = {"setup_s": setup_s, "serve_s": serve_s,
               "requests": len(results), "tokens": st["tokens"],
               "attend_path": st["attend_path"]}
    if "pool" in st:
        p = st["pool"]
        figures["pool_pages_used_at_end"] = p["used_pages"]
        figures["pool_pages"] = p["used_pages"] + p["free_pages"]
    return {r.rid: np.asarray(r.tokens) for r in results}, figures


def forced_logits(engine: Engine, requests: list[Request], forced: dict,
                  steps: int) -> dict:
    """Teacher-forced logits through the engine's slot API: every request is
    prefilled into a slot, then decoded ``steps`` times on the tokens in
    ``forced`` (rid -> tokens), so two engines see the same inputs.
    Returns rid -> f32 [steps + 1, vocab] (last prefill position first)."""
    view = engine.new_view()
    B = engine.ecfg.batch
    out = {}
    for lo in range(0, len(requests), B):
        group = requests[lo:lo + B]
        pos = np.zeros(B, np.int32)
        cur = np.zeros(B, np.int32)
        rows = {}
        for s, r in enumerate(group):
            lg = view.prefill_slot({"tokens": jnp.asarray(r.tokens[None])}, s,
                                   reserve_tokens=len(r.tokens) + r.max_new_tokens)
            rows[r.rid] = [lg[0, -1].astype(jnp.float32)]
            pos[s] = len(r.tokens)
            cur[s] = forced[r.rid][0]
        for t in range(steps):
            # fresh host arrays every step: a CPU device array may alias the
            # numpy buffer it came from while the step is still running
            lg = view.decode({"tokens": jnp.asarray(cur[:, None])}, pos)
            for s, r in enumerate(group):
                rows[r.rid].append(lg[s, -1].astype(jnp.float32))
            cur = np.array([forced[r.rid][t + 1] for r in group]
                           + [0] * (B - len(group)), np.int32)
            pos = pos + 1
        out.update({rid: np.asarray(jnp.stack(v)) for rid, v in rows.items()})
    return out


def compare(title: str, got: dict, ref: dict, bound: float) -> bool:
    rel = [float(np.linalg.norm(got[rid] - ref[rid]) / np.linalg.norm(ref[rid]))
           for rid in ref]
    agree = np.mean([np.mean(got[rid].argmax(-1) == ref[rid].argmax(-1))
                     for rid in ref])
    ok = max(rel) <= bound
    log(f"compare {title}: logits ||delta||/||ref|| max {max(rel):.3e} mean "
        f"{np.mean(rel):.3e} (bound {bound:g}) over {len(rel)} requests x "
        f"{next(iter(ref.values())).shape[0]} positions; teacher-forced greedy "
        f"token agreement {agree:.4f} -> {'ok' if ok else 'FAIL'}")
    return ok


def rel_err(got, ref) -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.linalg.norm(got - ref) / np.linalg.norm(ref))


def kernel_parity(cfg, pol, slots: int, capacity: int, interpret: bool) -> bool:
    """Phase K: each main-path kernel against its jnp oracle on identical
    random inputs at the model's attention widths (slots x kv heads rows)."""
    H, Dh, nb = cfg.num_kv_heads, cfg.head_dim, pol.buffer_size
    capacity = -(-capacity // nb) * nb          # the engine's chunk-rounded capacity
    rows = slots * H
    key = jax.random.PRNGKey(1)
    rnd = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape)
    ccfg = CacheConfig(batch=slots, kv_heads=H, head_dim=Dh, capacity=capacity,
                       policy=pol)
    n_tok = capacity - nb // 2
    cache = jax.jit(lambda k, v: prefill_layer_cache(ccfg, init_layer_cache(ccfg), k, v))(
        rnd(0, (slots, H, n_tok, Dh)), rnd(1, (slots, H, n_tok, Dh)))
    flat = lambda x: None if x is None else x.reshape((rows,) + x.shape[2:])
    names = ("k_packed", "k_scale", "k_zero", "v_packed", "v_scale", "v_zero")
    arrays = [flat(getattr(cache, n)) for n in names]
    extras = {n: flat(getattr(cache, n)) for n in (
        "k_a", "k_b", "v_a", "v_b", "k_sp_val", "k_sp_idx", "v_sp_val", "v_sp_idx")}
    # ragged extents, so the kernel's per-row (scalar-prefetch) indexing counts
    n_comp = (jnp.arange(rows, dtype=jnp.int32) % (n_tok // nb) + 1) * nb
    kw = dict(bits=pol.bits, chunk=nb, scale_factor=Dh ** -0.5)
    q = rnd(2, (rows, 1, Dh))
    acc, _, l = gear_decode(q, *arrays, n_comp, **kw, **extras, interpret=interpret)
    r_acc, _, r_l = jax.jit(lambda *a: oracle.gear_decode_ref(*a, **kw, **extras))(
        q, *arrays, n_comp)
    errs = {"gear_decode": rel_err(acc / l[..., :1], r_acc / r_l[..., None])}

    qkv = [rnd(3 + i, (H, 1024, Dh)).astype(jnp.bfloat16) for i in range(3)]
    out = flash_prefill(*qkv, bq=128, bk=128, interpret=interpret)
    errs["flash_prefill"] = rel_err(out.astype(jnp.float32), jax.jit(
        lambda a, b, c: oracle.flash_prefill_ref(a, b, c, jnp.arange(1024)))(*qkv)
        .astype(jnp.float32))
    qkv = [rnd(6 + i, (H, nb, Dh)) for i in range(3)]
    kv_len = jnp.arange(H, dtype=jnp.int32) * 7 % nb + 1
    acc, _, l = flash_prefill_block(*qkv, kv_len, scale=Dh ** -0.5, interpret=interpret)
    r_acc, _, r_l = oracle.flash_block_ref(*qkv, kv_len, scale=Dh ** -0.5)
    errs["flash_prefill_block"] = rel_err(acc / l[..., :1], r_acc / r_l[..., None])

    x = rnd(9, (H, nb, Dh))
    for kind in ("k", "v"):
        scheme, group = pol.scheme_for(kind)
        n_out = outlier_count(nb if scheme == "per_channel" else Dh, pol.sparsity)
        kout = gear_compress(x, bits=pol.bits, scheme=scheme, group=group,
                             n_out=n_out, interpret=interpret)
        rout = oracle.gear_compress_ref(x, bits=pol.bits, scheme=scheme,
                                     group=group, n_out=n_out)
        # codes and outlier indices: share that differ; stats, outlier
        # values and the residual: relative error
        errs[f"gear_compress {kind}"] = max(
            float(np.mean(np.asarray(a) != np.asarray(b)))
            if jnp.issubdtype(a.dtype, jnp.integer) else rel_err(a, b)
            for a, b in zip(kout, rout) if a is not None)
    ok = max(errs.values()) <= KERNEL_BOUND
    log(f"phase K, kernel vs jnp oracle at {rows} rows x head_dim {Dh}, ragged "
        "extents: "
        + ", ".join(f"{k} {v:.3e}" for k, v in errs.items())
        + f" (bound {KERNEL_BOUND:g}) -> {'ok' if ok else 'FAIL'}")
    return ok


def figures_line(name: str, fig: dict) -> str:
    return (f"{name}: " + ", ".join(f"{k}={v:.2f}" if isinstance(v, float)
                                    else f"{k}={v}" for k, v in fig.items())
            + "  [set-up and sanity figures, not speed results]")


def main() -> int:
    args = parse_args()
    t_start = time.time()
    info = device_info()
    if args.rehearse:
        if info["platform"] != "cpu":
            raise SystemExit("--rehearse runs on the CPU (JAX_PLATFORMS=cpu)")
    elif info["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {info['platform']!r} devices; "
                         "this check runs only on the chip")
    if info["count"] < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX found {info['count']}")
    clock = CompileClock()
    cache_dir = enable_compile_cache()
    log(f"device {info}; compile cache {cache_dir}")

    if args.rehearse:
        cfg, lengths, n_req, gen, steps, slots = smoke_config(ARCH), (64, 128), 6, 6, 3, 4
    else:
        cfg, lengths, n_req, gen, steps, slots = (get_config(ARCH), LENGTHS, REQUESTS,
                                                  GEN, FORCED_STEPS, SLOTS)
    model = build_model(cfg)
    pol = named_policy("gear_kcvt4")
    t0 = time.time()
    params = init_params(model, args.seed)
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.num_heads} heads / {cfg.num_kv_heads} kv heads x "
        f"{cfg.head_dim}, vocab {cfg.vocab_size}; bf16 params {n_bytes} "
        f"bytes, init {time.time() - t0:.1f} s")
    requests = make_requests(args.seed, lengths, n_req, gen, cfg.vocab_size)

    def engine(fused: str, mesh=None, **kw) -> Engine:
        return Engine(model, params,
                      EngineConfig(batch=slots, capacity=max(lengths) + gen,
                                   policy=pol, fused=fused, **kw), mesh=mesh)

    # the kernels' path: compiled on the chip, interpreted in a rehearsal
    kern, want = (("interpret", "fused-interpret") if args.rehearse
                  else ("auto", "fused-kernel"))
    dense = dict(layout="dense", prefill_mode="monolithic")
    if args.chips == 4:
        mesh = make_test_mesh(data=1, model=4)
        cases = [(f"mesh {dict(mesh.shape)} ({kern})", (kern, mesh, dense),
                  "device 0 alone", (kern, None, dense), BOUND_DENSE)]
    else:
        paged = dict(layout="paged", prefill_mode="streaming")
        streaming = dict(layout="dense", prefill_mode="streaming")
        cases = [(f"phase A (dense, monolithic, fused={kern})", (kern, None, dense),
                  "reference A (dense, monolithic, fused=off)", ("off", None, dense),
                  BOUND_DENSE),
                 (f"phase B (paged, streaming, fused={kern})", (kern, None, paged),
                  "reference B (dense, streaming, fused=off)", ("off", None, streaming),
                  BOUND_STREAMING)]
    ok = True
    if args.chips == 1:
        ok = kernel_parity(cfg, pol, slots, max(lengths) + gen, args.rehearse)
    for name, (fused, mesh, kw), ref_name, (ref_fused, ref_mesh, ref_kw), bound in cases:
        c0 = clock.total
        eng = engine(fused, mesh, **kw)
        toks, fig = serve(eng, requests)
        kernels = "tpu_custom_call" in eng.compiled_decode_text()
        got = forced_logits(eng, requests, toks, steps)
        del eng
        fig.update(compile_s=clock.total - c0,
                   decode_step_has_pallas_kernels=kernels,
                   peak_bytes_in_use=peak_bytes())
        log(figures_line(name, fig))
        if fig["attend_path"] != want or not (kernels or args.rehearse):
            log(f"{name}: decode path {fig['attend_path']!r} (want {want!r}), "
                f"Pallas kernels in the compiled step: {kernels} -> FAIL")
            ok = False

        c0, t0 = clock.total, time.time()
        ref = forced_logits(engine(ref_fused, ref_mesh, **ref_kw), requests,
                            toks, steps)
        log(figures_line(ref_name, {"forced_s": time.time() - t0,
                                    "compile_s": clock.total - c0,
                                    "peak_bytes_in_use": peak_bytes()}))
        ok &= compare(f"{name} vs {ref_name}", got, ref, bound)
    log(f"total wall {time.time() - t_start:.1f} s, compile "
        f"{clock.total:.1f} s (cold or warm per the compile cache above)")
    if not ok:
        log("FAILED")
        return 1
    result = {"ok": True, "device": info}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
