"""Integration test of the dry-run machinery itself, on a tiny fake mesh.

Exercises build_cell → lower → compile → cost/collective extraction for one
cell of each mode (train/prefill/decode) with a reduced config, in a
subprocess so the fake device count never leaks."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
@pytest.mark.multidevice
def test_dryrun_machinery_small_mesh(tmp_path):
    body = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, json, dataclasses
        import repro.launch.dryrun as dr
        import repro.configs as C

        # shrink: tiny smoke config + tiny shapes on a 2x2 mesh
        smoke = C.smoke_config("minicpm-2b")
        C._SMOKE = smoke
        orig_get = C.get_config
        dr.get_config = lambda name: smoke
        import repro.perf.roofline as rl
        rl_model_flops = rl.model_flops
        dr.SHAPES = {
            "train_4k": dataclasses.replace(C.SHAPES["train_4k"], seq_len=64, global_batch=4),
            "prefill_32k": dataclasses.replace(C.SHAPES["prefill_32k"], seq_len=128, global_batch=2),
            "decode_32k": dataclasses.replace(C.SHAPES["decode_32k"], seq_len=128, global_batch=4),
        }
        from repro.launch.mesh import make_test_mesh
        dr.make_production_mesh = lambda multi_pod=False: make_test_mesh(2, 2)

        for shape in ("train_4k", "prefill_32k", "decode_32k"):
            rec = dr.dryrun_cell("minicpm-2b", shape, multi_pod=False, microbatches=2)
            assert rec["roofline"]["compute_s"] > 0, shape
            assert rec["loop_cost"]["flops"] > 0, shape
            assert "collectives" in rec, shape
            print(shape, "OK", rec["roofline"]["bottleneck"])
    """)
    # pinned to the CPU: on a machine with a chip the parent holds it
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", body], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.count("OK") == 3
