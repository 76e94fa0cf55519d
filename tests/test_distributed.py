"""Distributed tests: sharding rules, train loop on a mesh, PowerSGD,
checkpoint/restore/elastic-rescale.  Multi-device cases re-exec in a
subprocess so the fake host-device count never leaks into other tests."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.dist.sharding import fit_spec
from repro.launch.mesh import make_test_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_subprocess(body: str, devices: int = 4) -> str:
    # pinned to the CPU: on a machine with a chip the parent holds it
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


class FakeMesh:
    shape = {"data": 4, "model": 4}


class TestFitSpec:
    def test_migrates_axis(self):
        ps = fit_spec(P("model", None), (122753, 2304), FakeMesh())
        assert tuple(ps) == (None, "model")

    def test_drops_axis(self):
        ps = fit_spec(P("model",), (7,), FakeMesh())
        assert tuple(ps) == (None,)

    def test_keeps_legal(self):
        ps = fit_spec(P(None, "model"), (8, 16), FakeMesh())
        assert tuple(ps) == (None, "model")

    # --- edge cases beyond the seed's three -------------------------------

    def test_multi_axis_group_kept_when_divisible(self):
        ps = fit_spec(P(("data", "model"), None), (16, 4), FakeMesh())
        assert tuple(ps) == (("data", "model"), None)

    def test_multi_axis_group_splits_and_migrates(self):
        # dim0 (8) only fits the "data" prefix (4); the leftover "model"
        # axis migrates to dim1 (64).
        ps = fit_spec(P(("data", "model"), None), (8, 64), FakeMesh())
        assert tuple(ps) == ("data", "model")

    def test_multi_axis_group_drops_when_nothing_fits(self):
        ps = fit_spec(P(("data", "model"),), (7,), FakeMesh())
        assert tuple(ps) == (None,)

    def test_partially_migrated_group_rehomes_its_remainder(self):
        # dim0 fits nothing; "data" migrates to dim1 and the leftover
        # "model" keeps looking and lands on dim2.
        ps = fit_spec(P(("data", "model"), None, None), (2, 4, 4), FakeMesh())
        assert tuple(ps) == (None, "data", "model")

    def test_zero_size_dim_accepts_any_sharding(self):
        ps = fit_spec(P("model", None), (0, 5), FakeMesh())
        assert tuple(ps) == ("model", None)

    def test_mesh_axes_absent_from_spec_are_fine(self):
        class PodMesh:
            shape = {"pod": 2, "data": 4, "model": 4}
        ps = fit_spec(P(None, "model"), (8, 16), PodMesh())
        assert tuple(ps) == (None, "model")

    def test_spec_axis_unknown_to_mesh_is_dropped(self):
        ps = fit_spec(P("tensor", None), (8, 8), FakeMesh())
        assert tuple(ps) == (None, None)

    def test_short_spec_padded_to_rank(self):
        ps = fit_spec(P("model"), (8, 6), FakeMesh())
        assert tuple(ps) == ("model", None)

    def test_no_migration_when_disabled(self):
        ps = fit_spec(P("model", None), (7, 16), FakeMesh(), migrate=False)
        assert tuple(ps) == (None, None)

    def test_overlong_spec_rejected(self):
        with pytest.raises(ValueError):
            fit_spec(P("model", None), (16,), FakeMesh())


@pytest.mark.slow
@pytest.mark.multidevice
def test_train_restore_deterministic(tmp_path):
    """6 steps straight == 3 steps + restart + 3 steps (bitwise metrics)."""
    out = run_subprocess(f"""
        import jax, dataclasses, json
        from repro.configs import smoke_config
        from repro.models.model import build_model
        from repro.launch.mesh import make_test_mesh
        from repro.train.state import RunConfig
        from repro.train.loop import train_loop
        from repro.data.synthetic import DataConfig

        cfg = smoke_config("minicpm-2b")
        m = build_model(cfg)
        mesh = make_test_mesh(2, 2)
        dc = DataConfig(seed=0, vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
        logs = []
        run = RunConfig(total_steps=6, warmup_steps=1, microbatches=2, remat=True,
                        zero1=True, ckpt_dir="{tmp_path}/a", ckpt_every=0, log_every=1)
        s1 = train_loop(m, mesh, run, dc, log_fn=logs.append)
        runb = dataclasses.replace(run, total_steps=3, ckpt_dir="{tmp_path}/b", ckpt_every=0)
        import repro.ckpt.checkpoint as ck
        s2 = train_loop(m, mesh, runb, dc, log_fn=lambda *_: None)
        ck.save("{tmp_path}/b", 3, s2)
        runc = dataclasses.replace(run, total_steps=6, ckpt_dir="{tmp_path}/b", ckpt_every=0)
        s3 = train_loop(m, mesh, runc, dc, log_fn=lambda *_: None)
        import numpy as np
        p1 = jax.tree.leaves(s1.params); p3 = jax.tree.leaves(s3.params)
        diff = max(float(abs(np.asarray(a)-np.asarray(b)).max()) for a, b in zip(p1, p3))
        print("MAXDIFF", diff)
    """)
    diff = float(out.split("MAXDIFF")[1].strip())
    assert diff < 1e-5


@pytest.mark.slow
@pytest.mark.multidevice
def test_elastic_rescale_restore(tmp_path):
    """Checkpoint on a 2×2 mesh restores onto a 4×1 mesh (mesh-independent)."""
    run_subprocess(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.models.model import build_model
        from repro.launch.mesh import make_test_mesh
        from repro.train.state import RunConfig, init_train_state
        from repro.train.loop import train_state_shardings
        from repro.dist import sharding as shd
        import repro.ckpt.checkpoint as ck

        cfg = smoke_config("minicpm-2b")
        m = build_model(cfg)
        run = RunConfig(ckpt_every=0)
        mesh1 = make_test_mesh(2, 2)
        with mesh1:
            state = init_train_state(m.init(jax.random.PRNGKey(0)), run)
            sh1 = train_state_shardings(cfg, mesh1, state, run)
            state = jax.device_put(state, sh1)
            ck.save("{tmp_path}/ck", 1, state)
        mesh2 = make_test_mesh(4, 1)
        with mesh2:
            tgt = init_train_state(m.init(jax.random.PRNGKey(0)), run)
            sh2 = train_state_shardings(cfg, mesh2, tgt, run)
            restored = ck.restore("{tmp_path}/ck", 1, tgt, sh2)
        a = jax.tree.leaves(state.params)[0]
        b = jax.tree.leaves(restored.params)[0]
        assert np.allclose(np.asarray(a), np.asarray(b)), "elastic restore mismatch"
        print("ELASTIC_OK")
    """)


@pytest.mark.slow
@pytest.mark.multidevice
def test_powersgd_runs_on_pod_mesh(tmp_path):
    run_subprocess(f"""
        import dataclasses
        from repro.configs import smoke_config
        from repro.models.model import build_model
        from repro.launch.mesh import make_test_mesh
        from repro.train.state import RunConfig
        from repro.train.loop import train_loop
        from repro.data.synthetic import DataConfig
        cfg = smoke_config("minicpm-2b")
        m = build_model(cfg)
        mesh = make_test_mesh(data=2, model=2, pod=2)
        run = RunConfig(total_steps=2, warmup_steps=1, microbatches=1, remat=False,
                        zero1=False, grad_compression="powersgd", powersgd_rank=2,
                        powersgd_min_size=4096, ckpt_dir="{tmp_path}/ps",
                        ckpt_every=0, log_every=1)
        dc = DataConfig(seed=0, vocab_size=cfg.vocab_size, seq_len=32, global_batch=4)
        logs = []
        train_loop(m, mesh, run, dc, log_fn=logs.append)
        assert any("compressed_bytes" in l and "compressed_bytes=0 " not in l for l in logs), logs
        print("POWERSGD_OK")
    """, devices=8)


@pytest.mark.slow
@pytest.mark.multidevice
def test_serving_on_mesh(tmp_path):
    run_subprocess("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs import smoke_config
        from repro.models.model import build_model
        from repro.core.policy import named_policy
        from repro.launch.mesh import make_test_mesh
        from repro.serving.engine import Engine, EngineConfig
        cfg = smoke_config("minicpm-2b")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        pol = dataclasses.replace(named_policy("gear_kcvt4"), buffer_size=16)
        mesh = make_test_mesh(2, 2)
        with mesh:
            eng = Engine(m, params, EngineConfig(batch=4, capacity=96, policy=pol), mesh=mesh)
            batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 24), 0, cfg.vocab_size)}
            toks, stats = eng.generate(batch, 8)
        assert toks.shape == (4, 8)
        print("SERVE_MESH_OK")
    """)


@pytest.mark.parametrize("data,model", [(2, 2), (4, 1)])
def test_mesh_kernels_run_per_head_shard(data, model):
    """Under a (data, model) mesh the Pallas kernels run per shard of slots
    and heads (shard_map), a data-only mesh included.  Decode: the mesh
    engine forced through the kernel gives exactly the logits of the mesh
    engine on the jnp oracle, from the same cache.  Prefill:
    ``flash_prefill`` per shard equals the unsharded call bit for bit."""
    out = run_subprocess(f"""
        import jax, jax.numpy as jnp, dataclasses, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models.model import build_model
        from repro.models import attention as attn
        from repro.core.policy import named_policy
        from repro.launch.mesh import make_test_mesh
        from repro.serving.engine import Engine, EngineConfig
        cfg = smoke_config("minicpm-2b")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        pol = dataclasses.replace(named_policy("gear_kcvt4"), buffer_size=16)
        mesh = make_test_mesh({data}, {model})
        batch = {{"tokens": jax.random.randint(jax.random.PRNGKey(1), (4, 40), 0,
                                              cfg.vocab_size)}}
        nxt = {{"tokens": jnp.arange(4, dtype=jnp.int32)[:, None]}}
        engs = {{f: Engine(m, params, EngineConfig(batch=4, capacity=96, policy=pol,
                                                  fused=f), mesh=mesh)
                for f in ("auto", "interpret")}}
        _, caches = engs["auto"].prefill(batch)
        dec = {{f: np.asarray(e.decode(nxt, jax.tree.map(jnp.copy, caches), 40)[0])
               for f, e in engs.items()}}
        assert engs["interpret"].attend_path == "fused-interpret"
        np.testing.assert_array_equal(dec["interpret"], dec["auto"])

        q, k, v = jax.random.normal(jax.random.PRNGKey(2), (3, 4, 32, 4, 16))
        flash = jax.jit(lambda q, k, v: attn._sdpa_flash(cfg, q, k, v, "global", 0,
                                                         interpret=True))
        ref = np.asarray(flash(q, k, v))
        spec = NamedSharding(mesh, P("data", None, "model", None))
        with jax.set_mesh(mesh):
            got = flash(*(jax.device_put(x, spec) for x in (q, k, v)))
        assert got.sharding.spec[0] == "data" and got.sharding.spec[2] == "model", got.sharding
        np.testing.assert_array_equal(np.asarray(got), ref)
        print("MESH_KERNELS_OK")
    """)
    assert "MESH_KERNELS_OK" in out
