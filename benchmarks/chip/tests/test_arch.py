"""A configuration names its architecture module, and a configuration of
another architecture comes in as files alone."""

import dataclasses
import json
import shutil
import types

import pytest

import run
from harness import arch, flops, layers, spec

CFG = spec.BENCH_DIR / "configs" / "starcoder2-3b.json"
KERNEL = "gear_decode_paged"


def starcoder2():
    cfg = spec.load_json(CFG)
    return {k: v for k, v in cfg.items() if k != "rehearse"}


def test_starcoder2_names_dense_decoder():
    cfg = starcoder2()
    assert cfg["arch_module"] == "dense_decoder"
    assert arch.of(cfg) is arch.load("dense_decoder")
    assert "dense_decoder" in arch.known()


@pytest.mark.parametrize("named", [None, "no_such_arch"])
def test_missing_or_unknown_arch_module_exits(named):
    cfg = starcoder2()
    cfg.pop("arch_module")
    if named is not None:
        cfg["arch_module"] = named
    with pytest.raises(SystemExit) as e:
        arch.of(cfg)
    assert "dense_decoder" in str(e.value)


# starcoder2-3b as served, every field ``ModelConfig`` has: the keys the
# configuration file maps, and the rest as the registered base config has them
STARCODER2_SERVED = {
    "name": "starcoder2-3b", "family": "dense", "num_layers": 30,
    "d_model": 3072, "num_heads": 24, "num_kv_heads": 2, "head_dim": 128,
    "d_ff": 12288, "vocab_size": 49152, "mlp_kind": "gelu_mlp",
    "norm": "layernorm", "rope_theta": 999999.4420358813, "qk_norm": False,
    "attn_logit_softcap": 0.0, "attn_pattern": "global",
    "local_window": 1024, "pattern_locals": 5, "moe": False,
    "num_experts": 0, "moe_top_k": 0, "moe_d_ff": 0,
    "shared_expert": False, "capacity_factor": 1.25,
    "router_aux_weight": 0.01, "ssm": False, "ssm_state": 16,
    "ssm_conv": 4, "hybrid_parallel": False, "rwkv": False,
    "modality": "text", "num_prefix_tokens": 0, "num_codebooks": 0,
    "tie_embeddings": True, "lr_schedule": "cosine", "max_seq_len": 16384}
MAPPED = ("name", "num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
          "d_ff", "vocab_size", "mlp_kind", "norm", "rope_theta", "tie_embeddings",
          "max_seq_len")


def config_mismatches(got, base, served: dict) -> dict:
    """Fields of ``got`` that differ from ``served``'s literal values, or,
    for a field ``served`` does not name (one added to ``ModelConfig``
    since), from the registered ``base``'s; and fields either side lacks."""
    missing = object()
    g = dataclasses.asdict(got)
    want = {**dataclasses.asdict(base), **served}
    return {k: (g.get(k, missing), want.get(k, missing))
            for k in g.keys() | want.keys() if g.get(k, missing) != want.get(k, missing)}


def wider(cfg):
    """``cfg`` as an instance of a config type with one more defaulted field."""
    from repro.configs.base import ModelConfig
    Wider = dataclasses.make_dataclass(
        "Wider", [("yarn_factor", float, dataclasses.field(default=1.0))],
        bases=(ModelConfig,), frozen=True)
    return Wider(**dataclasses.asdict(cfg))


def changed(value):
    """Another value of the same type."""
    if isinstance(value, bool):
        return not value
    if isinstance(value, str):
        return value + "-x"
    return value * 2


@pytest.mark.parametrize("case", ["as served", "a wider ModelConfig",
                                  "registered attn_pattern changed",
                                  *(f"mapped {k} changed" for k in MAPPED)])
def test_dense_decoder_model_config_field_by_field(case, monkeypatch):
    """Every field of starcoder2-3b's served config against its literal
    value; a field added to ``ModelConfig`` since, against the registered
    base config.  A field added with a default passes; any change in how
    the model is served fails, from the configuration file or from the
    registry alike."""
    import repro.configs
    cfg = starcoder2()
    if case == "registered attn_pattern changed":
        registered = repro.configs.get_config
        monkeypatch.setattr(repro.configs, "get_config", lambda name: dataclasses.replace(
            registered(name), attn_pattern=changed(registered(name).attn_pattern)))
    got = arch.load("dense_decoder").model_config(cfg)
    base = repro.configs.get_config(cfg["program"]["arch"])
    if case == "a wider ModelConfig":
        got, base = wider(got), wider(base)
        assert dataclasses.asdict(got)["yarn_factor"] == 1.0
    elif case.startswith("mapped "):
        field = case.split()[1]
        got = dataclasses.replace(got, **{field: changed(getattr(got, field))})
    bad = config_mismatches(got, base, STARCODER2_SERVED)
    if case.endswith(" changed"):
        assert list(bad) == [case.split()[1]]
    else:
        assert bad == {}


def test_dense_decoder_flop_model_is_dense():
    cfg = starcoder2()
    m, want = arch.load("dense_decoder").flop_model(cfg), flops.Dense.from_config(cfg)
    assert m == want and m.gear_layers == m.layers == 30
    assert m.matmul_params() == want.matmul_params()
    for n in (1, 2, 63, 64, 1024, 1537, 2047):
        for logits in (False, True):
            assert m.token_flops(n, logits) == want.token_flops(n, logits)
        assert m.prefill_flops(n) == want.prefill_flops(n)


# -- the roofline of gear_decode_paged counts the GEAR layers only

def roofline_ctx(model):
    # two decode steps inside the traced part (lengths 130/700/0 and
    # 131/701/64: 12 and 13 chunks of 64 a KV head), one after it
    steps = [(1.0, 0.1, [130, 700, 0]), (2.0, 0.1, [131, 701, 64]),
             (4.0, 0.1, [4096, 4096, 4096])]
    return types.SimpleNamespace(
        hub=types.SimpleNamespace(steps=steps), traced=(0.5, 3.0),
        trace={"kernel_s": {KERNEL: 0.004}},
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        model=model, gear=flops.Gear(head_dim=128), slots=3, kv_heads=2,
        group=12)


def parent_formula(ctx):
    """The reader as it was before ``gear_layers``: every layer counted."""
    f = b = 0
    rows = ctx.slots * ctx.kv_heads
    for _, _, lengths in layers.traced_steps(ctx):
        live = ctx.kv_heads * sum(n // ctx.gear.chunk for n in lengths)
        sf, sb = flops.decode_paged_cost(ctx.gear, live, rows, ctx.group)
        f += ctx.model.layers * sf
        b += ctx.model.layers * sb
    return layers.roofline(ctx, KERNEL, f, b)


def test_gear_roofline_scales_with_gear_layers():
    read = run.load_reader("gear_decode_paged_roofline").read
    g = flops.Gear(head_dim=128)
    (f1, b1), (f2, b2) = (flops.decode_paged_cost(g, 2 * c, 6, 12) for c in (12, 13))
    least = lambda n: max(n * (f1 + f2) / 197e12, n * (b1 + b2) / 819e9)

    windowed = types.SimpleNamespace(layers=28, gear_layers=7)
    assert read(roofline_ctx(windowed)) == pytest.approx(100.0 * least(7) / 0.004,
                                                         rel=1e-12)
    assert read(roofline_ctx(windowed)) == pytest.approx(
        parent_formula(roofline_ctx(windowed)) * 7 / 28, rel=1e-12)

    dense = flops.Dense.from_config(starcoder2())
    assert read(roofline_ctx(dense)) == parent_formula(roofline_ctx(dense))
    assert read(roofline_ctx(dense)) == 100.0 * least(30) / 0.004


# -- a configuration of another architecture, by files alone

def test_new_arch_by_files_alone_rehearses_correct(tmp_path, monkeypatch, capsys):
    bench = tmp_path / "benchmarks" / "chip"
    for d in ("configs", "traffic", "cells", "archs", "metrics"):
        shutil.copytree(spec.BENCH_DIR / d, bench / d)
    shutil.copy(spec.BENCH_DIR / "peaks.json", bench / "peaks.json")
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")

    # the new files: an arch module, a configuration, a cell's limits
    shutil.copy(bench / "archs" / "dense_decoder.py", bench / "archs" / "toy_dense.py")
    cfg = spec.load_json(CFG)
    cfg.update(name="toy-dense", arch_module="toy_dense")
    cfg["rehearse"].update(num_hidden_layers=1)
    (bench / "configs" / "toy-dense.json").write_text(json.dumps(cfg))
    shutil.copy(bench / "cells" / "starcoder2-3b.decode_2k.json",
                bench / "cells" / "toy-dense.decode_2k.json")
    # ... and the entries
    b = spec.load_json(tmp_path / "BENCHMARK.json")
    b["configs"].append({"name": "toy-dense", "source": "a test",
                         "file": "benchmarks/chip/configs/toy-dense.json",
                         "reduced": [], "why": "a test"})
    b["workloads"].append({"name": "toy-dense.decode_2k", "config": "toy-dense",
                           "traffic": "decode_2k", "chips": 1, "why": "a test"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("toy-dense.decode_2k")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    monkeypatch.setattr(spec, "ROOT", tmp_path)
    monkeypatch.setattr(spec, "BENCH_DIR", bench)
    monkeypatch.setattr(arch, "ARCH_DIR", bench / "archs")
    monkeypatch.setattr(run, "BENCH", bench)
    assert run.main(["--workload", "toy-dense.decode_2k", "--seed", str(2**31 + 7),
                     "--seconds", "3", "--trace", "0", "--rehearse"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["rehearsal"] is True
    assert out["checks"]["mean_logit_gap"]["value"] is not None
    assert bench / "archs" / "toy_dense.py" in arch._loaded
