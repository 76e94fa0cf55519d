"""The architecture a configuration names: ``archs/<arch_module>.py``.

A configuration file names its module in the required top-level key
``"arch_module"``.  The module gives, each from the configuration dict
alone and never from the program:

* ``model_config(cfg)``: the served program's ``ModelConfig``;
* ``gaps(cfg, params, prompt, served, pad_to, control=False)``: the plain
  float32 reference's logit gaps of the served tokens, or with ``control``
  of the tokens its float8 forward puts first (``harness.reference``);
* ``flop_model(cfg)``: an object with ``token_flops(context, logits)``,
  ``prefill_flops(n)`` and ``gear_layers`` (the layers whose K/V the GEAR
  pool holds).
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

ARCH_DIR = Path(__file__).resolve().parents[1] / "archs"
_loaded: dict[Path, object] = {}      # one module (and its jitted forward) per file


def known() -> list[str]:
    return sorted(p.stem for p in ARCH_DIR.glob("*.py") if not p.stem.startswith("_"))


def load(name: str):
    """``archs/<name>.py``; an unknown name stops the run."""
    if name not in known():
        raise SystemExit(f"unknown arch_module {name!r}; known: {known()}")
    path = ARCH_DIR / f"{name}.py"
    if path not in _loaded:
        mod_spec = importlib.util.spec_from_file_location(f"arch_{name}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def of(cfg: dict):
    """The module that configuration ``cfg`` names; there is no default."""
    if "arch_module" not in cfg:
        raise SystemExit(f"configuration {cfg.get('name')!r} names no "
                         f"arch_module; known: {known()}")
    return load(cfg["arch_module"])
