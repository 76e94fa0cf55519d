"""Find a cell's data by name: ``BENCHMARK.json`` at the checkout root names
the cell, its configuration and its traffic mix; each of those is a file of
its own under ``benchmarks/chip``, so a cell or a configuration is added by
adding files and entries, never by editing code:

* ``configs/<config>.json``: the configuration as run, with its sizes, a
  ``rehearse`` block for the CPU, ``serving`` (policy, slots, pool) and
  ``"arch_module"``, the name of its architecture module;
* ``archs/<arch_module>.py``: the architecture (``harness/arch.py`` says
  what it gives: the program's ``ModelConfig``, the plain reference and the
  FLOP count), where no module there has it yet;
* ``traffic/<mix>.json``: the traffic mix, where it is new;
* ``cells/<cell>.json``: the cell's limits for ``correct``;
* entries in ``BENCHMARK.json``: the configuration under ``configs``, the
  cell under ``workloads``, and the cell's name in the ``workloads`` of each
  metric it reports.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # configs/<config>.json
    traffic: dict         # traffic/<mix>.json
    limits: dict          # cells/<cell>.json
    end_to_end: list      # BENCHMARK.json metrics this cell reports, trace 0
    per_layer: list       # ... and with trace 1


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path | None = None) -> Cell:
    root = ROOT if root is None else root
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = load_json(BENCH_DIR / "cells" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=cfg, traffic=traffic,
                limits=limits,
                end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by JAX's ``device_kind``.  A
    device that is not in the table is an error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"no peaks for device_kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[device_kind]
