"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs[].file``) and a traffic mix
(``traffic/<mix>.json``); the configuration's ``model_type`` names its
architecture, ``archs/<model_type>.py``; each metric is
``metrics/<name>.py`` and each kernel's operation and byte count
``costs/<kernel>.py``; peaks are keyed by ``device_kind`` in
``peaks.json``. Adding a cell, a configuration, an architecture, a mix or
a metric adds files and entries and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))

# what every archs/<model_type>.py exports (archs/__init__.py)
ARCH_CONTRACT = ("spec", "program_config", "canonical", "program_params",
                 "logits_at", "matmul_weights", "attn_flops_per_pair")


def arch(model_type: str) -> ModuleType:
    """``archs/<model_type>.py``: everything that depends on the
    architecture."""
    path = os.path.join(BENCH_DIR, "archs", f"{model_type}.py")
    if (not model_type.isidentifier() or model_type.startswith("_")
            or not os.path.isfile(path)):
        raise ValueError(f"no architecture module bench/archs/"
                         f"{model_type}.py for model_type {model_type!r}")
    return importlib.import_module(f"archs.{model_type}")


@dataclasses.dataclass
class Cell:
    """A cell; its architecture module and sizes follow from ``config``."""
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    arch: ModuleType = dataclasses.field(init=False)
    spec: Any = dataclasses.field(init=False)

    def __post_init__(self):
        self.arch = arch(self.config["model_type"])
        self.spec = self.arch.spec(self.config)

    def program_config(self):
        return self.arch.program_config(self.config, self.spec,
                                        page=self.traffic["page"])

    def metrics(self, trace: bool) -> List[Dict]:
        return self.per_layer if trace else self.end_to_end


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_benchmark(root: str) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(root: str, bench: Dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic",
                           w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def _load(kind: str, name: str):
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str):
    """``metrics/<name>.py``: ``read(ctx) -> float | None``."""
    return _load("metrics", name)


def cost(kernel: str):
    """``costs/<kernel>.py``: the kernel's operations and bytes."""
    return _load("costs", kernel)


def peaks(device_kind: str) -> Dict:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json")
    return table[device_kind]
