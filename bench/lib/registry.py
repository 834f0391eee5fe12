"""Find the parts of a cell by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, entry driver
or per-layer metric sits in a file of its own under ``bench/``:

    bench/configs/<config>.json     one configuration (the file named
                                    in BENCHMARK.json's ``configs``)
    bench/traffic/<traffic>.json    one traffic mix; ``entry`` names
                                    its driver
    bench/entries/<entry>.py        one entry driver
    bench/metrics/<metric>.py       one per-layer metric's reader
    bench/reference/detectors/<family>.py
                                    one detector family's plain
                                    reference: ``forward`` and
                                    ``candidates``
    bench/lib/detectors/<family>.py the same family's program side:
                                    ``program_widths`` and ``work``

so a later change adds a cell, a mix, a metric or a detector family
(named by ``detector.family`` in a configuration) by adding files and
entries, never by editing this module.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass, field
from types import ModuleType
from typing import Dict, List, NamedTuple, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: Optional[str] = None) -> ModuleType:
    """Import a Python file by path (names may hold dots)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    name = name or "bench_dyn_" + os.path.relpath(path, BENCH_DIR) \
        .replace(os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of BENCHMARK.json with its parts resolved."""
    name: str
    chips: int
    config: dict                 # the configuration file's contents
    traffic: dict                # the traffic file's contents
    end_to_end: List[dict] = field(default_factory=list)
    per_layer: List[dict] = field(default_factory=list)
    bench_dir: str = BENCH_DIR

    @property
    def entry(self) -> str:
        return self.traffic["entry"]

    @property
    def root(self) -> str:
        return os.path.dirname(self.bench_dir)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell_name: str,
             e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in e2e_names


def find_cell(name: str, bench: Optional[dict] = None,
              root: str = ROOT) -> Cell:
    bench = bench if bench is not None else benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     f"{w['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    e2e_names = [m["name"] for m in e2e]
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic, e2e, per_layer,
                bench_dir=os.path.join(root, "bench"))


def find_entry(name: str, root: str = ROOT) -> ModuleType:
    return load_module(os.path.join(root, "bench", "entries",
                                    f"{name}.py"))


def find_metric(name: str, root: str = ROOT) -> ModuleType:
    return load_module(os.path.join(root, "bench", "metrics",
                                    f"{name}.py"))


class Family(NamedTuple):
    """One detector family's two files, as modules."""
    reference: ModuleType        # forward, candidates
    program: ModuleType          # program_widths, work


def find_family(name: str, root: str = ROOT) -> Family:
    """The detector family ``name``; a family without both files is an
    error (``FileNotFoundError``)."""
    bench = os.path.join(root, "bench")
    return Family(
        load_module(os.path.join(bench, "reference", "detectors",
                                 f"{name}.py")),
        load_module(os.path.join(bench, "lib", "detectors", f"{name}.py")))


def peaks(device_kind: str, root: str = ROOT) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(f"device_kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
