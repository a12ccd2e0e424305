"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

Nothing here lists a cell, a configuration, a mix or a metric: each is
a file under the benchmark's directory named after its entry, so a new
one is a new file and a new entry, and no existing file changes.

  configuration  <bench>/configs/<config>.json
  traffic mix    <bench>/traffic/<traffic>.json
  metric         <bench>/metrics/<metric>.py, defining read(record)
  reference      <bench>/reference/<family>.py (family from the config)
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load(root: Path = ROOT) -> Dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def cell(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_entry(bench: Dict, name: str) -> Dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def config(bench: Dict, name: str, root: Path = ROOT) -> Dict:
    return _json(Path(root) / config_entry(bench, name)["file"])


def traffic(name: str, bench_dir: Path = BENCH_DIR) -> Dict:
    return _json(Path(bench_dir) / "traffic" / f"{name}.json")


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric(name: str, bench_dir: Path = BENCH_DIR):
    """The reader of per-layer metric ``name``: a module with
    ``read(record) -> float | None``."""
    return _module(Path(bench_dir) / "metrics" / f"{name}.py",
                   f"chipbench_metric_{name}")


def reference(family: str, bench_dir: Path = BENCH_DIR):
    return _module(Path(bench_dir) / "reference" / f"{family}.py",
                   f"chipbench_reference_{family}")


def end_to_end(bench: Dict, cell_name: str) -> List[Dict]:
    return [m for m in bench["end_to_end"]
            if cell_name in m.get("workloads", [cell_name])]


def per_layer(bench: Dict, cell_name: str) -> List[Dict]:
    return [m for m in bench["per_layer"]
            if cell_name in m.get("workloads", [cell_name])]
