"""Find a cell's parts by name: ``BENCHMARK.json`` and the files beside it.

A cell names a configuration (``configs[].file``) and a traffic mix
(``traffic/<name>.json``); each metric is read by ``metrics/<name>.py``.
Adding a cell takes new data files and an entry, never an edit here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

__all__ = ["BENCH_DIR", "ROOT", "load_benchmark", "cell_spec", "load_reader"]

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    """``BENCHMARK.json`` at the root of the checkout."""
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(entries, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{[e['name'] for e in entries]}")


def cell_spec(bm: dict, name: str, root: Path = ROOT) -> dict:
    """Everything one run of cell ``name`` needs, as plain data."""
    cell = _named(bm["workloads"], name, "workload")
    cfg = _named(bm["configs"], cell["config"], "configuration")

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config": json.loads((root / cfg["file"]).read_text()),
        "traffic": json.loads(
            (BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bm["end_to_end"] if applies(m)],
        "per_layer": [m for m in bm["per_layer"] if applies(m)],
    }


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    if spec is None or not path.is_file():
        raise KeyError(f"no reader for metric {metric!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
