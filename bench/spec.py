"""Find a cell's parts by name: ``BENCHMARK.json`` and the files beside it.

A cell names a configuration (``configs[].file``) and a traffic mix
(``traffic/<name>.json``); each metric is read by ``metrics/<name>.py``.
The configuration may name the system the cell drives (``"system"``),
built by ``systems/<name>.py``; where it names none, the system is
``matmul``, the coded ``A^T B``.  A system module's ``build(config,
traffic, devices, dtype)`` returns what ``bench/cell.py`` drives, and its
``CONTROL_DTYPE`` is the precision ``bench/control.py`` runs the control
at; ``systems/matmul.py`` shows every member the harness uses.

Adding a cell, even of a new kind of system, takes new files (data, a
system module, its reference, its readers) and an entry, never an edit here
or to a file the benchmark already has.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path

__all__ = ["BENCH_DIR", "ROOT", "SYSTEMS_DIR", "DEFAULT_SYSTEM",
           "load_benchmark", "cell_spec", "load_reader", "load_system"]

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
SYSTEMS_DIR = BENCH_DIR / "systems"
DEFAULT_SYSTEM = "matmul"


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
    """Everything one run of cell ``name`` needs: plain data, and under
    ``system`` the module that builds the system its configuration names."""
    cell = _named(bm["workloads"], name, "workload")
    cfg = _named(bm["configs"], cell["config"], "configuration")
    config = json.loads((root / cfg["file"]).read_text())

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": int(cell["chips"]),
        "system": load_system(config.get("system", DEFAULT_SYSTEM)),
        "config": config,
        "traffic": json.loads(
            (BENCH_DIR / "traffic" / f"{cell['traffic']}.json").read_text()),
        "end_to_end": [m for m in bm["end_to_end"] if applies(m)],
        "per_layer": [m for m in bm["per_layer"] if applies(m)],
    }


def _load(path: Path, kind: str, name: str):
    """The module at ``path``, registered in ``sys.modules`` while it runs
    (a dataclass looks its module up there)."""
    module_name = f"bench_{kind}_" + name.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def load_reader(metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise KeyError(f"no reader for metric {metric!r} at {path}")
    return _load(path, "metric", metric).read


def load_system(name: str):
    """The module ``systems/<name>.py``, whose ``build`` makes the system."""
    path = SYSTEMS_DIR / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in SYSTEMS_DIR.glob("*.py"))
        raise KeyError(f"no system named {name!r} in {SYSTEMS_DIR}; known: {known}")
    return _load(path, "system", name)
