"""A cell drives the system its configuration names, found by name.

Every accepted cell names none and so drives the coded matmul.  A system
that lives only in new files, a module in a systems directory of its own,
runs through ``run_cell`` to a result line, and ``correct`` there is decided
by that system's own check: sound it is correct, with a fault planted in
its call it is not.
"""
from __future__ import annotations

import json
import sys
import textwrap
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import spec  # noqa: E402

# A system in one file: the product 2x of a small integer matrix, checked
# exactly against numpy.  ``double`` is the timed path a test can break.
TOY = textwrap.dedent('''
    import jax.numpy as jnp
    import numpy as np

    LIMITS = {"max_abs_err": 0.0}
    CONTROL_DTYPE = "float16"
    SHAPE = (8, 4)


    def double(x):
        return x * 2


    class System:
        limits = LIMITS
        patterns = [(0,), (1,), (2,)]
        shape = SHAPE
        item_bytes = 8 * 4 * 4
        kept_bytes = 3 * 8 * 4 * 4

        def __init__(self, dtype):
            self.dtype = dtype

        def make_items(self, seq, first, count):
            out = []
            for i in range(first, first + count):
                rng = np.random.default_rng([*seq.generate_state(2), i])
                x = rng.integers(-50, 50, SHAPE).astype(np.float32)
                out.append((jnp.asarray(x, self.dtype), x))
            return out

        def prepare(self):
            pass

        def call(self, item, erased):
            return double(item[0])

        def plain(self, item):
            return item[0] + item[0]

        @staticmethod
        def check_inputs(item):
            return item[1]

        def check(self, kept, refs):
            return [{"max_abs_err": float(np.max(np.abs(
                        np.asarray(out, np.float64) - 2.0 * refs[k])))}
                    for out, k in kept.values()]

        def trace_stages(self, item, device_id):
            return None


    def build(config, traffic, devices, dtype=None):
        return System(jnp.dtype(dtype or config["dtype"]))
''')

METRICS = ("call_ms", "coding_tax", "setup_s", "toy_patterns")

# A reader of the toy system's own, beside the shared ones: it reads the
# system the window drove.
TOY_READER = textwrap.dedent('''
    def read(ctx):
        return len(ctx.system.patterns)
''')


def _benchmark(tmp_path: Path, system: str | None) -> dict:
    """A benchmark of one cell whose configuration names ``system``."""
    config = {"name": "toy", "dtype": "float32"}
    if system is not None:
        config["system"] = system
    (tmp_path / "toy.json").write_text(json.dumps(config))
    return {
        "configs": [{"name": "toy", "file": "toy.json"}],
        "workloads": [{"name": "toy.local", "config": "toy",
                       "traffic": "max-erasure.local", "chips": 1}],
        "end_to_end": [{"name": m, "unit": "x"} for m in METRICS],
        "per_layer": [],
    }


@pytest.mark.parametrize("cell", ["paper-8000.polycode", "serve-int4-8000.mesh4"])
def test_every_accepted_cell_drives_the_coded_matmul(cell):
    """The accepted configurations name no system, so each is ``matmul``."""
    bm = spec.load_benchmark()
    got = spec.cell_spec(bm, cell)
    assert "system" not in got["config"]
    assert Path(got["system"].__file__) == spec.SYSTEMS_DIR / "matmul.py"
    assert callable(got["system"].build)


def test_an_unknown_system_raises_naming_the_known_ones(tmp_path, monkeypatch):
    """A configuration that names a system with no module is refused with
    the list of the systems there are: all of them in a systems directory of
    the test's own, and ``matmul`` among those of the benchmark."""
    bm = _benchmark(tmp_path, "no-such-system")
    with pytest.raises(KeyError, match=r"no-such-system.*known: \[.*'matmul'"):
        spec.cell_spec(bm, "toy.local", root=tmp_path)

    systems = tmp_path / "systems"
    systems.mkdir()
    for stub in ("toy", "other"):
        (systems / f"{stub}.py").write_text(TOY)
    monkeypatch.setattr(spec, "SYSTEMS_DIR", systems)
    with pytest.raises(KeyError, match=r"no-such-system.*known: \['other', 'toy'\]"):
        spec.cell_spec(bm, "toy.local", root=tmp_path)


@pytest.mark.parametrize("fault", [False, True])
def test_a_system_in_new_files_is_judged_by_its_own_check(tmp_path, monkeypatch, fault):
    """A system module in a directory of its own runs through ``run_cell``
    to a whole result line, and a reader of its own reads the system.
    Sound, it is correct; with one entry of its timed call's answer off by
    one, it is not, and every output fails."""
    import jax

    from bench.cell import run_cell
    from repro import obs

    systems = tmp_path / "systems"
    systems.mkdir()
    (systems / "toy.py").write_text(TOY)
    monkeypatch.setattr(spec, "SYSTEMS_DIR", systems)
    monkeypatch.setattr(obs, "_session", obs._session)   # run_cell turns obs on
    cell = spec.cell_spec(_benchmark(tmp_path, "toy"), "toy.local", root=tmp_path)
    assert Path(cell["system"].__file__) == systems / "toy.py"
    metrics = tmp_path / "bench" / "metrics"
    metrics.mkdir(parents=True)
    for m in METRICS[:-1]:
        (metrics / f"{m}.py").write_text((spec.BENCH_DIR / "metrics" / f"{m}.py").read_text())
    (metrics / "toy_patterns.py").write_text(TOY_READER)
    monkeypatch.setattr(spec, "BENCH_DIR", metrics.parent)
    if fault:
        monkeypatch.setattr(cell["system"], "double",
                            lambda x: (x * 2).at[0, 0].add(1))

    res = run_cell(cell, 2 ** 31 + 99, 0.2, False, devices=jax.devices()[:1],
                   t_start=time.perf_counter())

    assert list(res) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["attempted"] >= 3 and set(res["metrics"]) == set(METRICS)
    assert res["checks"]["max_abs_err"]["limit"] == 0.0
    assert res["metrics"]["toy_patterns"]["value"] == 3
    if fault:
        assert not res["correct"] and res["failed"] == 3
        assert res["checks"]["max_abs_err"]["value"] == 1.0
    else:
        assert res["correct"] and res["failed"] == 0
        assert res["checks"]["max_abs_err"]["value"] == 0.0
