"""The check that decides ``correct``, on the CPU at a size a test run holds.

Each cell runs through ``run_cell`` as on the chip, with only the look for a
chip skipped: the configuration keeps its contraction length v, its grid,
K, points and entry range, and narrows r and t.  The sound program must
come out correct.  The control (the program's own float32 path in place of
float64) and each fault the cell can have, planted in the timed path, must
come out not correct.  The mesh cell runs in a child process with four
virtual CPU devices.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

NARROW = {"paper-8000.polycode": 32, "serve-int4-8000.mesh4": 16}

# Planted in the child before the facade is built; each breaks one thing.
FAULTS = {
    "sound": "",
    "control": "",
    # an answer altered where it is produced: one entry of C off by one
    "answer": (
        "import repro.runtime.executors as ex\n"
        "_unpad = ex.unpad\n"
        "patch(ex, 'unpad', lambda x, rt: _unpad(x, rt).at[0, 0].add(1.0))\n"),
    # one erasure pattern's decode wrong: its panel's weights off by 0.1%
    # where the last K - tau workers are erased, every other pattern sound
    "pattern": (
        "import dataclasses, numpy as np\n"
        "import repro.core.decoding as dec\n"
        "_get = dec.DecodePanelCache.get\n"
        "def _bad(self, mask=None):\n"
        "    panel = _get(self, mask)\n"
        "    m = [int(x) for x in np.asarray(panel.mask)]\n"
        "    if m == sorted(m, reverse=True) and not m[-1]:\n"
        "        panel = dataclasses.replace(panel, W=panel.W * 1.001)\n"
        "    return panel\n"
        "patch(dec.DecodePanelCache, 'get', _bad)\n"),
    # the exchange between chips left out: each chip decodes from its own
    # product alone, as if every one of the K=4 workers had returned it
    "exchange": (
        "import jax, jax.numpy as jnp\n"
        "patch(jax.lax, 'all_gather', lambda x, axis, **kw:\n"
        "      jnp.broadcast_to(x[None], (4,) + x.shape))\n"),
}

CHILD = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
jax.config.update("jax_enable_x64", True)
from bench.cell import run_cell
from bench.spec import cell_spec, load_benchmark
out, undo = {{}}, []
def patch(obj, name, value):
    undo.append((obj, name, getattr(obj, name)))
    setattr(obj, name, value)
for case, plant in {cases!r}:
    exec(plant)
    cell = cell_spec(load_benchmark(), {cell!r})
    cell["config"].update(r={narrow}, t={narrow})
    res = run_cell(cell, 2**31 + 77, 0.3, False, devices=jax.devices()[:cell["chips"]],
                   t_start=time.perf_counter(),
                   dtype="float32" if case == "control" else None)
    out[case] = res
    while undo:
        setattr(*undo.pop())
print(json.dumps(out))
"""

CASES = {
    "paper-8000.polycode": ["sound", "control", "answer", "pattern"],
    "serve-int4-8000.mesh4": ["sound", "control", "answer", "pattern", "exchange"],
}
PATTERNS = {"paper-8000.polycode": 10, "serve-int4-8000.mesh4": 6}


def _env(devices: int) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


@pytest.fixture(scope="module")
def results():
    """Every case of every cell: sound first, so a fault cannot leak into it."""
    out = {}
    for cell, cases in CASES.items():
        code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"), cell=cell,
                            narrow=NARROW[cell],
                            cases=[(c, FAULTS[c]) for c in cases])
        chips = 4 if cell.endswith("mesh4") else 1
        t0 = time.perf_counter()
        proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                              text=True, timeout=600, env=_env(chips))
        assert proc.returncode == 0, proc.stderr[-3000:]
        out[cell] = json.loads(proc.stdout.strip().splitlines()[-1])
        out[cell]["_seconds"] = time.perf_counter() - t0
    return out


@pytest.mark.parametrize("cell,case", [(c, k) for c, ks in CASES.items() for k in ks])
def test_correct_separates_sound_from_broken(results, cell, case):
    """Sound is correct with max_abs_err 0; the control and each fault are
    not, and the numbers compared come last with their limits."""
    res = results[cell][case]
    check = res["checks"]["max_abs_err"]
    assert list(res)[-1] == "checks" and check["limit"] == 0.0
    assert res["attempted"] >= PATTERNS[cell]    # a window deals every pattern
    if case == "sound":
        assert res["correct"] and check["value"] == 0.0 and res["failed"] == 0
        assert {"call_ms", "coding_tax", "setup_s"} <= set(res["metrics"])
    elif case == "pattern":                      # one output kept per pattern
        assert not res["correct"] and res["failed"] == 1 and check["value"] >= 1.0
    else:
        assert not res["correct"] and res["failed"] >= 1
        assert check["value"] == "inf" or check["value"] >= 1.0
