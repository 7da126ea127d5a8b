"""Serving-path bench: cold-compile vs cached-call latency per executor.

The unified runtime memoises jit-compiled executables by (backend, shape,
dtype, erasure-kind) and passes the erasure pattern strictly as data, so a
serving loop that sees a NEW erasure pattern every step still reuses one
compiled program.  This bench measures, per backend:

  cold_ms     first call: pipeline build + jit trace + XLA compile
  warm_ms     mean over repeated calls, each with a DIFFERENT mask
  executables jit specialisations after the loop (must stay at 1 - the
              proof that the cache removes recompiles from serving)

Rows are saved to BENCH_runtime.json (``main(save=...)`` / run.py).  The
mesh backend needs one device per worker (K=4): with that many devices the
mesh rows run in this process; on a CPU host with fewer they come from a
child interpreter with 8 fake CPU devices, and a failed child fails the
run.  A TPU host with fewer chips cannot give a child the chip this
process holds, so there the mesh rows are an error.  Off the chip,
absolute times are CPU-interpret numbers and the cold/warm RATIO is the
signal.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

import jax
from jax.sharding import AxisType

LOCAL_BACKENDS = ("reference", "staged", "fused")
_MESH_FLAG = "--mesh-json"


def _problem():
    import jax.numpy as jnp

    from repro.core import make_plan

    rng = np.random.default_rng(0)
    v, r, t = 512, 256, 256
    A = jnp.asarray(rng.integers(-4, 5, size=(v, r)), jnp.float64)
    B = jnp.asarray(rng.integers(-4, 5, size=(v, t)), jnp.float64)
    plan = make_plan("bec", 2, 2, 1, K=4, L=v * 4 * 4 + 1, points="chebyshev")
    return plan, A, B


def _masks(K: int, n: int):
    """n distinct single-erasure patterns, cycled."""
    return [[k % K] for k in range(n)]


def bench_backend(cm, A, B, reps: int = 8) -> dict:
    t0 = time.perf_counter()
    jax.block_until_ready(cm(A, B, erased=[0]))
    cold_ms = (time.perf_counter() - t0) * 1e3

    masks = _masks(cm.plan.K, reps)
    for erased in masks:  # warm the panels so warm_ms times the call path
        jax.block_until_ready(cm(A, B, erased=erased))
    t0 = time.perf_counter()
    for erased in masks:
        jax.block_until_ready(cm(A, B, erased=erased))
    warm_ms = (time.perf_counter() - t0) * 1e3 / reps

    info = cm.cache_info()
    return {
        "backend": cm.backend,
        "cold_ms": round(cold_ms, 2),
        "warm_ms": round(warm_ms, 3),
        "cold_over_warm": round(cold_ms / max(warm_ms, 1e-9), 1),
        "warm_patterns": len({tuple(m) for m in masks}),
        "builds": info["builds"],
        "executables": cm.executable_cache_size(),
    }


def run_local() -> list:
    from repro.core.numerics import enable_x64
    from repro.runtime import CodedMatmul

    with enable_x64():
        plan, A, B = _problem()
        rows = []
        for backend in LOCAL_BACKENDS:
            # independent facade per backend: per-row counters start at zero
            row = bench_backend(CodedMatmul(plan, backend), A, B)
            assert row["executables"] == row["builds"] == 1, row
            rows.append(row)
        return rows


def run_mesh() -> list:
    """Mesh-backend rows, one coded worker per device of a (n/4, 4) mesh."""
    from repro.core.numerics import enable_x64
    from repro.runtime import CodedMatmul

    with enable_x64():
        plan, A, B = _problem()
        n_dev = len(jax.devices())
        mesh = jax.make_mesh((n_dev // plan.K, plan.K), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cm = CodedMatmul(plan, "mesh", mesh=mesh, dtype=jax.numpy.float64)
        row = bench_backend(cm, A, B)
        assert row["executables"] == row["builds"], row
        return [row]


def run() -> list:
    rows = run_local()
    if len(jax.devices()) >= 4:
        rows.extend(run_mesh())
    elif jax.default_backend() == "cpu":
        rows.extend(_mesh_rows_via_subprocess())
    else:
        raise SystemExit(
            f"mesh rows need 4 {jax.default_backend()} devices, have "
            f"{len(jax.devices())}")
    return rows


def _mesh_rows_via_subprocess() -> list:
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (str(root / "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.runtime_bench", _MESH_FLAG],
        env=env, cwd=root, capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"mesh child failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def save_json(rows, path: str) -> None:
    with open(path, "w") as f:
        json.dump(rows, f, indent=2)
        f.write("\n")


def main(save: str | None = None):
    rows = run()
    print("backend,cold_ms,warm_ms,cold_over_warm,executables")
    for r in rows:
        print(f"{r['backend']},{r['cold_ms']},{r['warm_ms']},"
              f"{r['cold_over_warm']},{r['executables']}")
    if save:
        save_json(rows, save)
        print(f"saved {save}")
    return rows


if __name__ == "__main__":
    if _MESH_FLAG in sys.argv:
        print(json.dumps(run_mesh()))
    else:
        save = None if "--no-save" in sys.argv else "BENCH_runtime.json"
        if "--save" in sys.argv:
            i = sys.argv.index("--save")
            save = (sys.argv[i + 1] if i + 1 < len(sys.argv)
                    else "BENCH_runtime.json")
        main(save=save)
