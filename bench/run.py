"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout, on a machine that holds the chips the
cell asks for.  It exits non-zero, and prints no result, where JAX finds no
TPU, fewer chips than the cell needs, or a chip missing from the peaks
table.  The last line of stdout is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number with its limit;
the same numbers end stderr.  JAX's persistent compilation cache is
``JAX_COMPILATION_CACHE_DIR`` where that is set, else ``.jax_cache`` in the
checkout; a traced run's profile goes to a temporary directory and is
deleted once read.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu logs to /tmp/tpu_logs unless told otherwise; a run writes nothing
# outside its checkout and its own temporary directory
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def chips_for(cell: dict):
    """The cell's TPU chips with the compile cache and x64 on; None, after
    saying why on stderr, where JAX has too few of them."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"{cell['name']} needs {cell['chips']} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_enable_x64", True)
    return devices[:cell["chips"]]


def main(argv=None) -> int:
    """Parse the arguments, find the chips, run the cell, print the line."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.spec import cell_spec, load_benchmark

    cell = cell_spec(load_benchmark(ROOT), args.workload)
    devices = chips_for(cell)
    if devices is None:
        return 1
    from bench.cell import run_cell
    from bench.peaks import peaks_for

    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices, t_start=T_START,
                      peaks=peaks_for(devices[0].device_kind))
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
