"""The two readings a cell's limit is set from, on the chip.

    python bench/control.py --workload <cell> --seconds <s> \
        --seeds <n> ... --control-seeds <n> ...

Each of ``--seeds`` is one run of the program as the configuration states
it: each number its check compares (``max_abs_err`` for the coded matmul)
is a lower reading.  Each of ``--control-seeds`` is one run of the control,
the program's own path one precision below, which the cell's system module
names (``CONTROL_DTYPE``; float32 in place of float64 for the coded
matmul): its numbers are upper readings, and the control has to come out
not correct.  All runs share one process and one system per precision,
built through the cell's system module, so each precision compiles once.
One JSON line per run on stdout, then a summary line.  The benchmark's own
runs never run this.
"""
import argparse
import json
import sys
import time

from run import ROOT, chips_for


def main(argv=None) -> int:
    """Run the sound seeds, then the control seeds; print the readings."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    from bench.cell import run_cell
    from bench.spec import cell_spec, load_benchmark

    cell = cell_spec(load_benchmark(ROOT), args.workload)
    devices = chips_for(cell)
    if devices is None:
        return 1
    system = cell["system"]
    readings = {}
    for dtype, seeds in ((None, args.seeds), (system.CONTROL_DTYPE, args.control_seeds)):
        if not seeds:
            continue
        sut = system.build(cell["config"], cell["traffic"], devices, dtype)
        for seed in seeds:
            res = run_cell(cell, seed, args.seconds, False, devices=devices,
                           t_start=time.perf_counter(), sut=sut)
            got = {k: c["value"] for k, c in res["checks"].items()}
            readings.setdefault(str(sut.dtype), []).append(got)
            print(json.dumps({"dtype": str(sut.dtype), "seed": seed,
                              "correct": res["correct"], "attempted": res["attempted"],
                              "checks": got, "metrics": res["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
