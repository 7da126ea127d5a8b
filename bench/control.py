"""The two readings a cell's limit is set from, on the chip.

    python bench/control.py --workload <cell> --seconds <s> \
        --seeds <n> ... --control-seeds <n> ...

Each of ``--seeds`` is one run of the program as the configuration states
it: its ``max_abs_err`` is a lower reading.  Each of ``--control-seeds`` is
one run of the control, the program's own float32 path switched on in place
of float64: its ``max_abs_err`` is an upper reading, and the control has to
come out not correct.  All runs share one process and one facade per
precision, so each precision compiles once.  One JSON line per run on
stdout, then a summary line.  The benchmark's own runs never run this.
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
    from bench.system import build

    cell = cell_spec(load_benchmark(ROOT), args.workload)
    devices = chips_for(cell)
    if devices is None:
        return 1
    readings = {}
    for dtype, seeds in (("float64", args.seeds), ("float32", args.control_seeds)):
        if not seeds:
            continue
        sut = build(cell["config"], cell["traffic"], devices, dtype)
        for seed in seeds:
            res = run_cell(cell, seed, args.seconds, False, devices=devices,
                           t_start=time.perf_counter(), sut=sut)
            err = res["checks"]["max_abs_err"]["value"]
            readings.setdefault(dtype, []).append(err)
            print(json.dumps({"dtype": dtype, "seed": seed, "correct": res["correct"],
                              "attempted": res["attempted"], "max_abs_err": err,
                              "metrics": res["metrics"]}), flush=True)
    print(json.dumps({"workload": args.workload, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
