"""The trace reduction, the peaks table and the spec lookups, on the CPU."""
from __future__ import annotations

import json
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import peaks, spec, tracefile  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402


def _trace():
    """Two chips; chip 0 runs a loop with two ops inside, then an
    all-gather whose async part overlaps a fusion; chip 1 runs one op."""
    ops0 = [
        Event("%while.1 = (u32[]) while(...)", 100, 200),
        Event("%fusion.2 = f32[8] fusion(...)", 110, 130),
        Event("%convolution.3 = s32[8] convolution(...)", 140, 190),
        Event("%all-gather-start.1 = f64[4,2] all-gather-start(...)", 300, 305),
        Event("%fusion.4 = f64[8] fusion(...)", 305, 340),
        Event("%all-gather-done.1 = f64[4,2] all-gather-done(...)", 340, 350),
    ]
    async0 = [Event("%all-gather-start.1 = f64[4,2] all-gather-start(...)", 305, 345)]
    ops1 = [Event("%fusion.9 = f64[8] fusion(...)", 120, 180)]
    modules = {0: [Event("jit_bench_worker_stage(123)", 100, 200),
                   Event("jit_bench_worker_stage(123)", 400, 500),
                   Event("jit_fn(9)", 300, 350)]}
    host = [Event("bench.window", 50, 450),
            Event("bench.dispatch", 60, 95),
            Event("bench.wait", 95, 290),
            Event("bench.dispatch", 355, 420)]
    return Trace({0: ops0, 1: ops1}, {0: async0}, modules, host)


def test_short_name():
    """An HLO op's text is cut to its name; anything else is kept."""
    assert tracefile.short_name("%while.57 = (u32[]{:T(128)}) while()") == "while.57"
    assert tracefile.short_name("%all-gather-start.1 = f64[2]") == "all-gather-start.1"
    assert tracefile.short_name("jit_fn(123)") == "jit_fn"


def test_union_merges_and_clips():
    """Overlapping and nested intervals merge; the window clips them."""
    evs = [Event("a", 0, 10), Event("b", 5, 20), Event("c", 30, 40),
           Event("d", 32, 35), Event("e", 50, 60)]
    assert tracefile.union(evs, 2, 55) == [(2, 20), (30, 40), (50, 55)]
    assert tracefile.union(evs, 100, 200) == []


def test_window_from_annotation_and_fallback():
    """The harness's window annotation wins; without it, the device span."""
    tr = _trace()
    assert tracefile.window(tr) == (50, 450)
    tr.host = []
    assert tracefile.window(tr) == (100, 350)
    with pytest.raises(ValueError):
        tracefile.window(Trace({}, {}, {}, []))


def test_busy_and_idle_cover_the_window():
    """Busy is the union of sync and async ops; busy plus gaps is the window."""
    tr = _trace()
    lo, hi = tracefile.window(tr)
    assert tracefile.busy_ns(tr, 0, lo, hi) == 100 + 50
    gaps = tracefile.idle_gaps(tr, 0, lo, hi)
    assert gaps == [(50, 100), (200, 300), (350, 450)]
    assert tracefile.busy_ns(tr, 0, lo, hi) + sum(e - s for s, e in gaps) == hi - lo
    assert tracefile.busy_ns(tr, 1, lo, hi) == 60
    assert tracefile.busy_ns(tr, 7, lo, hi) == 0


def test_gap_label_takes_the_widest_overlap():
    """A gap is labelled by the annotation that covers most of it."""
    host = _trace().host
    assert tracefile.gap_label((50, 100), host) == "dispatch"
    assert tracefile.gap_label((200, 300), host) == "wait"
    assert tracefile.gap_label((350, 450), host) == "dispatch"
    assert tracefile.gap_label((290, 300), host) == "harness"


def test_self_times_count_no_nanosecond_twice():
    """A loop keeps only the time no op inside it covers."""
    tr = _trace()
    st = tracefile.self_times(tr.ops[0])
    assert st["while.1"] == 100 - 20 - 50
    assert st["fusion.2"] == 20 and st["convolution.3"] == 50
    assert sum(st.values()) == sum(e - s for s, e in tracefile.union(tr.ops[0], 0, 1e9))


def test_module_and_collective_time():
    """Module runs sum by name prefix; the all-gather covers start to done."""
    tr = _trace()
    assert tracefile.module_ns(tr, 0, "jit_bench_worker_stage") == (200, 2)
    assert tracefile.module_ns(tr, 0, "jit_bench_decode_stage") == (0, 0)
    assert tracefile.matching_ns(tr, 0, "all-gather", 50, 450) == 5 + 40 + 10 - 5
    assert tracefile.matching_ns(tr, 1, "all-gather", 50, 450) == 0


def test_breakdown_lists_ops_and_gaps_in_seconds():
    """Top ops by self time and the longest gaps, each as [name, seconds]."""
    tr = _trace()
    b = tracefile.breakdown(tr, 0, *tracefile.window(tr), top=2)
    assert b["device_ops"] == [["convolution.3", 50e-9], ["fusion.4", 35e-9]]
    assert b["idle_gaps"] == [["wait", 100e-9], ["dispatch", 100e-9]]


def test_load_reads_the_harness_annotations(tmp_path):
    """A trace the profiler writes on the CPU loads, with its annotations."""
    code = f"""
import sys
sys.path[:0] = [{str(ROOT)!r}]
import jax, jax.numpy as jnp
from bench import tracefile
f = jax.jit(lambda x: x @ x)
x = jnp.ones((64, 64))
f(x).block_until_ready()
jax.profiler.start_trace({str(tmp_path)!r})
with jax.profiler.TraceAnnotation("bench.window"):
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        y = f(x)
    y.block_until_ready()
jax.profiler.stop_trace()
from pathlib import Path
tr = tracefile.load(next(Path({str(tmp_path)!r}).rglob("*.xplane.pb")))
print(sorted(e.name for e in tr.host), tracefile.window(tr)[1] > tracefile.window(tr)[0])
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env={**_cpu_env()})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "['bench.dispatch', 'bench.window'] True"


def _cpu_env():
    import os

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_unknown_device_kind_raises():
    """Peaks come only from the table: an unknown chip is an error."""
    assert peaks.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks_for("cpu")


def test_least_time_names_its_bound():
    """The paper's 8000^3 product is compute-bound; a thin one is memory-bound."""
    p = peaks.peaks_for("TPU v5 lite")
    ops, nbytes = peaks.plain_matmul_work(8000, 8000, 8000)
    assert (ops, nbytes) == (2 * 8000 ** 3, 2 * 8000 ** 2 + 4 * 8000 ** 2)
    least, bound = peaks.least_time_s(ops, nbytes, p)
    assert bound == "compute" and least == pytest.approx(1.024e12 / 393e12)
    least, bound = peaks.least_time_s(*peaks.plain_matmul_work(8, 8000, 8000), p)
    assert bound == "memory" and least == pytest.approx((64000 + 4 * 64e6 + 64000) / 819e9)


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_and_metric_is_found_by_name():
    """Each cell's configuration, traffic and metric readers exist as files."""
    bm = spec.load_benchmark()
    cells = {w["name"] for w in bm["workloads"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and callable(spec.load_reader(m["name"]))
        assert set(m.get("workloads", cells)) <= cells
    for w in bm["workloads"]:
        cell = spec.cell_spec(bm, w["name"])
        assert cell["config"]["name"] == w["config"]
        assert cell["traffic"]["backend"] in ("reference", "mesh")
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2 and cell["per_layer"]
    with pytest.raises(KeyError):
        spec.cell_spec(bm, "no-such-cell")
    with pytest.raises(KeyError):
        spec.load_reader("no-such-metric")


def test_configs_state_every_size_they_run_with():
    """The configuration files carry every key the harness reads."""
    bm = spec.load_benchmark()
    for c in bm["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        for key in ("v", "r", "t", "p", "m", "n", "K", "points", "entry_min",
                    "entry_max", "scheme", "dtype"):
            assert key in cfg, (c["name"], key)


def test_run_fails_without_a_tpu():
    """On the CPU the command exits non-zero and prints no result."""
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-8000.polycode",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120, env=_cpu_env())
    assert out.returncode != 0 and out.stdout == ""
    assert "TPU" in out.stderr


def test_covered_window_ends_where_a_chip_stopped_recording():
    """A chip whose record ends early ends the window; an empty one does not."""
    tr = _trace()
    assert tracefile.covered(tr, [0], 50, 450) == (50, 350)
    assert tracefile.covered(tr, [0, 1], 50, 450) == (50, 180)
    assert tracefile.covered(tr, [0, 7], 50, 450) == (50, 350)
    assert tracefile.calls_in(tr, 50, 450) == 2
    assert tracefile.calls_in(tr, 50, 350) == 1


def _ctx(devices, **kw):
    from bench.cell import Context

    tr = _trace()
    ctx = Context(setup_s=1.0, window_s=4e-7, latencies_s=[2e-7, 2e-7],
                  dispatch_s=[1e-8, 3e-8], plain_s=1e-9, peak_bytes=0,
                  compiles_in_window=0, shape=(8000, 8000, 8000),
                  peaks=peaks.peaks_for("TPU v5 lite"), trace=tr,
                  trace_devices=devices, **kw)
    ctx.trace_window = tracefile.covered(tr, devices, *tracefile.window(tr))
    ctx.trace_calls = tracefile.calls_in(tr, *ctx.trace_window)
    return ctx


@pytest.mark.parametrize("devices,metric,value", [
    ([0], "device.idle_share", 50.0),
    ([0, 1], "device.idle_share", 100 * (50 + 70) / 2 / 130),
    ([0], "mesh.allgather_ms", 50e-6),
    ([0, 1], "mesh.allgather_ms", None),
    ([0], "host.dispatch_ms", 2e-5),
    ([0], "call_ms", 2e-4),
    ([0], "coding_tax", 200.0),
    ([0], "peak_hbm_gb", None),
    ([0], "worker.stage_ms", None),
])
def test_readers_on_a_synthetic_run(devices, metric, value):
    """Each reader reads what it names, and nothing where nothing ran."""
    got = spec.load_reader(metric)(_ctx(devices))
    assert got == (None if value is None else pytest.approx(value))


def test_roofline_reader_uses_the_table_and_the_stage_time():
    """worker_roofline is the plain product's least time over the stage's."""
    ctx = _ctx([0], stages={"worker": 391.3e6, "decode": 172.5e6})
    least = 1.024e12 / 393e12
    assert spec.load_reader("worker_roofline")(ctx) == pytest.approx(100 * least / 0.3913)
    assert spec.load_reader("worker.stage_ms")(ctx) == pytest.approx(391.3)
    assert spec.load_reader("decode.stage_ms")(ctx) == pytest.approx(172.5)


@pytest.mark.parametrize("stats,reserve,room", [
    (None, 0, 0),
    ({"peak_bytes_in_use": 3}, 0, 0),
    ({"bytes_limit": 100, "peak_bytes_in_use": 30}, 10, 40),
    ({"bytes_limit": 100, "peak_bytes_in_use": 30, "peak_bytes_reserved": 25}, 10, 15),
    ({"bytes_limit": 100, "peak_bytes_in_use": 75}, 10, 0),
])
def test_pool_room_fills_the_chip_beside_the_peak(stats, reserve, room):
    """The pool grows to POOL_FILL of the chip, less its peak (buffers and
    the executables' reserved temporaries) and what the kept outputs will
    hold; not at all where the backend gives no limit."""
    from bench.cell import POOL_FILL, pool_room

    assert POOL_FILL == 0.8 and pool_room(stats, reserve) == room


def test_peak_counts_the_reserved_temporaries():
    """On a TPU an executable's temporaries sit in the reserved bytes."""
    from bench.cell import peak_bytes

    assert peak_bytes(None) == 0
    assert peak_bytes({"peak_bytes_in_use": 2863081472,
                       "peak_bytes_reserved": 7177617408}) == 10040698880


def test_deck_deals_every_pattern_once_a_round():
    """Each round is a permutation of the patterns, in an order of the seed's."""
    import numpy as np

    from bench.cell import _deck

    pats = [(0,), (1,), (2,), (3,)]
    deals = [[next(d) for _ in range(12)]
             for d in (_deck(pats, np.random.default_rng(s)) for s in (1, 1, 2))]
    for deal in deals:
        assert all(sorted(deal[i:i + 4]) == pats for i in (0, 4, 8))
    assert deals[0] == deals[1] and deals[0] != deals[2]
