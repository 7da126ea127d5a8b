"""The readers of the program's stages and spans, on a hand-built run."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import peaks, spec, stages, tracefile  # noqa: E402
from bench.tracefile import Event, Trace  # noqa: E402
from repro import obs  # noqa: E402

NEW = ["worker.encode_ms", "worker.slice_ms", "worker.dots_ms", "decode.apply_ms",
       "decode.extract_ms", "device.start_wait_ms", "host.panel_ms"]
OLD = ["call_ms", "call_ms.p90", "coding_tax", "peak_hbm_gb", "setup_s",
       "host.dispatch_ms", "host.compiles_in_window", "worker.stage_ms",
       "worker_roofline", "decode.stage_ms", "mesh.allgather_ms", "device.idle_share"]
CELLS = ["paper-8000.polycode", "serve-int4-8000.mesh4"]

def _scope(path):
    return "jit(coded_concrete)/" + path


# The one program as its optimized HLO gives it: a worker loop
# (no scope of its own) holding a sliced product, an encode fusion, a decode
# and an extraction; custom-call.9 is plumbing XLA adds with no scope and no
# user.  Each op: (name, op_name, operand names).
MODULE = ("jit_coded_concrete", [
    ("fusion.1", _scope("coded.encode/mul"), []),
    ("while.2", _scope("while"), ["fusion.1"]),
    ("fusion.3", _scope("while/body/coded.slice/round"), []),
    ("convolution.4", _scope("while/body/coded.dots/dot_general"), ["fusion.3"]),
    ("fusion.5", _scope("coded.decode/add"), []),
    ("fusion.6", _scope("coded.extract/select_n"), ["fusion.5"]),
    ("custom-call.9", "A", []),
])
# Another live executable whose op names repeat the program's.
OTHER = ("jit_bench_operands", [(op, "jit(bench_operands)/coded.decode/x", [])
                                for op, _, _ in MODULE[1]])


def _trace():
    """Two calls on two chips.  Chip 0 runs the program at 1000-1900 and
    3000-3900: an encode, a loop (slice and dot inside, 10 ns of its own),
    a decode, an extraction and the unscoped split; chip 1 starts later.
    The harness's dispatch and wait annotations frame each call."""
    def run(t):
        return [Event("%custom-call.9 = f32[8] custom-call()", t + 0, t + 40),
                Event("%fusion.1 = f64[8] fusion()", t + 50, t + 150),
                Event("%while.2 = (s32[]) while()", t + 150, t + 600),
                Event("%fusion.3 = s8[8] fusion()", t + 160, t + 300),
                Event("%convolution.4 = s32[8] convolution()", t + 300, t + 600),
                Event("%fusion.5 = f64[8] fusion()", t + 600, t + 800),
                Event("%fusion.6 = f64[8] fusion()", t + 800, t + 900)]

    ops = {0: run(1000) + run(3000), 1: run(1200) + run(3100)}
    modules = {0: [Event("jit_coded_concrete(77)", 1000, 1900),
                   Event("jit_coded_concrete(77)", 3000, 3900)],
               1: [Event("jit_coded_concrete(77)", 1150, 2100),
                   Event("jit_coded_concrete(77)", 3100, 4000)]}
    host = [Event("bench.window", 500, 4500),
            Event("bench.dispatch", 600, 990), Event("bench.wait", 990, 2500),
            Event("bench.dispatch", 2700, 2990), Event("bench.wait", 2990, 4200)]
    return Trace(ops, {}, modules, host)


def _ctx(devices, trace=None):
    from bench.cell import Context

    tr = trace or _trace()
    ctx = Context(setup_s=1.0, window_s=4e-6, latencies_s=[2e-6, 2e-6],
                  dispatch_s=[4e-7, 3e-7], plain_s=1e-9, peak_bytes=10 ** 9,
                  compiles_in_window=0, shape=(8000, 4000, 4000),
                  peaks=peaks.peaks_for("TPU v5 lite"), trace=tr,
                  trace_devices=devices, stages={"worker": 9e7, "decode": 1e7})
    ctx.trace_window = tracefile.covered(tr, devices, *tracefile.window(tr))
    ctx.trace_calls = tracefile.calls_in(tr, *ctx.trace_window)
    return ctx


@pytest.fixture
def program(monkeypatch):
    """The live executables' HLO, and a session holding the spans of a
    warm-up call and the two traced calls, on a simulated clock in seconds;
    each call's launch opens 0.2 us after the call, which its dispatch
    annotation starts."""
    monkeypatch.setattr(stages, "_live_modules", lambda: [OTHER, MODULE])
    clock = obs.SettableClock()
    sess = obs.enable(fresh=True, clock=clock)
    for start in (1.0, 2.0, 3.0):
        clock.set(start)
        with obs.span(obs.CALL, ordinal=int(start)):
            clock.set(start + 5e-8)
            with obs.span(obs.PANEL):
                clock.set(start + 1.5e-7)
            clock.set(start + 2e-7)
            with obs.span(obs.LAUNCH):
                clock.set(start + 3e-7)
    yield sess
    obs.disable()


@pytest.mark.parametrize("devices,metric,value", [
    ([0], "worker.encode_ms", 100e-6),
    ([0], "worker.slice_ms", 140e-6),
    ([0], "worker.dots_ms", 300e-6),
    ([0], "decode.apply_ms", 200e-6),
    ([0], "decode.extract_ms", 100e-6),
    ([0], "host.panel_ms", 1e-4),
    # launches at 800 and 2900 ns: chip 0's first ops follow 200 and 100 ns
    # later, chip 1's 400 and 200 ns later
    ([0], "device.start_wait_ms", (200 + 100) / 2 * 1e-6),
    ([0, 1], "device.start_wait_ms", (400 + 200) / 2 * 1e-6),
])
def test_stage_readers_split_the_traced_calls(program, devices, metric, value):
    """Each op counts in the innermost stage of its scope, by self time, per
    traced call; spans are placed by the dispatch annotation of their call."""
    got = spec.load_reader(metric)(_ctx(devices))
    assert got == pytest.approx(value, rel=1e-6)


@pytest.mark.parametrize("metric,value", [("worker.dots_ms", 300e-6),
                                          ("device.start_wait_ms", 150e-6)])
def test_readers_need_no_module_line(program, metric, value):
    """A profile that drops a chip's module runs (the mesh's chip 0 does)
    still splits the calls: ops are matched by name, launches by time."""
    tr = _trace()
    tr.modules = {0: tr.modules[0][:1]}
    assert spec.load_reader(metric)(_ctx([0], tr)) == pytest.approx(value, rel=1e-6)


def test_split_is_read_on_the_first_chip_that_names_the_program(program):
    """Where chip 0's record files its ops under names the program does not
    have (the mesh's chip 0), the split is read on the next chip."""
    tr = _trace()
    tr.ops[0] = [Event(f"%region.{i} = f32[8] fusion()", e.start, e.end)
                 for i, e in enumerate(tr.ops[0])]
    ctx = _ctx([0, 1], tr)
    assert spec.load_reader("worker.slice_ms")(ctx) == pytest.approx(140e-6)
    assert spec.load_reader("worker.slice_ms")(_ctx([0], tr)) is None


def test_stages_cover_the_busy_time_but_the_unscoped_plumbing(program):
    """The five scoped metrics, the unscoped custom-call and the worker loop's
    own time add up to chip 0's busy time per call."""
    ctx = _ctx([0])
    scoped = sum(spec.load_reader(m)(ctx) for m in NEW[:5])
    busy = tracefile.busy_ns(ctx.trace, 0, *ctx.trace_window) / 1e6 / ctx.trace_calls
    assert scoped + (40 + 10) * 1e-6 == pytest.approx(busy)


def test_a_stage_with_no_op_reads_zero(program):
    """Where the program ran but none of its ops is in a stage, the stage
    reads 0, not nothing."""
    assert stages.stage_ms(_ctx([0]), "coded.allgather") == 0.0


def test_ops_are_joined_with_the_module_whose_run_holds_them(program):
    """Op names repeat across executables: a run of another module, whose
    ops carry the program's names, counts in that module's stages."""
    tr = _trace()
    tr.ops[0] += [Event(e.name, e.start + 5000, e.end + 5000) for e in tr.ops[0][:7]]
    tr.modules[0].append(Event("jit_bench_operands(5)", 6000, 6900))
    tr.host[0] = Event("bench.window", 500, 7000)
    ctx = _ctx([0], tr)
    assert ctx.trace_calls == 2
    assert spec.load_reader("worker.encode_ms")(ctx) == pytest.approx(100e-6)
    assert spec.load_reader("decode.apply_ms")(ctx) == pytest.approx(
        (2 * 200 + 40 + 100 + 10 + 140 + 300 + 200 + 100) / 2 * 1e-6)


def test_module_stages_takes_the_innermost_scope():
    """A loop's body ops belong to their own stage; an op whose op_name holds
    no known stage, and no user to take one from, is left out."""
    ops = [("while.1", "jit(f)/coded.dots/while", []),
           ("fusion.2", "jit(f)/coded.dots/while/body/coded.slice/round", []),
           ("custom-call.3", "A", []),
           ("add.5", "jit(f)/coded.notastage/add", [])]
    assert stages.module_stages(ops) == {"while.1": "coded.dots",
                                         "fusion.2": "coded.slice"}


def test_module_stages_gives_xla_plumbing_the_stage_of_its_users():
    """An op XLA adds with no scope (a float64 parameter's split into f32,
    a copy feeding it on) takes the stage its users share; one whose users
    disagree keeps none."""
    ops = [("A.1", "args[0]", []),
           ("args_0_.0", "args[0]", ["A.1"]),
           ("copy.2", "", ["args_0_.0"]),
           ("fusion.3", "jit(f)/coded.encode/mul", ["copy.2"]),
           ("W.4", "args[3]", []),
           ("fusion.5", "jit(f)/coded.decode/add", ["W.4", "fusion.3"]),
           ("fusion.6", "jit(f)/coded.extract/round", ["fusion.3"]),
           ("tuple.7", "", ["fusion.5", "fusion.6"])]
    got = stages.module_stages(ops)
    assert got["args_0_.0"] == got["copy.2"] == got["A.1"] == "coded.encode"
    assert got["W.4"] == "coded.decode" and "tuple.7" not in got


def test_live_executables_hold_the_programs_stages():
    """XLA keeps each live executable's optimized HLO; read from the protobuf
    wire, the facade's module gives its stages."""
    import jax.numpy as jnp

    from repro.core import make_plan
    from repro.runtime import CodedMatmul

    plan = make_plan("bec", 2, 2, 1, K=4, L=32 * 16 + 1, points="chebyshev")
    cm = CodedMatmul(plan, "reference", dtype=jnp.float32)
    cm(jnp.ones((32, 16)), jnp.ones((32, 8)), erased=[1]).block_until_ready()
    got = {name: set(stages.module_stages(ops).values())
           for name, ops in stages._live_modules()}
    assert {"coded.decode", "coded.dots", "coded.encode", "coded.extract"} <= got[
        "jit_coded_concrete"]


def test_calls_outside_the_covered_window_are_not_matched(program):
    """Only calls whose dispatch lies where every chip's record reaches count."""
    ctx = _ctx([0])
    ctx.trace_window = (500, 2600)
    assert [c.attrs["ordinal"] for _, c, _ in stages.traced_calls(ctx)] == ["2"]


@pytest.mark.parametrize("metric", NEW)
def test_new_readers_read_nothing_without_the_program(monkeypatch, metric):
    """Without the program's stages and spans (a program that names no stage
    and records no span, or observability off), each new reader returns None
    and raises nothing; so also without a trace."""
    unscoped = (MODULE[0], [(op, "jit(fn)/mul", ins) for op, _, ins in MODULE[1]])
    monkeypatch.setattr(stages, "_live_modules", lambda: [unscoped])
    obs.disable()
    assert spec.load_reader(metric)(_ctx([0])) is None
    obs.enable(fresh=True)
    try:
        assert spec.load_reader(metric)(_ctx([0])) is None
        monkeypatch.setattr(stages, "_live_modules", lambda: [MODULE])
        ctx = _ctx([0])
        ctx.trace = None
        assert spec.load_reader(metric)(ctx) is None
    finally:
        obs.disable()


@pytest.mark.parametrize("metric", OLD)
def test_existing_readers_read_the_same_with_the_program_recorded(monkeypatch, metric):
    """The program's scopes and spans change no number an earlier reader gives."""
    obs.disable()
    before = spec.load_reader(metric)(_ctx([0, 1]))
    monkeypatch.setattr(stages, "_live_modules", lambda: [MODULE])
    clock = obs.SettableClock()
    obs.enable(fresh=True, clock=clock)
    try:
        with obs.span("coded.call", ordinal=1):
            clock.set(1.0)
        assert spec.load_reader(metric)(_ctx([0, 1])) == before
    finally:
        obs.disable()


def test_every_new_metric_is_found_by_name_in_every_cell():
    """Each new metric has a reader and is reported in both cells."""
    bm = spec.load_benchmark()
    entries = {m["name"]: m for m in bm["per_layer"]}
    for name in NEW:
        assert callable(spec.load_reader(name))
        assert entries[name]["workloads"] == CELLS
    for cell in CELLS:
        assert set(NEW) <= {m["name"] for m in spec.cell_spec(bm, cell)["per_layer"]}
