"""One run of one cell: set-up, the measured window, and the check.

The harness knows no kind of system.  A cell's configuration names the
system it drives (``bench/spec.py``; ``bench/systems/<name>.py``, the coded
matmul where it names none), and everything about that system's inputs, its
timed call, its plain counterpart and its check comes from the ``System``
that module builds.  A new cell, even of a new kind of system, is new files
(data, a system module, its reference, its readers) and an entry in
``BENCHMARK.json``, never an edit here.

Set-up, all of it in ``setup_s``: build the system, make its first pool
items on the device from the seed, let it prepare (the coded matmul factors
the decode panel of every erasure pattern the traffic can draw), warm the
timed call, time the plain counterpart, and grow the pool to fill the chip
beside the program's peak.  A traced run also lets the system trace its
split stages, which the window itself never calls.

The window is a closed loop with one call in flight: take the next erasure
pattern, call the system on the next pool item, block until the result is
ready.  Patterns come as a deck shuffled from the seed, every pattern once
a round, and the window lasts at least one round.  Items cycle through the
pool.  One output of each pattern, drawn from the seed among that pattern's
calls, is kept for the check.  A traced run traces the window's first
``TRACE_CALLS`` calls and no more.

After the window the peak device memory is read, the pool is freed but for
what the check needs of it, and the system checks every kept output against
its plain reference: each number it compares is the worst over the kept
outputs, held to the system's limit.  Then each metric the cell reports is
read by its reader, from a ``Context``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import math
import statistics
import sys
import tempfile
import time

import jax
import numpy as np

from bench import tracefile
from bench.counters import CompileWatch
from bench.spec import load_reader

__all__ = ["Context", "run_cell", "peak_bytes", "pool_room"]

WARM_CALLS = 2          # the first call compiles or loads from the cache
POOL_MIN = 2            # pool items made before the warm-up
POOL_FILL = 0.8         # the pool grows until the chip's peak would reach this share
PLAIN_REPS = 5          # the plain counterpart's time is the median of these
PLAIN_BATCH_S = 0.3     # each a batch this long: the host clock errs by ~0.5 ms
PLAIN_IN_FLIGHT = 3
TRACE_CALLS = 12        # calls of the window a traced run traces


@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) reads."""

    setup_s: float
    window_s: float
    latencies_s: list       # per call: dispatch to ready
    dispatch_s: list        # per call: entering the system's call to its return
    plain_s: float          # one plain counterpart of a call, median
    peak_bytes: int         # peak_bytes() of the fullest chip
    compiles_in_window: int
    shape: tuple            # (v, r, t)
    peaks: dict | None
    trace: tracefile.Trace | None = None
    trace_devices: list = dataclasses.field(default_factory=list)
    trace_window: tuple = ()     # (lo, hi) ns that every chip's trace covers
    trace_calls: int = 0         # calls dispatched in trace_window
    stages: dict | None = None   # stage -> device ns per call
    system: object = None        # the system the window drove, for its own readers


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


def peak_bytes(stats: dict | None) -> int:
    """A chip's peak from its ``memory_stats()``: the peak of its live
    buffers plus the peak its runtime reserved for executables' temporaries,
    which on a TPU are held apart from the buffers and counted only there."""
    stats = stats or {}
    return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)


def pool_room(stats: dict | None, reserve: int) -> int:
    """Bytes the pool may still take on a chip whose ``memory_stats()`` are
    ``stats``: ``POOL_FILL`` of its memory, less its peak so far and
    ``reserve``; 0 where the backend reports no limit."""
    if not stats or "bytes_limit" not in stats:
        return 0
    return max(0, int(POOL_FILL * stats["bytes_limit"])
               - peak_bytes(stats) - reserve)


def _time_plain(sut, item) -> float:
    """Seconds of one plain counterpart of a call on ``item``: the median
    over ``PLAIN_REPS`` batches, each a run of back-to-back calls with a few
    in flight."""
    jax.block_until_ready(sut.plain(item))
    t0 = time.perf_counter()
    jax.block_until_ready(sut.plain(item))
    n = max(PLAIN_IN_FLIGHT, math.ceil(PLAIN_BATCH_S / (time.perf_counter() - t0)))
    means = []
    for _ in range(PLAIN_REPS):
        flight = collections.deque()
        t0 = time.perf_counter()
        for _ in range(n):
            flight.append(sut.plain(item))
            if len(flight) > PLAIN_IN_FLIGHT:
                flight.popleft().block_until_ready()
        jax.block_until_ready(list(flight))
        means.append((time.perf_counter() - t0) / n)
    return statistics.median(means)


def _deck(patterns: list, rng):
    """The patterns, every one once a round, each round shuffled by ``rng``."""
    while True:
        for k in rng.permutation(len(patterns)):
            yield patterns[k]


def _untraced(name):
    return contextlib.nullcontext()


def _window(sut, pool: list, seconds: float, deck, keep, trace_dir):
    """The measured loop; with ``trace_dir`` its first ``TRACE_CALLS`` calls
    are traced there.  Returns (t_first, t_end, start times, latencies,
    dispatch times, kept outputs as {pattern: (output, item index)})."""
    starts, lat, disp, kept, seen = [], [], [], {}, collections.Counter()

    def call(annotate):
        erased, k = next(deck), len(lat) % len(pool)
        t0 = time.perf_counter()
        with annotate("bench.dispatch"):
            C = sut.call(pool[k], erased)
        t1 = time.perf_counter()
        with annotate("bench.wait"):
            C.block_until_ready()
        starts.append(t0)
        lat.append(time.perf_counter() - t0)
        disp.append(t1 - t0)
        seen[erased] += 1
        if keep.integers(seen[erased]) == 0:   # one per pattern (Algorithm R)
            kept[erased] = (C, k)
        # an output not kept is freed here, before the next call allocates one

    def more():
        return len(lat) < len(sut.patterns) or time.perf_counter() < deadline

    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    t_first = time.perf_counter()
    deadline = t_first + seconds
    if trace_dir is not None:
        with jax.profiler.TraceAnnotation(tracefile.WINDOW):
            while len(lat) < TRACE_CALLS and more():
                call(jax.profiler.TraceAnnotation)
        jax.profiler.stop_trace()
    while more():
        call(_untraced)
    return t_first, time.perf_counter(), starts, lat, disp, kept


def _number(x: float):
    return x if math.isfinite(x) else str(x)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             devices: list, t_start: float, peaks: dict | None = None,
             dtype=None, sut=None) -> dict:
    """One run of ``cell`` (``spec.cell_spec``); returns the result line.

    ``dtype`` runs the program at another precision (the control), and
    ``sut`` reuses a system built by ``cell["system"].build`` across runs in
    one process.
    """
    if sut is None:
        sut = cell["system"].build(cell["config"], cell["traffic"], devices, dtype)
    watch = CompileWatch()
    data_seq, draw_seq, keep_seq = np.random.SeedSequence(seed % 2 ** 64).spawn(3)
    pool = sut.make_items(data_seq, 0, POOL_MIN)
    sut.prepare()
    for i in range(WARM_CALLS):
        jax.block_until_ready(sut.call(pool[i % len(pool)],
                                       sut.patterns[i % len(sut.patterns)]))
    plain_s = _time_plain(sut, pool[0])
    stages = sut.trace_stages(pool[0], devices[0].id) if trace else None
    # every executable of the run has reserved its temporaries by now
    room = pool_room(devices[0].memory_stats(), sut.kept_bytes)
    pool += sut.make_items(data_seq, len(pool), room // sut.item_bytes)

    tmp = tempfile.TemporaryDirectory(prefix="bench-window-") if trace else None
    watch.mark()
    t_first, t_end, starts, lat, disp, kept = _window(
        sut, pool, seconds, _deck(sut.patterns, np.random.default_rng(draw_seq)),
        np.random.default_rng(keep_seq), tmp and tmp.name)
    peak = max(peak_bytes(d.memory_stats()) for d in devices)
    compiles = watch.delta()
    trace_read = None
    if trace:
        trace_read = tracefile.load_dir(tmp.name)
        tmp.cleanup()

    refs = [sut.check_inputs(item) for item in pool]
    items = len(pool)
    del pool
    gc.collect()
    errs = sut.check(kept, refs)
    del kept
    limits = sut.limits
    checks = {k: max(e[k] for e in errs) for k in limits}
    correct = all(checks[k] <= limits[k] for k in limits)

    ctx = Context(
        setup_s=t_first - t_start, window_s=t_end - t_first, latencies_s=lat,
        dispatch_s=disp, plain_s=plain_s, peak_bytes=peak,
        compiles_in_window=compiles, shape=sut.shape, peaks=peaks,
        trace=trace_read, trace_devices=[d.id for d in devices],
        stages=stages, system=sut)
    if trace_read is not None:
        ctx.trace_window = tracefile.covered(
            trace_read, ctx.trace_devices, *tracefile.window(trace_read))
        ctx.trace_calls = tracefile.calls_in(trace_read, *ctx.trace_window)
    metrics = {}
    for m in cell["per_layer" if trace else "end_to_end"]:
        value = load_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": len(lat),
              "failed": sum(any(e[k] > limits[k] for k in limits) for e in errs),
              "metrics": metrics, "device": device}
    if trace_read is not None:
        lo, hi = ctx.trace_window
        busy = [tracefile.busy_ns(trace_read, d, lo, hi) for d in ctx.trace_devices]
        device["busy_s"] = statistics.fmean(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        result["breakdown"] = tracefile.breakdown(
            trace_read, ctx.trace_devices[0], lo, hi)
    q = np.percentile(lat, [0, 10, 50, 100]) * 1e3
    slowest = ", ".join(
        f"#{i} at {starts[i] - t_start:.2f} s {lat[i] * 1e3:.2f} ms "
        f"(dispatch {disp[i] * 1e3:.2f})"
        for i in sorted(range(len(lat)), key=lambda i: -lat[i])[:3])
    _log(f"[bench] {cell['name']} seed={seed} dtype={sut.dtype}: setup "
         f"{ctx.setup_s:.3f} s, pool {items} items, {len(lat)} calls in "
         f"{ctx.window_s:.3f} s (ms min/p10/p50/max {q[0]:.2f} {q[1]:.2f} "
         f"{q[2]:.2f} {q[3]:.2f}; slowest {slowest}), {len(errs)} patterns "
         f"checked, plain {plain_s * 1e3:.4f} ms, {compiles} builds in "
         f"the window, peak {peak} bytes")
    result["checks"] = {k: {"value": _number(checks[k]), "limit": limits[k]}
                        for k in limits}
    return result
