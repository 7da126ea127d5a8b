"""One run of one cell: set-up, the measured window, and the check.

Set-up, all of it in ``setup_s``: build the facade, make the operand pool
on the device from the seed, factor the decode panel of every erasure
pattern the traffic can draw, warm the one-shot executable, grow the pool
to fill the chip beside the program's peak, and time the plain product.  A
traced run also warms and traces the facade's split stages, which the
window itself never calls.

The window is a closed loop with one call in flight: take the next erasure
pattern, call ``cm(A, B, erased=...)``, block until the result is ready.
Patterns come as a deck shuffled from the seed, every pattern once a round,
and the window lasts at least one round.  Operand pairs cycle through the
pool.  One output of each pattern, drawn from the seed among that
pattern's calls, is kept for the check.  A traced run traces the window's
first ``TRACE_CALLS`` calls and no more.

After the window the peak device memory is read, the pool is freed, and
every kept output is compared with the plain reference on every device that
holds a copy of it.  Then each metric the cell reports is read by its
reader, from a ``Context``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import gc
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, system, tracefile
from bench.counters import CompileWatch
from bench.spec import load_reader

__all__ = ["Context", "LIMITS", "run_cell", "make_pairs", "peak_bytes", "pool_room"]

WARM_CALLS = 2          # the first call compiles or loads from the cache
POOL_MIN = 2            # operand pairs made before the warm-up
POOL_FILL = 0.8         # the pool grows until the chip's peak would reach this share
PLAIN_REPS = 5          # the plain product's time is the median of these
PLAIN_BATCH_S = 0.3     # each a batch this long: the host clock errs by ~0.5 ms
PLAIN_IN_FLIGHT = 3
STAGE_CALLS = 3
TRACE_CALLS = 12        # calls of the window a traced run traces
STAGES = {"worker": "jit_bench_worker_stage", "decode": "jit_bench_decode_stage"}
# Exact: every entry of the decoded C equals A^T B.  max_abs_err is the
# largest |C - A^T B| over the kept outputs and every copy of them.
LIMITS = {"max_abs_err": 0.0}


@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) reads."""

    setup_s: float
    window_s: float
    latencies_s: list       # per call: dispatch to ready
    dispatch_s: list        # per call: entering cm(...) to its return
    plain_s: float          # one plain product, median
    peak_bytes: int         # peak_bytes() of the fullest chip
    compiles_in_window: int
    shape: tuple            # (v, r, t)
    peaks: dict | None
    trace: tracefile.Trace | None = None
    trace_devices: list = dataclasses.field(default_factory=list)
    trace_window: tuple = ()     # (lo, hi) ns that every chip's trace covers
    trace_calls: int = 0         # calls dispatched in trace_window
    stages: dict | None = None   # stage -> device ns per call


@dataclasses.dataclass
class Pair:
    """One operand pair, as the program gets it and as the reference does."""

    A: object
    B: object
    A8: object
    B8: object


def _log(*args) -> None:
    print(*args, file=sys.stderr, flush=True)


@functools.partial(jax.jit, static_argnames=("v", "r", "t", "lo", "hi"))
def bench_operands(key, i, *, v, r, t, lo, hi):
    """Pair ``i`` of the pool drawn from ``key``: A (v, r) and B (v, t) with
    integer entries in [lo, hi], as float64 and as int8."""
    ka, kb = jax.random.split(jax.random.fold_in(key, i))
    a = jax.random.randint(ka, (v, r), lo, hi + 1, jnp.int32)
    b = jax.random.randint(kb, (v, t), lo, hi + 1, jnp.int32)
    return (a.astype(jnp.float64), b.astype(jnp.float64),
            a.astype(jnp.int8), b.astype(jnp.int8))


def make_pairs(config: dict, seq: np.random.SeedSequence, first: int,
               count: int) -> list:
    """Pairs ``first .. first + count - 1`` of the pool drawn from ``seq``,
    made on the default device: float64 for the program, int8 for the
    reference.  A pair's entries depend on the seed and its index alone."""
    key = jax.random.wrap_key_data(jnp.asarray(seq.generate_state(2, np.uint32)))
    shape = {k: config[k] for k in ("v", "r", "t")}
    return [Pair(*jax.block_until_ready(bench_operands(
                key, i, lo=config["entry_min"], hi=config["entry_max"], **shape)))
            for i in range(first, first + count)]


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


def _time_plain(pair: Pair) -> float:
    """Seconds of one plain product: the median over ``PLAIN_REPS`` batches,
    each a run of back-to-back calls with a few in flight."""
    jax.block_until_ready(reference.bench_plain(pair.A8, pair.B8))
    t0 = time.perf_counter()
    jax.block_until_ready(reference.bench_plain(pair.A8, pair.B8))
    n = max(PLAIN_IN_FLIGHT, math.ceil(PLAIN_BATCH_S / (time.perf_counter() - t0)))
    means = []
    for _ in range(PLAIN_REPS):
        flight = collections.deque()
        t0 = time.perf_counter()
        for _ in range(n):
            flight.append(reference.bench_plain(pair.A8, pair.B8))
            if len(flight) > PLAIN_IN_FLIGHT:
                flight.popleft().block_until_ready()
        jax.block_until_ready(list(flight))
        means.append((time.perf_counter() - t0) / n)
    return statistics.median(means)


def _xplane(directory: str) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return found[-1]


def _trace_stages(sut, pair: Pair, device_id: int) -> dict | None:
    """Device ns per call of the facade's split stages, each under a jit of
    the harness's naming; None where the backend has no split seam."""
    cm, (_, r, t) = sut.cm, sut.shape
    erased = list(sut.patterns[0])

    def bench_worker_stage(A, B):
        return cm.worker_stage(A, B)

    def bench_decode_stage(Y):
        return cm.decode_stage(Y, (r, t), erased=erased)

    worker, decode = jax.jit(bench_worker_stage), jax.jit(bench_decode_stage)
    try:
        jax.block_until_ready(decode(worker(pair.A, pair.B)))
    except NotImplementedError:
        return None
    with tempfile.TemporaryDirectory(prefix="bench-stages-") as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(STAGE_CALLS):
            jax.block_until_ready(decode(jax.block_until_ready(worker(pair.A, pair.B))))
        jax.profiler.stop_trace()
        trace = tracefile.load(_xplane(tmp))
    out = {}
    for stage, prefix in STAGES.items():
        ns, runs = tracefile.module_ns(trace, device_id, prefix)
        if runs:
            out[stage] = ns / runs
    return out


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
    dispatch times, kept outputs as {pattern: (C, pair index)})."""
    starts, lat, disp, kept, seen = [], [], [], {}, collections.Counter()

    def call(annotate):
        erased, k = next(deck), len(lat) % len(pool)
        t0 = time.perf_counter()
        with annotate("bench.dispatch"):
            C = sut.cm(pool[k].A, pool[k].B, erased=list(erased))
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


def _check(kept: dict, pool8: list, rt: tuple) -> list:
    """max |C - A^T B| of each kept output over every copy of it."""
    errs = []
    for C, k in kept.values():
        A8, B8 = pool8[k]
        ref = reference.bench_plain(A8, B8)
        worst = math.inf if tuple(C.shape) != tuple(rt) else 0.0
        for shard in C.addressable_shards if worst == 0.0 else ():
            e = float(reference.max_abs_err(
                shard.data, jax.device_put(ref[shard.index], shard.device)))
            worst = math.inf if math.isnan(e) else max(worst, e)
        errs.append(worst)
    return errs


def _number(x: float):
    return x if math.isfinite(x) else str(x)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             devices: list, t_start: float, peaks: dict | None = None,
             dtype=None, sut=None) -> dict:
    """One run of ``cell`` (``spec.cell_spec``); returns the result line.

    ``dtype`` runs the program at another precision (the control), and
    ``sut`` reuses a built ``system.System`` across runs in one process.
    """
    config, traffic = cell["config"], cell["traffic"]
    reference.check_range(config["v"], config["entry_min"], config["entry_max"])
    if sut is None:
        sut = system.build(config, traffic, devices, dtype)
    watch = CompileWatch()
    data_seq, draw_seq, keep_seq = np.random.SeedSequence(seed % 2 ** 64).spawn(3)
    pool = make_pairs(config, data_seq, 0, POOL_MIN)
    for erased in sut.patterns:
        mask = np.ones(sut.K)
        mask[list(erased)] = 0.0
        sut.cm.panel_cache.get(mask)
    for i in range(WARM_CALLS):
        jax.block_until_ready(sut.cm(
            pool[i % len(pool)].A, pool[i % len(pool)].B,
            erased=list(sut.patterns[i % len(sut.patterns)])))
    plain_s = _time_plain(pool[0])
    stages = _trace_stages(sut, pool[0], devices[0].id) if trace else None
    # every executable of the run has reserved its temporaries by now
    v, r, t = sut.shape
    kept_bytes = len(sut.patterns) * r * t * sut.dtype.itemsize
    room = pool_room(devices[0].memory_stats(), kept_bytes)
    pool += make_pairs(config, data_seq, len(pool), room // (v * (r + t) * 9))

    tmp = tempfile.TemporaryDirectory(prefix="bench-window-") if trace else None
    watch.mark()
    t_first, t_end, starts, lat, disp, kept = _window(
        sut, pool, seconds, _deck(sut.patterns, np.random.default_rng(draw_seq)),
        np.random.default_rng(keep_seq), tmp and tmp.name)
    peak = max(peak_bytes(d.memory_stats()) for d in devices)
    compiles = watch.delta()
    trace_read = None
    if trace:
        trace_read = tracefile.load(_xplane(tmp.name))
        tmp.cleanup()

    pool8 = [(p.A8, p.B8) for p in pool]
    pairs = len(pool)
    del pool
    gc.collect()
    errs = _check(kept, pool8, sut.shape[1:])
    del kept
    checks = {"max_abs_err": max(errs)}
    correct = all(checks[k] <= LIMITS[k] for k in LIMITS)

    ctx = Context(
        setup_s=t_first - t_start, window_s=t_end - t_first, latencies_s=lat,
        dispatch_s=disp, plain_s=plain_s, peak_bytes=peak,
        compiles_in_window=compiles, shape=sut.shape, peaks=peaks,
        trace=trace_read, trace_devices=[d.id for d in devices],
        stages=stages)
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
              "failed": sum(e > LIMITS["max_abs_err"] for e in errs),
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
         f"{ctx.setup_s:.3f} s, pool {pairs} pairs, {len(lat)} calls in "
         f"{ctx.window_s:.3f} s (ms min/p10/p50/max {q[0]:.2f} {q[1]:.2f} "
         f"{q[2]:.2f} {q[3]:.2f}; slowest {slowest}), {len(errs)} patterns "
         f"checked, plain product {plain_s * 1e3:.4f} ms, {compiles} builds in "
         f"the window, peak {peak} bytes")
    result["checks"] = {k: {"value": _number(checks[k]), "limit": LIMITS[k]}
                        for k in LIMITS}
    return result
