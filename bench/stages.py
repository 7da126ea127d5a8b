"""Split a traced run's calls by the program's own stages and host spans.

Stages.  The program names its device work with ``jax.named_scope`` stages
(``STAGES``), which land in each op's ``op_name`` metadata.  A TPU
profile's op events carry the op's HLO name alone.  The metadata is kept
in each executable's optimized HLO, which XLA holds for every live
executable (``hlo_modules()`` of JAX's ``MeshExecutable``; a TPU profile's
metadata plane holds it too, but only for the modules that ran while it
recorded, and the harness deletes the window's profile once loaded).  So
``programs`` reads, after the window, each live module's ``{op name:
stage}``: the innermost ``coded.*`` scope in the op's ``op_name``, or, for
an op XLA added with no scope of its own (the split of a float64 parameter
into a float32 pair, a copy of loop state), the stage that all of its users
share.  Op names repeat across modules, so each traced op is joined with
the module whose run holds it on the ``XLA Modules`` line; ops outside any
run are joined with the one module that names most of them among those
that ran on the chip (among all where none did).

The split is read on chip 0 unless its record does not name the program's
ops: on the four-chip mesh the profile files most of chip 0's ops under a
bogus module (``region.268435455``) with names like ``region.144``, so the
split is read on the first chip whose ops carry the program's names; every
chip there runs the same program for the same busy time.

Spans.  The program times each call with ``coded.call``, ``coded.panel``
and ``coded.launch`` spans in its ``repro.obs`` session, which the harness
keeps on.  They are stamped on the program's clock; each traced call's
spans are placed on the profiler's clock by the start of the harness's
``bench.dispatch`` annotation around the same call, a few microseconds
before ``coded.call`` opens.

Every function returns None where the program records none of this (a
program without stages or spans), so a reader reports nothing there.
"""
from __future__ import annotations

import bisect
import re
import statistics
from collections import defaultdict

from bench import tracefile

__all__ = ["STAGES", "module_stages", "programs", "stage_ms",
           "traced_calls", "span_ms", "start_wait_ms"]

STAGES = ("coded.encode", "coded.slice", "coded.dots", "coded.allgather",
          "coded.decode", "coded.extract", "coded.recompose")
CALL, LAUNCH = "coded.call", "coded.launch"
DISPATCH = tracefile.HOST_PREFIX + "dispatch"
_SCOPE = re.compile(r"coded\.[a-z]+")


# -- the executables' HLO ----------------------------------------------------

_HLO_OP = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")


def _ops(hlo_text: str) -> list:
    """[(op name, its ``op_name`` metadata, the names it refers to)] of one
    module's HLO text, over all of its computations."""
    out = []
    for op, rest in _HLO_OP.findall(hlo_text):
        op_name = _OP_NAME.search(rest)
        out.append((op, op_name.group(1) if op_name else "", _OPERAND.findall(rest)))
    return out


def module_stages(ops: list) -> dict:
    """{op name: stage} of one module's ``_ops``.  An op's stage is the innermost
    ``STAGES`` scope in its ``op_name``; an op with none takes the stage its
    users share, where they all have one; the rest are left out."""
    stages, users, unscoped = {}, defaultdict(list), []
    for op, op_name, operands in ops:
        found = [s for s in _SCOPE.findall(op_name) if s in STAGES]
        if found:
            stages[op] = found[-1]
        else:
            unscoped.append(op)
        for operand in operands:
            users[operand].append(op)
    while unscoped:
        left = []
        for op in unscoped:
            used_in = {stages[u] for u in users[op] if u in stages}
            if len(used_in) == 1:
                stages[op] = used_in.pop()
            else:
                left.append(op)
        if len(left) == len(unscoped):
            break
        unscoped = left
    return stages


def _live_modules() -> list:
    """[(module name, ``_ops`` of its optimized HLO)] of every executable JAX
    keeps alive."""
    import gc

    from jax._src.interpreters import pxla

    return [(m.name, _ops(m.to_string()))
            for exe in gc.get_objects() if isinstance(exe, pxla.MeshExecutable)
            for m in exe.xla_executable.hlo_modules()]


_memo: dict = {}


def programs(ctx) -> list | None:
    """[(module name, {op name: stage})] of the live executables whose ops
    carry a stage; None where none does.  Read once per traced run."""
    if ctx.trace is None:
        return None
    key = id(ctx.trace)
    if key not in _memo or _memo[key][0] is not ctx.trace:
        mods = [(name, module_stages(ops)) for name, ops in _live_modules()]
        _memo.clear()
        _memo[key] = (ctx.trace, [m for m in mods if m[1]] or None)
    return _memo[key][1]


# -- stage time --------------------------------------------------------------

def _module_name(event_name: str) -> str:
    """``'jit_coded_concrete(6554...)'`` -> ``'jit_coded_concrete'``."""
    return event_name.split("(", 1)[0].strip()


def _best(self_ns: dict, candidates: list) -> dict | None:
    """The stage map that names most of ``self_ns``'s time; None where none
    names any."""
    best, most = None, 0.0
    for stages in candidates:
        named = sum(ns for op, ns in self_ns.items() if op in stages)
        if named > most:
            best, most = stages, named
    return best


def _chip_stages(ctx, device, mods) -> tuple:
    """({stage: ns}, named ns, total ns) of one chip's ops in the traced
    window, each joined with the module whose run holds it."""
    lo, hi = ctx.trace_window
    runs = sorted((e for e in ctx.trace.modules.get(device, ())
                   if e.end > lo and e.start < hi), key=lambda e: e.start)
    starts = [e.start for e in runs]
    groups = defaultdict(list)
    for e in ctx.trace.ops.get(device, ()):
        if not lo <= e.start < hi:
            continue
        i = bisect.bisect_right(starts, e.start) - 1
        groups[i if i >= 0 and e.start < runs[i].end else None].append(e)
    ran = [s for name, s in mods
           if any(_module_name(runs[i].name) == name for i in groups if i is not None)]
    by_stage, named, total = defaultdict(float), 0.0, 0.0
    for i, events in groups.items():
        self_ns = tracefile.self_times(events)
        total += sum(self_ns.values())
        same = ran if i is None else [
            s for name, s in mods if name == _module_name(runs[i].name)]
        stages = _best(self_ns, same or [s for _, s in mods])
        for op, ns in self_ns.items():
            if stages is not None and op in stages:
                by_stage[stages[op]] += ns
                named += ns
    return by_stage, named, total


def stage_ms(ctx, stage: str) -> float | None:
    """Milliseconds per traced call of one chip's ops in ``stage``, by self
    time (``tracefile.self_times``: a loop op counts only where no op inside
    it runs); 0 where the program ran and no op of it is in ``stage``."""
    if ctx.trace is None or not ctx.trace_calls:
        return None
    mods = programs(ctx)
    if not mods:
        return None
    chips = [_chip_stages(ctx, d, mods) for d in ctx.trace_devices]
    best = max((named / total if total else 0.0) for _, named, total in chips)
    if not best:
        return None
    by_stage = next(s for s, named, total in chips
                    if total and named / total >= best - 0.01)
    return by_stage.get(stage, 0.0) / 1e6 / ctx.trace_calls


# -- host spans --------------------------------------------------------------

def traced_calls(ctx) -> list | None:
    """Per traced call in the window: (its ``bench.dispatch`` event, its
    ``coded.call`` span, {child span name: span}); None without spans."""
    from repro import obs

    if ctx.trace is None or not obs.enabled():
        return None
    spans = obs.session().recorder.spans
    calls = [s for s in spans if s.name == CALL][-len(ctx.latencies_s):]
    dispatches = sorted((e for e in ctx.trace.host if e.name == DISPATCH),
                        key=lambda e: e.start)
    if not calls or len(dispatches) > len(calls):
        return None
    by_call = {c.sid: (d, c, {}) for d, c in zip(dispatches, calls)}
    for s in spans:
        if s.parent in by_call:
            by_call[s.parent][2][s.name] = s
    lo, hi = ctx.trace_window
    return [v for v in by_call.values() if lo <= v[0].start < hi]


def span_ms(ctx, name: str) -> float | None:
    """Mean milliseconds of the traced calls' child span ``name``."""
    calls = traced_calls(ctx)
    durations = [kids[name].duration_s for _, _, kids in calls or ()
                 if name in kids]
    return 1e3 * statistics.fmean(durations) if durations else None


def start_wait_ms(ctx) -> float | None:
    """Mean over the traced calls of the time from the start of the call's
    ``coded.launch`` span to the first op after it, on the chip where that
    op comes last: with one call in flight, the first op of the module run
    the launch started."""
    calls = traced_calls(ctx)
    if not calls:
        return None
    lo, hi = ctx.trace_window
    starts = {d: sorted(e.start for e in ctx.trace.device_ops(d) if lo <= e.start < hi)
              for d in ctx.trace_devices}
    waits = []
    for dispatch, call, kids in calls:
        launch = kids.get(LAUNCH)
        if launch is None:
            continue
        t0 = dispatch.start + (launch.start_s - call.start_s) * 1e9
        firsts = [next((t for t in starts[d] if t >= t0), None)
                  for d in ctx.trace_devices]
        if None not in firsts:
            waits.append(max(firsts) - t0)
    return statistics.fmean(waits) / 1e6 if waits else None
