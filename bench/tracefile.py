"""Reduce a JAX profiler trace to the benchmark's device numbers.

``load`` reads an ``.xplane.pb`` into a ``Trace``: per device, the events of
its ``XLA Ops`` line (one event per HLO op, a loop's event enclosing its
body's), of its ``Async XLA Ops`` line (copies and collectives that run
beside them) and of its ``XLA Modules`` line (one event per executable
run), and the harness's own host annotations (names starting with
``bench.``).  Host and device events share the profiler's clock, in
nanoseconds.  Everything below works on that plain form, so the tests can
build a ``Trace`` by hand.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path

__all__ = [
    "Event", "Trace", "load", "load_dir", "short_name", "union", "window", "covered",
    "calls_in", "busy_ns",
    "idle_gaps", "gap_label", "module_ns", "matching_ns", "self_times",
    "breakdown",
]

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
_HLO_NAME = re.compile(r"^%?([\w.\-]+)")


@dataclasses.dataclass(frozen=True)
class Event:
    """One traced interval, [start, end) in nanoseconds."""

    name: str
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    """A trace reduced to what the benchmark reads."""

    ops: dict          # device id -> [Event] of the op line
    async_ops: dict    # device id -> [Event] of the async op line
    modules: dict      # device id -> [Event] of the module line
    host: list         # [Event] of the harness's annotations

    def device_ops(self, device: int) -> list:
        """Every op event of ``device``, synchronous and async."""
        return [*self.ops.get(device, ()), *self.async_ops.get(device, ())]


def load(path) -> Trace:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    lines = {OP_LINE: defaultdict(list), ASYNC_LINE: defaultdict(list),
             MODULE_LINE: defaultdict(list)}
    host = []
    for plane in ProfileData.from_file(str(path)).planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in lines:
                dest = lines[line.name][int(m.group(1))]
            elif not m and plane.name.startswith("/host"):
                dest = None
            else:
                continue
            for e in line.events:
                if dest is None:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, e.start_ns, e.end_ns))
                else:
                    dest.append(Event(e.name, e.start_ns, e.end_ns))
    return Trace(*(dict(lines[k]) for k in (OP_LINE, ASYNC_LINE, MODULE_LINE)),
                 host)


def load_dir(directory) -> Trace:
    """``load`` the newest ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``directory``."""
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return load(found[-1])


def short_name(hlo: str) -> str:
    """``'%while.57 = (u32[] ...'`` -> ``'while.57'``."""
    m = _HLO_NAME.match(hlo)
    return m.group(1) if m else hlo


def union(events, lo: float, hi: float) -> list:
    """Merged [start, end) intervals of ``events`` clipped to [lo, hi)."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    out = []
    for s, e in spans:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        elif e > s:
            out.append([s, e])
    return [tuple(x) for x in out]


def window(trace: Trace) -> tuple:
    """[lo, hi) of the harness's ``bench.window`` annotation.

    Without one, the span of every device event stands in."""
    marks = [e for e in trace.host if e.name == WINDOW]
    if marks:
        return marks[0].start, marks[0].end
    every = [e for evs in trace.ops.values() for e in evs]
    if not every:
        raise ValueError("the trace holds no device event")
    return min(e.start for e in every), max(e.end for e in every)


def covered(trace: Trace, devices, lo: float, hi: float) -> tuple:
    """[lo, hi') of the window that every device's ops cover.

    The profiler can drop a chip's trace buffers once they fill: on a
    four-chip mesh the chip that holds the inputs stopped recording after
    about a second, though its work went on.  A device whose ops end early
    ends the window there, so no chip's missing record reads as idle time.
    """
    for d in devices:
        evs = trace.device_ops(d)
        if evs:
            hi = min(hi, max(e.end for e in evs))
    return lo, hi


def calls_in(trace: Trace, lo: float, hi: float) -> int:
    """Calls the harness dispatched in [lo, hi)."""
    return sum(1 for e in trace.host
               if e.name == HOST_PREFIX + "dispatch" and lo <= e.start < hi)


def busy_ns(trace: Trace, device: int, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi) in which some op ran on ``device``."""
    return sum(e - s for s, e in union(trace.device_ops(device), lo, hi))


def idle_gaps(trace: Trace, device: int, lo: float, hi: float) -> list:
    """The [start, end) stretches of [lo, hi) with no op on ``device``."""
    gaps, at = [], lo
    for s, e in union(trace.device_ops(device), lo, hi):
        if s > at:
            gaps.append((at, s))
        at = e
    if hi > at:
        gaps.append((at, hi))
    return gaps


def gap_label(gap: tuple, host) -> str:
    """What the harness was doing in ``gap``: the annotation that overlaps it
    most (``bench.dispatch`` -> ``dispatch``), else ``harness``."""
    best, label = 0.0, "harness"
    for e in host:
        if e.name == WINDOW:
            continue
        overlap = min(e.end, gap[1]) - max(e.start, gap[0])
        if overlap > best:
            best, label = overlap, e.name[len(HOST_PREFIX):]
    return label


def module_ns(trace: Trace, device: int, prefix: str) -> tuple:
    """(total nanoseconds, runs) of the executables named ``prefix...``."""
    runs = [e for e in trace.modules.get(device, ()) if e.name.startswith(prefix)]
    return sum(e.end - e.start for e in runs), len(runs)


def matching_ns(trace: Trace, device: int, pattern: str, lo: float,
                hi: float) -> float:
    """Nanoseconds of [lo, hi) covered by ops whose name holds ``pattern``."""
    hits = [e for e in trace.device_ops(device)
            if pattern in short_name(e.name)]
    return sum(e - s for s, e in union(hits, lo, hi))


def self_times(events) -> dict:
    """Per op name, the time its events ran outside their nested children.

    An enclosing op (a loop) gets only the time that no op inside it covers,
    so summing over names counts no nanosecond twice.
    """
    out = defaultdict(float)
    stack = []                                   # [event, time in children]

    def close(item):
        e, inner = item
        out[short_name(e.name)] += (e.end - e.start) - inner

    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and e.start >= stack[-1][0].end:
            close(stack.pop())
        if stack:
            stack[-1][1] += min(e.end, stack[-1][0].end) - e.start
        stack.append([e, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def breakdown(trace: Trace, device: int, lo: float, hi: float,
              top: int = 10) -> dict:
    """The ops that took most self time on ``device`` in [lo, hi), and the
    longest idle gaps there labelled by what the harness was doing; seconds."""
    sync = [e for e in trace.ops.get(device, ()) if lo <= e.start < hi]
    ops = sorted(self_times(sync).items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(trace, device, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[gap_label(g, trace.host), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top]],
    }
