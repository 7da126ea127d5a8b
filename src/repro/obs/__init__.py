"""``repro.obs`` — spans, metrics, stage scopes and exporters for the coded stack.

One process-wide :class:`ObsSession` holds a metrics registry, a span
recorder, and an injectable clock.  Instrumented call sites use the
module-level conveniences (:func:`count`, :func:`observe`, :func:`span`,
:func:`emit_span`) which are near-free no-ops until :func:`enable` is
called — the disabled fast path is one global ``None`` check, so the
instrumented code paths return bit-identical results with observability
off.

Spans stamped from the real clock (the default ``MONOTONIC``) also open a
``jax.profiler.TraceAnnotation`` of the same name, so while a profile is
being recorded they land in its ``.xplane.pb`` on the host's threads, on
the same clock as the device's ops.  Spans on a simulated clock (a
:class:`SettableClock`, as the serve tier installs) and pre-timed
:func:`emit_span` records stay in the recorder only: their seconds are
not the profiler's.

Device work is named by :func:`stage`: a ``jax.named_scope`` from
:data:`STAGES` around each stage of the traced coded pipeline.  It costs
nothing at run time; the name lands in each op's ``op_name`` metadata,
which a profile keeps with each executable's HLO, so a profile's op
events can be attributed to the stage.  An op belongs to the innermost
``coded.*`` scope in its ``op_name``.

Enable programmatically::

    from repro import obs
    obs.enable(fresh=True)
    with obs.span("my.region", kind="demo"):
        ...
    obs.session().registry.total("runtime.executable.compile")

or via the environment: ``REPRO_OBS=1`` enables collection at import
time (used by CI to run the ordinary test suite instrumented).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax

from repro.obs.clock import MONOTONIC, Clock, SettableClock
from repro.obs.metrics import DEFAULT_BUCKETS, MetricsRegistry
from repro.obs.spans import NULL_SPAN, Span, SpanRecorder, span_id_for

__all__ = [
    "ObsSession", "SettableClock", "Span", "SpanRecorder",
    "MetricsRegistry", "DEFAULT_BUCKETS", "span_id_for",
    "enable", "disable", "enabled", "session",
    "count", "gauge", "observe", "span", "emit_span", "use_clock",
    "stage", "STAGES", "ENCODE", "SLICE", "DOTS", "ALLGATHER", "DECODE",
    "EXTRACT", "RECOMPOSE", "CALL", "PANEL", "LAUNCH",
]

# -- names of the coded pipeline's stages (device) and host spans -----------

ENCODE = "coded.encode"        # block decomposition, coefficient sums of A, B
SLICE = "coded.slice"          # Ozaki split of a worker's operands into int8
DOTS = "coded.dots"            # the worker product proper
ALLGATHER = "coded.allgather"  # the exchange of worker products between chips
DECODE = "coded.decode"        # erasure and the decode's weighted sums
EXTRACT = "coded.extract"      # digit extraction or rounding
RECOMPOSE = "coded.recompose"  # layout of C
STAGES = (ENCODE, SLICE, DOTS, ALLGATHER, DECODE, EXTRACT, RECOMPOSE)

CALL = "coded.call"            # one CodedMatmul.__call__
PANEL = "coded.panel"          # its decode-panel lookup and upload
LAUNCH = "coded.launch"        # its executable launch (operand moves included)


def stage(name: str):
    """``jax.named_scope(name)`` for one of :data:`STAGES`."""
    if name not in STAGES:
        raise ValueError(f"unknown stage {name!r}; stages: {STAGES}")
    return jax.named_scope(name)


class ObsSession:
    """One collection session: registry + span recorder + clock."""

    def __init__(self, clock: Clock = MONOTONIC):
        self.registry = MetricsRegistry()
        self.recorder = SpanRecorder(clock)

    @property
    def clock(self) -> Clock:
        """The session's time source (spans stamp from it)."""
        return self.recorder.clock

    @clock.setter
    def clock(self, clock: Clock) -> None:
        """Swap the time source (e.g. a simulated ``SettableClock``)."""
        self.recorder.clock = clock


_session: Optional[ObsSession] = None


def enable(fresh: bool = False, clock: Clock = MONOTONIC) -> ObsSession:
    """Turn collection on, returning the active session.

    ``fresh=True`` discards any previous session (tests and benches use
    this to start from zeroed counters); otherwise an existing session
    keeps accumulating.
    """
    global _session
    if fresh or _session is None:
        _session = ObsSession(clock)
    return _session


def disable() -> None:
    """Turn collection off (instrumented sites become no-ops again)."""
    global _session
    _session = None


def enabled() -> bool:
    """Whether a collection session is active."""
    return _session is not None


def session() -> ObsSession:
    """The active session (raises if observability is disabled)."""
    if _session is None:
        raise RuntimeError(
            "observability is disabled — call repro.obs.enable() first")
    return _session


# -- instrumentation-site conveniences (no-ops while disabled) ---------------

def count(name: str, n: float = 1.0, **labels) -> None:
    """Increment counter ``name`` by ``n`` (no-op while disabled)."""
    if _session is not None:
        _session.registry.counter(name, **labels).inc(n)


def gauge(name: str, value: float, **labels) -> None:
    """Set gauge ``name`` (no-op while disabled)."""
    if _session is not None:
        _session.registry.gauge(name, **labels).set(value)


def observe(name: str, value: float,
            buckets: Optional[Sequence[float]] = None, **labels) -> None:
    """Record ``value`` into histogram ``name`` (no-op while disabled)."""
    if _session is not None:
        _session.registry.histogram(name, buckets=buckets,
                                    **labels).observe(value)


def span(name: str, track: str = "main", lane: str = "main", **attrs):
    """A context manager timing ``name`` (shared no-op while disabled)."""
    if _session is None:
        return NULL_SPAN
    return _session.recorder.span(name, track=track, lane=lane, **attrs)


def emit_span(name: str, start_s: float, end_s: float, track: str = "main",
              lane: str = "main", **attrs) -> Optional[Span]:
    """Record a pre-timed span (no-op while disabled, returning None)."""
    if _session is None:
        return None
    return _session.recorder.emit(name, start_s, end_s, track=track,
                                  lane=lane, **attrs)


def use_clock(clock: Clock) -> None:
    """Point the active session's clock at ``clock`` (no-op if disabled).

    The serve tier calls this with its :class:`SettableClock` so every
    span recorded during the run stamps simulated seconds.
    """
    if _session is not None:
        _session.clock = clock


if os.environ.get("REPRO_OBS", "").strip() not in ("", "0"):
    enable()
