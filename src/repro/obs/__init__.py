"""Unified telemetry for the train and serve hot paths.

One small layer, three pieces:

  * :mod:`repro.obs.registry` — process-local metrics primitives:
    counters, gauges, rolling-window histograms, and THE quantile
    codepath (``quantile``) every p50/p95/p99 in the repo goes through
    (``serve.engine.latency_stats``, the guard rails' rolling loss
    median, the sink rollups).
  * :mod:`repro.obs.sink` — a buffered streaming JSONL event sink with
    a run-metadata header and size-based rotation; ``--metrics-dir`` on
    the launchers installs one process-wide, and every emitter below
    writes through it.
  * :mod:`repro.obs.trace` / :mod:`repro.obs.audit` — plan-stage
    tracing: the executor names every plan-IR stage
    (``jax.named_scope``), the timed harness measures per-stage wall
    times (prefix-program differencing — the full-plan program is
    untouched, so outputs stay bitwise-identical), and the audit joins
    them against ``PerfModel.t_plan_stages`` predictions into a
    predicted-vs-measured report (``launch/dryrun.py --audit``).

Set-up phases (:func:`phase`) time the host work before training —
building the state, lowering and compiling the step, a checkpoint save
— on the host clock, keep the last ``PHASES_MAX`` of them in memory
(:func:`phases`), and mark each on the profiler's host plane with a
``jax.profiler.TraceAnnotation`` of the same name.

Emission is opt-in and cheap when off: ``emit(...)`` with no sink
installed is a single attribute test, and nothing here runs inside a
jitted program — runtime events arrive through the same host-side
seams the launchers already owned (per-step logging, engine lifecycle
transitions, ``jax.debug.callback`` for the fp8 monitor).

Two context planes keep events attributable:

  * runtime context (:func:`set_context`) — host-side facts like the
    current train step, merged into every event at emit time;
  * trace context (:func:`trace_tag` / :func:`trace_context`) — facts
    only known while *tracing* (e.g. which MoE layer an fp8 encode
    belongs to), captured into the debug-callback closure so runtime
    events from that trace carry them.
"""

from __future__ import annotations

import collections
import time
from contextlib import contextmanager
from typing import NamedTuple, Optional

import jax

from repro.obs.registry import (Counter, Gauge, Histogram,  # noqa: F401
                                Registry, quantile)
from repro.obs.sink import JsonlSink  # noqa: F401

_SINK = None            # process-wide JsonlSink (None = telemetry off)
_RUNTIME_CTX: dict = {}  # host-side event context (e.g. step=)
_TRACE_CTX: dict = {}    # trace-time context (e.g. moe_layer=)
PHASES_MAX = 1024        # phases kept in memory, newest last
_PHASES: collections.deque = collections.deque(maxlen=PHASES_MAX)
_OPEN_PHASES: list = []  # names of the phases entered and not yet left


def configure(metrics_dir: str, meta=None, **sink_kw) -> JsonlSink:
    """Install a process-wide JSONL sink writing under ``metrics_dir``.
    Returns it (also reachable via :func:`get_sink`)."""
    global _SINK
    if _SINK is not None:
        _SINK.close()
    _SINK = JsonlSink(metrics_dir, meta=meta, **sink_kw)
    return _SINK


def get_sink():
    return _SINK


def enabled() -> bool:
    return _SINK is not None


def emit(event: str, **fields) -> None:
    """Write one event through the installed sink (no-op when none is
    installed).  The runtime context is merged in under the event's own
    fields (explicit fields win)."""
    if _SINK is None:
        return
    if _RUNTIME_CTX:
        merged = dict(_RUNTIME_CTX)
        merged.update(fields)
        fields = merged
    _SINK.emit(event, **fields)


def flush() -> None:
    if _SINK is not None:
        _SINK.flush()


def close() -> None:
    """Flush and close the installed sink (idempotent)."""
    global _SINK
    if _SINK is not None:
        _SINK.close()
        _SINK = None
    _RUNTIME_CTX.clear()
    _TRACE_CTX.clear()


def set_context(**fields) -> None:
    """Merge host-side context (e.g. ``step=12``) into every subsequent
    :func:`emit`.  A value of None removes the key."""
    for k, v in fields.items():
        if v is None:
            _RUNTIME_CTX.pop(k, None)
        else:
            _RUNTIME_CTX[k] = v


def trace_context() -> dict:
    """Snapshot of the trace-time context (copy; safe to close over)."""
    return dict(_TRACE_CTX)


@contextmanager
def trace_tag(**fields):
    """Tag everything traced inside the block (e.g. ``moe_layer=3``) so
    runtime callbacks built there can stamp their events with it."""
    saved = {k: _TRACE_CTX.get(k) for k in fields}
    _TRACE_CTX.update(fields)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                _TRACE_CTX.pop(k, None)
            else:
                _TRACE_CTX[k] = v


class Phase(NamedTuple):
    """One finished :func:`phase`: ``time.perf_counter_ns`` at entry and
    exit, and the name of the phase it ran inside (None at top level)."""
    name: str
    start_ns: int
    end_ns: int
    parent: Optional[str]


@contextmanager
def phase(name: str, **args):
    """Time a block of host work as the phase ``name``: a
    ``jax.profiler.TraceAnnotation`` while it runs, a :class:`Phase` in
    :func:`phases` when it ends, and a ``phase`` event (with ``args``)
    through the sink when one is installed.  Adds no synchronisation:
    device work the block starts is timed by whatever waits for it."""
    parent = _OPEN_PHASES[-1] if _OPEN_PHASES else None
    _OPEN_PHASES.append(name)
    start = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(name, **args):
            yield
    finally:
        end = time.perf_counter_ns()
        _OPEN_PHASES.pop()
        _PHASES.append(Phase(name, start, end, parent))
        emit("phase", name=name, seconds=(end - start) / 1e9,
             parent=parent, **args)


def phases() -> list:
    """The finished phases, oldest first (at most ``PHASES_MAX``)."""
    return list(_PHASES)
