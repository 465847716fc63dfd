"""Flight recorder: process-wide metrics registry + span tracer for the
EGRL loop and the placement service.  Dependency-free (stdlib only;
never imports jax — see the profiler bridge below).

Mode (``REPRO_OBS``, parsed fail-loud via utils/envpolicy.py):

- ``off``  (default) — spans are the shared no-op singleton: no event,
  no allocation, no clock read.  METRICS stay live (plain int adds) so
  ``PlacementService.stats()`` and the bench summaries — which are
  rebased on obs counters — are correct in every mode.
- ``mem``  — events stream into a bounded in-memory ring
  (``drain()`` / ``events()``).
- ``jsonl`` — the ring PLUS an append-mode, flush-per-event JSONL file
  at ``REPRO_OBS_PATH`` (default ``obs_trace.jsonl``), consumed by
  tools/trace_report.py.

Profiler bridge, in every mode: while a ``jax.profiler`` session
records (``jax.profiler.trace`` / ``start_trace``), each span also
enters a ``jax.profiler.TraceAnnotation`` named ``obs/<name>``, so the
program's spans sit in the device trace on the device events' clock.
Off mode with no session recording still hands back ``NOOP_SPAN``, at
the cost of the mode check plus one ``TraceAnnotation.is_enabled()``.
The annotation type is looked up only once jax is already imported:
without jax no profiler can be recording.

Span taxonomy and event schema: docs/observability.md.

Usage::

    from repro import obs
    with obs.span("evolve", n_class=256) as sp:
        ...
        sp.set(generations=4)
    obs.counter("hits").inc()
    obs.histogram("wall_ms", path="hit").observe(3.2)

Tests and benches swap state explicitly: ``override(mode=..., path=...,
clock=...)`` is a context manager restoring the previous state (the
bench_serve overhead A/B uses it to alternate off/jsonl on one warmed
service); ``configure`` rebuilds in place; ``reset`` drops back to the
environment policy.
"""
from __future__ import annotations

import os
import sys
from contextlib import contextmanager
from typing import Callable, List, Optional

from repro.obs.log import Logger, get_logger, set_quiet          # noqa: F401
from repro.obs.metrics import (Counter, Gauge, Histogram,        # noqa: F401
                               MetricsRegistry, log_edges)
from repro.obs.trace import (NOOP_SPAN, JsonlSink,              # noqa: F401
                             ProfilerSpan, RingSink, Span, Tracer)
from repro.utils.envpolicy import env_policy

DEFAULT_PATH = "obs_trace.jsonl"
MODES = ("off", "mem", "jsonl")


class ObsState:
    """One (mode, sinks, tracer) configuration.  Swapped wholesale by
    configure/override/reset so a mode change can never leave a stale
    sink list behind."""

    def __init__(self, mode: str, path: str,
                 clock: Optional[Callable[[], float]] = None,
                 ring_size: int = 16384):
        self.mode = mode
        self.path = path
        self.ring = RingSink(ring_size)
        self.jsonl: Optional[JsonlSink] = None
        sinks = [self.ring]
        if mode == "jsonl":
            self.jsonl = JsonlSink(path)
            sinks.append(self.jsonl)
        self.tracer = Tracer(sinks) if clock is None else Tracer(sinks, clock)

    def close(self) -> None:
        if self.jsonl is not None:
            self.jsonl.close()


_STATE: Optional[ObsState] = None
# process-wide metrics: ALWAYS live, independent of the trace mode (see
# the module docstring); components needing isolated series (each
# PlacementService) hold their own MetricsRegistry
_REGISTRY = MetricsRegistry()


def _state() -> ObsState:
    global _STATE
    if _STATE is None:
        m = env_policy("REPRO_OBS", choices=MODES, default="off")
        _STATE = ObsState(m, os.environ.get("REPRO_OBS_PATH", DEFAULT_PATH))
    return _STATE


def configure(mode: Optional[str] = None, path: Optional[str] = None,
              clock: Optional[Callable[[], float]] = None) -> ObsState:
    """Rebuild the global obs state with explicit values (unspecified
    fields keep their current resolution).  Closes the previous JSONL
    sink; the ring starts empty."""
    global _STATE
    cur = _state()
    cur.close()
    _STATE = ObsState(mode if mode is not None else cur.mode,
                      path if path is not None else cur.path, clock)
    return _STATE


def reset() -> ObsState:
    """Drop the state and re-read ``REPRO_OBS`` / ``REPRO_OBS_PATH``
    from the environment (fail-loud immediately on a bad value)."""
    global _STATE
    if _STATE is not None:
        _STATE.close()
    _STATE = None
    return _state()


@contextmanager
def override(mode: Optional[str] = None, path: Optional[str] = None,
             clock: Optional[Callable[[], float]] = None):
    """Temporarily swap mode/path/clock; the previous state (and its
    still-open sinks) is restored on exit, the temporary one closed."""
    global _STATE
    prev = _state()
    tmp = ObsState(mode if mode is not None else prev.mode,
                   path if path is not None else prev.path, clock)
    _STATE = tmp
    try:
        yield tmp
    finally:
        tmp.close()
        _STATE = prev


def mode() -> str:
    return _state().mode


def enabled() -> bool:
    return _state().mode != "off"


def _recording():
    """``jax.profiler.TraceAnnotation`` while a profiler session records,
    else None (and None while jax is not imported)."""
    ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    return ann if ann is not None and ann.is_enabled() else None


def span(name: str, **attrs):
    """A context-manager span, also written into the profiler's trace as
    ``obs/<name>`` while a session records; the no-op singleton when
    tracing is off and no session records."""
    st = _state()
    ann = _recording()
    if ann is not None:
        ann = ann("obs/" + name, **attrs)
    if st.mode == "off":
        return NOOP_SPAN if ann is None else ProfilerSpan(ann)
    sp = st.tracer.span(name, **attrs)
    sp._ann = ann
    return sp


def device_read(fetch: Callable, x):
    """``fetch(x)`` for one blocking device-to-host read (``np.asarray``,
    ``float``) of a generation: counted in ``egrl.device_reads`` and
    spanned as ``device_read``.  Returns ``fetch(x)`` unchanged."""
    _REGISTRY.counter("egrl.device_reads").inc()
    with span("device_read"):
        return fetch(x)


def emit_event(event: dict) -> None:
    """Emit a non-span event (log lines, metrics snapshots) into the
    current sinks; dropped silently when off."""
    st = _state()
    if st.mode == "off":
        return
    event.setdefault("ts", round(st.tracer.now(), 6))
    st.tracer.emit(event)


def drain() -> List[dict]:
    """Empty and return the in-memory ring."""
    return _state().ring.drain()


def events() -> List[dict]:
    """Peek the in-memory ring without draining."""
    return _state().ring.peek()


def registry() -> MetricsRegistry:
    return _REGISTRY


def counter(name: str, **labels) -> Counter:
    return _REGISTRY.counter(name, **labels)


def gauge(name: str, **labels) -> Gauge:
    return _REGISTRY.gauge(name, **labels)


def histogram(name: str, edges=None, **labels) -> Histogram:
    return _REGISTRY.histogram(name, edges=edges, **labels)


def emit_metrics(reg: Optional[MetricsRegistry] = None) -> None:
    """Emit a ``metrics`` snapshot event of ``reg`` (default: the
    process-wide registry); no-op when off."""
    emit_event({"type": "metrics",
                "snapshot": (reg if reg is not None else _REGISTRY).snapshot()})
