"""Spans for the serve engine and trainer, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation``: while a profiler
session runs (``jax.profiler.trace`` / ``start_trace``), it lands on the
host plane of the same trace as the device ops, with its attributes as
event stats, so device idle time can be put down to the host work that
was running.  With no session ``NULL.span`` returns a shared no-op
context manager after one check of the profiler's state, so untraced
hot paths allocate nothing (docs/observability.md §Overhead discipline).

    tr = engine.tracer              # trace.NULL unless trace_path is set
    with tr.span("serve.decode", active=n):
        ...

Call sites need no ``if tracing:`` guards.  The profiler trace is the one
on the device clock; read it with ``jax.profiler.ProfileData``.

Library code that has no tracer of its own reads the ambient one via
:func:`current`; owners (``ServeEngine``, ``Trainer``) install theirs for
the duration of a step with :func:`use`.

A :class:`Tracer` also keeps each span in memory in the chrome
trace-event format (``ph="X"`` spans, ``ph="C"`` counters, ``ph="i"``
instants), timed by ``time.perf_counter_ns`` on the host alone, and saves
them as JSON that Perfetto loads.  The cost-model fit
(``benchmarks/fit_costs.py``, ``repro.obs.replay``) reads that file.

Attr values must be JSON-serializable; numpy scalars are coerced on save.
"""
from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import TraceAnnotation

_profiling = TraceAnnotation.is_enabled


class _NullSpan:
    """Shared no-op context manager: a span's cost with no profiler
    session and no recorder."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullTracer:
    """``trace.NULL``: spans go to the profiler alone (a profiler
    annotation while a session runs, else the shared no-op), and nothing
    is kept in memory."""

    enabled = False

    def span(self, name, **attrs):
        if _profiling():
            return TraceAnnotation(name, **attrs)
        return _NULL_SPAN

    def instant(self, name, **attrs):
        pass

    def counter(self, name, **values):
        pass

    def clear(self):
        pass

    def save(self, path=None):
        raise ValueError("NullTracer has nothing to save; construct a "
                         "Tracer(path=...) to capture spans")

    @property
    def events(self):
        return []


NULL = NullTracer()


_perf_ns = time.perf_counter_ns
_ident = threading.get_ident


class _Span:
    """One live span: a profiler annotation that also appends a raw
    ``(name, t0, t1, tid, attrs)`` tuple on exit; the ``X`` (complete)
    event dict is built at save time."""

    __slots__ = ("_events", "_name", "_attrs", "_t0", "_ann")

    def __init__(self, events, name, attrs):
        self._events = events
        self._name = name
        self._attrs = attrs
        self._ann = TraceAnnotation(name, **attrs)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = _perf_ns()
        return self

    def __exit__(self, et, ev, tb):
        self._events.append(
            (self._name, self._t0, _perf_ns(), _ident(), self._attrs))
        self._ann.__exit__(et, ev, tb)
        return False


def _jsonable(v):
    """Coerce numpy scalars/arrays and other strays to JSON types."""
    if isinstance(v, (str, int, float, bool)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    item = getattr(v, "item", None)
    if item is not None and getattr(v, "ndim", 1) == 0:
        return item()
    tolist = getattr(v, "tolist", None)
    if tolist is not None:
        return tolist()
    return str(v)


class Tracer:
    """Chrome-trace event recorder, in memory on the host clock (each
    span is a profiler annotation as well).

    ``path`` is where :meth:`save` writes by default (the owner decides
    when — e.g. ``ServeEngine.run`` saves at trace end).  Events
    accumulate across :meth:`save` calls; :meth:`clear` drops them (the
    serve benchmark replays a trace best-of-N and keeps every replay's
    spans — more samples for the cost fit).
    """

    enabled = True

    def __init__(self, path: str | None = None, *,
                 process_name: str = "repro"):
        self.path = path
        self.pid = os.getpid()
        self._epoch = time.perf_counter_ns()
        self._events: list[dict] = []
        self._meta = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": process_name},
        }]

    # -- recording --------------------------------------------------------
    def span(self, name: str, **attrs) -> _Span:
        """Context manager timing one named span; ``attrs`` become the
        event's ``args`` (shapes, counts — what the cost model fits on)."""
        return _Span(self._events, name, attrs)

    def instant(self, name: str, **attrs) -> None:
        self._events.append({
            "name": name, "ph": "i", "s": "t", "cat": "repro",
            "ts": (time.perf_counter_ns() - self._epoch) / 1e3,
            "pid": self.pid, "tid": threading.get_ident() & 0xFFFFFFFF,
            "args": attrs,
        })

    def counter(self, name: str, **values) -> None:
        """One sample of a counter track (Perfetto draws it as a graph)."""
        self._events.append({
            "name": name, "ph": "C", "cat": "repro",
            "ts": (time.perf_counter_ns() - self._epoch) / 1e3,
            "pid": self.pid, "tid": 0,
            "args": values,
        })

    # -- output -----------------------------------------------------------
    @property
    def events(self) -> list[dict]:
        """Recorded events as chrome-trace dicts (span tuples from the
        hot path are materialized here, off the timed path)."""
        epoch, pid = self._epoch, self.pid
        out = []
        for e in self._events:
            if type(e) is tuple:
                name, t0, t1, tid, attrs = e
                out.append({
                    "name": name, "ph": "X", "cat": "repro",
                    "ts": (t0 - epoch) / 1e3, "dur": (t1 - t0) / 1e3,
                    "pid": pid, "tid": tid & 0xFFFFFFFF, "args": attrs,
                })
            else:
                out.append(e)
        return out

    def clear(self) -> None:
        self._events.clear()

    def save(self, path: str | None = None) -> str:
        """Write ``{"traceEvents": [...]}`` JSON; returns the path."""
        path = path or self.path
        if path is None:
            raise ValueError("no trace path: pass save(path=...) or "
                             "construct Tracer(path=...)")
        payload = {
            "traceEvents": _jsonable(self._meta + self.events),
            "displayTimeUnit": "ms",
        }
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(payload, f)
        os.replace(tmp, path)
        return path


def load(path: str) -> list[dict]:
    """Read a trace file back as its event list (both the ``traceEvents``
    object form this module writes and a bare JSON array)."""
    with open(path) as f:
        data = json.load(f)
    return data["traceEvents"] if isinstance(data, dict) else data


# ---------------------------------------------------------------------------
# ambient tracer (instrumented library code reads, owners install)
# ---------------------------------------------------------------------------

_STACK: list = [NULL]


def current():
    """The ambient tracer — ``NULL`` unless an owner installed one."""
    return _STACK[-1]


class _Use:
    """Context manager installing ``tracer`` as the ambient one."""

    __slots__ = ("_tracer",)

    def __init__(self, tracer):
        self._tracer = tracer

    def __enter__(self):
        _STACK.append(self._tracer)
        return self._tracer

    def __exit__(self, *exc):
        _STACK.pop()
        return False


def use(tracer) -> _Use:
    return _Use(tracer)
