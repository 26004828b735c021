"""Host-side span tracer with Chrome-trace / Perfetto JSON export.

The tracer answers "where did the step's wall-clock go" at the *host*
level — data fetch, device dispatch, checkpoint copies, host
gather/scatter in the tiered store, serving batch drains. Spans are
plain ``(name, ts, dur, tid)`` complete events ("ph": "X"), so the
export loads directly in Perfetto / chrome://tracing and nests by
timestamp containment per thread.

Design constraints (DESIGN.md §13):

  * **near-zero overhead when disabled** — ``span()`` on a disabled
    tracer is one attribute check plus returning a shared no-op context
    manager (no allocation, no clock read, no jax import). The <2%
    tracing-off budget is asserted in tests/test_obs.py.
  * **thread-aware** — events carry ``tid`` (``threading.get_ident``),
    so the prefetch producer, the checkpoint writer and the main loop
    land on separate tracks.
  * **on the device trace's clock** — an enabled span also enters
    ``jax.profiler.TraceAnnotation`` (``StepTraceAnnotation`` for a span
    given ``step=``), so a jax profile captured at the same time holds
    every program span on its host plane, beside the device ops (a
    TraceMe costs well under a microsecond when no profiler collects).

Span names reuse the ACT scope grammar (``/``-joined path components,
e.g. ``train/step/data`` — see DESIGN.md §6, §13).
"""

from __future__ import annotations

import json
import os
import threading
import time

__all__ = ["Tracer", "get_tracer", "span"]


class _NullSpan:
    """Shared do-nothing context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


class _Span:
    """One live span: a profiler annotation and a clock read on enter,
    event append on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_mark")

    def __init__(self, tracer: "Tracer", name: str, args: dict | None):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self) -> "_Span":
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        step = self._args.get("step") if self._args else None
        self._mark = (TraceAnnotation(self._name) if step is None
                      else StepTraceAnnotation(self._name, step_num=step))
        self._mark.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._mark.__exit__(*exc)
        tr = self._tracer
        ev = {"name": self._name, "ph": "X", "cat": "host",
              "ts": (self._t0 - tr._epoch) * 1e6,
              "dur": (t1 - self._t0) * 1e6,
              "pid": tr._pid, "tid": threading.get_ident()}
        if self._args:
            ev["args"] = self._args
        with tr._lock:
            tr._events.append(ev)
        return False


class Tracer:
    """Collects host spans; exports the Chrome-trace event list.

    One tracer instance is process-global (``get_tracer()``); tests may
    build private instances. ``enabled`` is the only state the hot path
    reads.
    """

    def __init__(self):
        self.enabled = False
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._pid = os.getpid()
        self._epoch = time.perf_counter()

    # -- lifecycle ----------------------------------------------------------

    def enable(self) -> "Tracer":
        """Start (or restart) collection; clears prior events."""
        with self._lock:
            self._events = []
            self._epoch = time.perf_counter()
        self.enabled = True
        return self

    def disable(self) -> "Tracer":
        self.enabled = False
        return self

    # -- spans --------------------------------------------------------------

    def span(self, name: str, **args):
        """Context manager timing the enclosed block as one complete
        event, under a profiler annotation of the same name; ``step=``
        makes it the profiler's ``StepTraceAnnotation`` for that step.
        Returns a shared no-op when disabled."""
        if not self.enabled:
            return _NULL
        return _Span(self, name, args or None)

    # -- export -------------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome_trace(self, *, run: dict | None = None) -> dict:
        """The Perfetto/chrome://tracing JSON object."""
        return {"traceEvents": self.events(),
                "displayTimeUnit": "ms",
                "metadata": {"tracer": "repro.obs.trace",
                             **(run or {})}}

    def save(self, path: str, *, run: dict | None = None) -> str:
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(run=run), f)
        return path


# -- module-level convenience over the process tracer -----------------------

_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def span(name: str, **args):
    return _TRACER.span(name, **args)

