"""Span tracing: nested wall-clock spans, Chrome-trace export, device hookup.

``span("name")`` works as a context manager or decorator and costs two
``perf_counter`` calls plus one small dict append when enabled.  Spans nest
through a per-thread stack, so the recorded events reconstruct the call tree
both in the Chrome trace viewer (Perfetto / ``chrome://tracing`` read the
``traceEvents`` JSON natively) and in :meth:`Tracer.aggregate`, which rolls
them up per name for the bench JSON contract.

When a device profile is active (``Accelerator.profile`` flips
:func:`set_device_trace_active`), every span additionally enters a
``jax.profiler.TraceAnnotation`` so the same names appear on the XPlane/
TensorBoard timeline, lined up against the device stream.  Each flip also
stamps the default tracer, so :meth:`Tracer.capture` hands back the events of
the traced slice: the program's spans and the device trace then cover the
same interval, on clocks joined by the mirrored annotations.

Every event carries an ``id`` and the ``parent`` that was open on its thread
when it began, so a span's self time is its duration less its children's.
:meth:`Tracer.record` stores an interval whose start was stamped elsewhere
(on another thread, say); it is never mirrored into the device trace.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import json
import os
import threading
import time
from typing import Any, Deque, Dict, List, Optional

_DEVICE_TRACE_ACTIVE = False


def set_device_trace_active(active: bool) -> None:
    """Flag a live ``jax.profiler`` capture: spans mirror into TraceAnnotations,
    and the default tracer notes when the capture began and ended."""
    global _DEVICE_TRACE_ACTIVE
    _DEVICE_TRACE_ACTIVE = bool(active)
    _DEFAULT.mark_capture(_DEVICE_TRACE_ACTIVE)


def device_trace_active() -> bool:
    return _DEVICE_TRACE_ACTIVE


class Tracer:
    """Bounded in-memory span recorder.

    ``max_events`` caps the retained Chrome-trace events (FIFO drop, counted in
    ``dropped_events``) so an unbounded training loop cannot grow host memory;
    the per-name aggregate keeps counting regardless.
    """

    def __init__(self, enabled: Optional[bool] = None, max_events: int = 100_000):
        if enabled is None:
            enabled = os.environ.get("ATPU_TELEMETRY", "1").lower() not in ("0", "false", "off")
        self.enabled = enabled
        self.max_events = int(max_events)
        self.dropped_events = 0
        # deque(maxlen=) evicts the oldest event in O(1); the old list-FIFO
        # paid an O(n) ``pop(0)`` under the lock on every span once the ring
        # filled.  Eviction is silent, so the drop counter checks fullness
        # before each append.
        self._events: Deque[Dict[str, Any]] = collections.deque(maxlen=self.max_events)
        self._agg: Dict[str, Dict[str, float]] = {}
        self._local = threading.local()
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()
        self._ids = itertools.count(1)  # next() on it is atomic: no lock at span entry
        # [began, ended] of the last device capture (perf_counter; ended is
        # None while it is on), None before the first
        self._capture: Optional[List[Optional[float]]] = None

    # ------------------------------------------------------------- recording
    def _stack(self) -> List[int]:
        """Ids of the spans open on this thread, outermost first."""
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _append(self, name: str, t0: float, dt: float, span_id: int,
                parent: Optional[int], args: Optional[Dict[str, Any]]) -> None:
        event = {
            "name": name,
            "ph": "X",
            "ts": (t0 - self._epoch) * 1e6,  # Chrome trace wants microseconds
            "dur": dt * 1e6,
            "pid": os.getpid(),
            "tid": threading.get_ident(),
            "id": span_id,
            "parent": parent,
        }
        if args:
            event["args"] = args
        with self._lock:
            if len(self._events) >= self.max_events:
                self.dropped_events += 1
            self._events.append(event)
            agg = self._agg.get(name)
            if agg is None:
                agg = self._agg[name] = {"count": 0, "total_s": 0.0, "max_s": 0.0}
            agg["count"] += 1
            agg["total_s"] += dt
            if dt > agg["max_s"]:
                agg["max_s"] = dt

    @contextlib.contextmanager
    def span(self, name: str, **args: Any):
        """Record one wall-clock span; extra kwargs land in the event's args.

        Yields the args dict, so a count known only at the span's end is set
        on it there (``with span("serve/emit") as a: ...; a["tokens"] = n``).
        A span that belongs to one request passes ``req=<rid>``.  Open spans
        only on the thread that feeds the device: while a capture is on they
        name the device's idle gaps, and a span on another thread would claim
        gaps it did not cause."""
        if not self.enabled:
            yield args
            return
        stack = self._stack()
        depth = len(stack)
        parent = stack[-1] if stack else None
        span_id = next(self._ids)
        stack.append(span_id)
        annotation = None
        if _DEVICE_TRACE_ACTIVE:
            import jax

            annotation = jax.profiler.TraceAnnotation(name)
            annotation.__enter__()
        t0 = time.perf_counter()
        try:
            yield args
        finally:
            dt = time.perf_counter() - t0
            if annotation is not None:
                annotation.__exit__(None, None, None)
            stack.pop()
            self._append(name, t0, dt, span_id, parent,
                         {**args, "depth": depth} if args or depth else None)

    def record(self, name: str, t0: float, t1: float, **args: Any) -> None:
        """Store the interval ``[t0, t1]`` (``time.perf_counter`` readings)
        whose start was stamped elsewhere, on another thread for one.  It has
        no parent and never enters a ``TraceAnnotation``: the gaps of a device
        trace are named by the spans of the thread that feeds the device."""
        if self.enabled:
            self._append(name, t0, t1 - t0, next(self._ids), None, args)

    def mark_capture(self, active: bool) -> None:
        """Note that a device capture began (``True``) or ended (``False``)."""
        now = time.perf_counter()
        with self._lock:
            if active:
                self._capture = [now, None]
            elif self._capture is not None and self._capture[1] is None:
                self._capture[1] = now

    def trace(self, fn=None, *, name: Optional[str] = None):
        """Decorator form: ``@tracer.trace`` or ``@tracer.trace(name="...")``."""
        if fn is None:
            return functools.partial(self.trace, name=name)
        span_name = name or getattr(fn, "__qualname__", getattr(fn, "__name__", "span"))

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            with self.span(span_name):
                return fn(*a, **kw)

        return wrapper

    # --------------------------------------------------------------- exports
    @property
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def capture(self) -> Optional[Dict[str, Any]]:
        """``{"t0", "t1", "events"}`` of the last device capture: when it
        began and ended (microseconds on the events' ``ts`` clock; a capture
        still on ends now) and the retained events that overlap it.  ``None``
        before the first capture."""
        now = time.perf_counter()
        with self._lock:
            if self._capture is None:
                return None
            began, ended = self._capture
            t0 = (began - self._epoch) * 1e6
            t1 = ((now if ended is None else ended) - self._epoch) * 1e6
            events = [e for e in self._events if e["ts"] < t1 and e["ts"] + e["dur"] > t0]
        return {"t0": t0, "t1": t1, "events": events}

    def aggregate(self) -> Dict[str, Dict[str, float]]:
        """Per-name rollup ``{name: {count, total_s, mean_s, max_s}}``."""
        with self._lock:
            return {
                name: {**agg, "mean_s": agg["total_s"] / agg["count"]}
                for name, agg in self._agg.items()
            }

    def chrome_trace(self) -> Dict[str, Any]:
        """The Chrome trace-event JSON object (open in Perfetto / about:tracing)."""
        return {
            "traceEvents": self.events,
            "displayTimeUnit": "ms",
            "otherData": {"dropped_events": self.dropped_events},
        }

    def dump(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path`` and return it."""
        dirname = os.path.dirname(os.path.abspath(path))
        os.makedirs(dirname, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def reset(self) -> None:
        with self._lock:
            self._events.clear()
            self._agg.clear()
            self.dropped_events = 0
            self._epoch = time.perf_counter()
            self._capture = None


_DEFAULT = Tracer()


def get_tracer() -> Tracer:
    """Process-wide default tracer (the one built-in surfaces record into)."""
    return _DEFAULT


def span(name: str, **args: Any):
    """``with telemetry.span("phase"): ...`` on the default tracer."""
    return _DEFAULT.span(name, **args)


def trace(fn=None, *, name: Optional[str] = None):
    """Decorator on the default tracer."""
    return _DEFAULT.trace(fn, name=name)
