"""Lightweight tracing: spans and regions -> Chrome/Perfetto trace-event
JSON and the JAX profiler's trace.

Two entry points:

* ``span(name, **attrs)`` -- an opt-in span.  Disabled (the default) it
  returns the shared ``NULL_SPAN``: no event, no timestamp, no
  allocation.  Enabled it writes a JSON complete event and also opens a
  ``jax.profiler.TraceAnnotation``, so the span lands in any running
  profiler trace on the device trace's clock.
* ``region(name)`` -- for the few host boundaries around device work
  (the serving engine's build and step phases).  It is *always on*,
  whether or not tracing is enabled: it opens a ``TraceAnnotation``
  (recorded only while a profiler session runs; about half a
  microsecond otherwise) and adds its duration to the fixed-edge
  histogram ``repro_region_seconds{region=<name>}`` of the metrics
  registry.  When tracing is enabled it also writes a JSON event.  So a
  run that never enables tracing -- the chip benchmark -- still reads
  region times from ``metrics.snapshot()``.

Design constraints of ``span``, in order:

1.  **Near-zero cost when disabled.**  ``span()`` checks one module-level
    bool and returns a shared no-op context manager — no event object,
    no timestamp read, no lock.  The hot paths (``planned_dense_apply``
    dispatch, ``ServeEngine.step``) additionally guard their attribute
    construction on ``enabled()`` so a disabled run allocates nothing
    per call beyond the argument tuple of the guard itself.  The
    ``obs.overhead`` bench lane and ``tests/test_obs.py`` pin this.
2.  **Thread-safe.**  Realtime serving runs one worker thread per tier;
    events append under a lock, span timing itself is thread-local
    state on the span object.
3.  **Two clock domains.**  Runtime spans are stamped with
    ``time.perf_counter`` relative to the trace epoch (pid
    ``PID_RUNTIME``).  The virtual-time server instead emits
    *explicit-timestamp* complete events (``complete_event``) on pid
    ``PID_SERVER`` whose microseconds are simulated seconds — so a
    virtual-mode trace shows the request timeline the simulation
    computed, side by side with the real jit/interpret wall time.

Export format is the Chrome trace-event JSON object form
(``{"traceEvents": [...]}``) with ``ph: "X"`` complete events —
loadable by ``chrome://tracing`` and https://ui.perfetto.dev.

Enabling: set ``REPRO_TRACE=1`` (collect; fetch with ``events()`` /
``save()``), or ``REPRO_TRACE=/path/out.json`` (collect and write the
trace at process exit), or call ``enable()`` programmatically.
"""
from __future__ import annotations

import atexit
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from . import metrics as _metrics

__all__ = ["ENV_TRACE", "PID_RUNTIME", "PID_SERVER", "REGION_SECONDS",
           "enabled", "enable", "disable", "span", "region", "instant",
           "complete_event", "events", "clear", "save", "to_chrome"]

ENV_TRACE = "REPRO_TRACE"

# Chrome trace "process" ids: two logical timelines, not OS processes.
PID_RUNTIME = 1     # host wall clock (perf_counter since trace epoch)
PID_SERVER = 2      # serving clock (virtual seconds in virtual-time mode)

_FALSY = ("", "0", "false", "off", "no", "none")

_enabled = False
_lock = threading.Lock()
_events: List[Dict[str, Any]] = []
_epoch = time.perf_counter()


def enabled() -> bool:
    """True when span collection is on (the hot-path guard)."""
    return _enabled


def enable(clear_events: bool = False) -> None:
    global _enabled
    if clear_events:
        clear()
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def clear() -> None:
    with _lock:
        del _events[:]


def _now_us() -> float:
    return (time.perf_counter() - _epoch) * 1e6


class _NullSpan:
    """Shared do-nothing context manager returned while tracing is off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("name", "cat", "args", "_t0", "_ann")

    def __init__(self, name: str, cat: str, args: Optional[dict]):
        self.name = name
        self.cat = cat
        self.args = args

    def set(self, **attrs):
        """Attach attributes discovered mid-span (e.g. a resolved route)."""
        if self.args is None:
            self.args = attrs
        else:
            self.args.update(attrs)
        return self

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = _now_us()
        return self

    def __exit__(self, *exc):
        t1 = _now_us()
        self._ann.__exit__(*exc)
        ev = {"name": self.name, "cat": self.cat, "ph": "X",
              "ts": self._t0, "dur": t1 - self._t0,
              "pid": PID_RUNTIME, "tid": threading.get_ident()}
        if self.args:
            ev["args"] = self.args
        with _lock:
            _events.append(ev)
        return False


def span(name: str, cat: str = "repro", **attrs):
    """Context manager timing a runtime span; no-op when disabled.

    ``with obs.span("plan.build_schedule", m=m, k=k): ...``
    """
    if not _enabled:
        return NULL_SPAN
    return _Span(name, cat, attrs or None)


REGION_SECONDS = "repro_region_seconds"
_region_hists: Dict[str, Any] = {}


class _Region:
    __slots__ = ("name", "_hist", "_ann", "_t0")

    def __init__(self, name: str, hist):
        self.name = name
        self._hist = hist

    def __enter__(self):
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        self._hist.observe(t1 - self._t0)
        if _enabled:
            ev = {"name": self.name, "cat": "region", "ph": "X",
                  "ts": (self._t0 - _epoch) * 1e6,
                  "dur": (t1 - self._t0) * 1e6,
                  "pid": PID_RUNTIME, "tid": threading.get_ident()}
            with _lock:
                _events.append(ev)
        return False


def region(name: str) -> _Region:
    """Context manager timing a host region around device work; always on.

    ``with obs.region("serve.dispatch"): ...`` opens a profiler
    ``TraceAnnotation`` and observes the region's seconds into
    ``repro_region_seconds{region=name}`` whether or not tracing is
    enabled; with tracing enabled it also writes the JSON event.  The
    histogram child is bound once per name, so a call does no label
    lookup.
    """
    hist = _region_hists.get(name)
    if hist is None:
        hist = _metrics.get_registry().histogram(
            REGION_SECONDS, _metrics.REGION_EDGES).labels(region=name)
        _region_hists[name] = hist
    return _Region(name, hist)


def instant(name: str, cat: str = "repro", **attrs) -> None:
    """A zero-duration marker event on the runtime timeline."""
    if not _enabled:
        return
    ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
          "ts": _now_us(), "pid": PID_RUNTIME,
          "tid": threading.get_ident()}
    if attrs:
        ev["args"] = attrs
    with _lock:
        _events.append(ev)


def complete_event(name: str, t0_s: float, t1_s: float, *,
                   tid: int = 0, pid: int = PID_SERVER,
                   cat: str = "serve",
                   args: Optional[dict] = None) -> None:
    """Record a complete event with explicit timestamps (seconds).

    Used for spans whose clock is not the host's — per-request lifecycle
    phases on the virtual serving clock, stamped post-hoc from the
    timestamps ``ServeRequest.to()`` recorded.  No-op when disabled.
    """
    if not _enabled:
        return
    ev = {"name": name, "cat": cat, "ph": "X", "ts": t0_s * 1e6,
          "dur": max(t1_s - t0_s, 0.0) * 1e6, "pid": pid, "tid": tid}
    if args:
        ev["args"] = args
    with _lock:
        _events.append(ev)


def events() -> List[Dict[str, Any]]:
    """A snapshot copy of the collected events."""
    with _lock:
        return list(_events)


def to_chrome() -> Dict[str, Any]:
    """The Chrome trace-event JSON object for the collected events."""
    meta = [
        {"ph": "M", "pid": PID_RUNTIME, "tid": 0, "name": "process_name",
         "args": {"name": "repro runtime (wall clock)"}},
        {"ph": "M", "pid": PID_SERVER, "tid": 0, "name": "process_name",
         "args": {"name": "repro serving clock"}},
    ]
    return {"traceEvents": meta + events(), "displayTimeUnit": "ms"}


def save(path: str) -> str:
    """Write the trace JSON to ``path`` (Chrome/Perfetto loadable)."""
    with open(path, "w") as fh:
        json.dump(to_chrome(), fh)
    return path


def _init_from_env() -> None:
    val = os.environ.get(ENV_TRACE)
    if val is None or val.strip().lower() in _FALSY:
        return
    enable()
    if val.strip().lower() not in ("1", "true", "on", "yes"):
        # value is an output path: write the trace at process exit
        atexit.register(save, val)


_init_from_env()
