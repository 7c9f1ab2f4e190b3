"""Lightweight request tracing.

A :class:`TraceContext` carries an 8-byte trace id and a list of named
span timings.  It is *activated* on the current thread; the single
cheap check everywhere on the hot path is ``current_trace()`` (a
thread-local read).

Every ``maybe_span(name)`` has two sinks: the active trace, when there
is one, and the JAX profiler, as a ``TraceAnnotation`` named
``repro.<name>`` on the same clock as the device's operations.  The
annotation costs under a microsecond when no profiler session records,
and is only opened once the process has loaded JAX: this module never
imports it, so cache-node processes stay JAX-free.

Propagation:

- ``ServingEngine`` creates a trace per request, always, and activates
  it around plan and the request's service on the engine thread; the
  request's record carries its ``span_totals()``.
- ``IOExecutor.submit``/``try_submit`` capture the submitting thread's
  trace and re-activate it inside the worker, so spans recorded in
  ``CacheHierarchy.fetch`` (and the store or cluster fan-out beneath
  it) land on the right trace without explicit plumbing.
- The cluster client attaches the id of an active trace made with
  ``on_wire=True`` to outgoing mux frames (``FLAG_TRACE`` + 8 id bytes,
  see ``cluster/protocol.py``); the node server closes the trace out by
  timing the request into its ``repro_node_trace_server_span_seconds``
  histogram and remembering the id in a recent-traces ring surfaced by
  ``OP_METRICS``.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional, Tuple

__all__ = [
    "TRACE_ID_BYTES",
    "ENGINE_SPANS",
    "TraceContext",
    "current_trace",
    "activate",
    "maybe_span",
    "wire_trace_id",
]

TRACE_ID_BYTES = 8

# Every span name that can land on a request's trace, enumerated here so
# the metric catalog and docs lint can enumerate the derived
# repro_engine_span_seconds_<name> histograms.  The engine's five
# pipeline phases come first; then what runs beneath them: the wait on a
# fetch, the model's prefill and copy to the host, the hierarchy's
# commit, and the store's probe, read, decode (on an I/O worker) and
# encode (write-behind: only when the write is inline).
ENGINE_SPANS = ("plan", "fetch", "fulfill", "compute", "commit",
                "fetch_wait", "prefill", "device_to_host", "to_blocks",
                "make_room", "enqueue", "probe", "read", "decode", "encode")
# Spans opened with no request's trace active (``step``) reach the
# profiler alone, under this prefix like every other.
PROFILER_PREFIX = "repro."

_tls = threading.local()


class TraceContext:
    """One request's trace: an id plus thread-safe span timings.

    Spans are (name, offset_from_trace_start_s, duration_s) tuples;
    multiple spans may share a name (e.g. a hedged fetch records two
    ``fetch`` spans) — ``span_totals`` aggregates by name.
    """

    __slots__ = ("trace_id", "on_wire", "t0", "_spans", "_lock")

    def __init__(self, trace_id: Optional[str] = None, on_wire: bool = True):
        self.trace_id = trace_id if trace_id else os.urandom(TRACE_ID_BYTES).hex()
        # whether the cluster client sends the id to the nodes it calls
        self.on_wire = on_wire
        self.t0 = time.perf_counter()
        self._spans: List[Tuple[str, float, float]] = []
        self._lock = threading.Lock()

    def id_bytes(self) -> bytes:
        return bytes.fromhex(self.trace_id)

    def add_span(self, name: str, start: float, duration_s: float) -> None:
        with self._lock:
            self._spans.append((name, start - self.t0, duration_s))

    @contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            self.add_span(name, t0, time.perf_counter() - t0)

    @property
    def spans(self) -> List[Tuple[str, float, float]]:
        with self._lock:
            return list(self._spans)

    def span_totals(self) -> Dict[str, float]:
        """Total seconds per span name (hedged/repeated spans summed)."""
        out: Dict[str, float] = {}
        with self._lock:
            for name, _off, dur in self._spans:
                out[name] = out.get(name, 0.0) + dur
        return out


def current_trace() -> Optional[TraceContext]:
    """The trace active on this thread, or None. One thread-local read."""
    return getattr(_tls, "trace", None)


@contextmanager
def activate(trace: Optional[TraceContext]):
    """Make ``trace`` the current trace for the dynamic extent; restores
    the previous one on exit. ``activate(None)`` suppresses tracing."""
    prev = getattr(_tls, "trace", None)
    _tls.trace = trace
    try:
        yield trace
    finally:
        _tls.trace = prev


def wire_trace_id() -> Optional[bytes]:
    """The id bytes of the active trace if it goes on the wire, else None."""
    tr = getattr(_tls, "trace", None)
    return tr.id_bytes() if tr is not None and tr.on_wire else None


_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` once the process has imported JAX,
    else None: this module must not be the one that loads it."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        try:
            from jax.profiler import TraceAnnotation
        except ImportError:  # JAX still loading on another thread
            return None
        _annotation = TraceAnnotation
    return _annotation


@contextmanager
def maybe_span(name: str):
    """Time ``name`` on the current trace, if one is active, and open it
    in the JAX profiler as ``repro.<name>``, if JAX is loaded.

    With neither, the cost is one thread-local read and two None checks;
    an annotation with no profiler session recording adds under a
    microsecond.
    """
    ann = _annotation if _annotation is not None else _profiler_annotation()
    tr = getattr(_tls, "trace", None)
    with ann(PROFILER_PREFIX + name) if ann is not None else nullcontext():
        if tr is None:
            yield None
            return
        t0 = time.perf_counter()
        try:
            yield tr
        finally:
            tr.add_span(name, t0, time.perf_counter() - t0)
