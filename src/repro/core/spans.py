"""Host spans of the campaign entry points, on the profiler's clock.

While a JAX profiler trace runs (`jax.profiler.trace(dir)`, or
`start_trace` ... `stop_trace`), every `span` opens a
`jax.profiler.TraceAnnotation` of its name, so each stage of the host
path appears in the trace on the `/host:` plane, on the clock of the
device ops, nested inside the span that called it.  It also adds its
time to an in-memory aggregate per name, which `summary()` returns:

  n        closed spans of the name,
  total_s  seconds between their opening and closing,
  self_s   total_s minus the time of the spans nested directly in
           them, so that the self times of a tree of spans add up to
           the time of its outermost span (its root),

plus each count the spans carried (`span(name, bytes=...)` or
`.count(...)` inside the block), summed.  `roots` counts the outermost
spans closed: one per traced entry call.

Outside a trace a span costs one `TraceAnnotation.is_enabled()` check:
it opens no annotation, reads no clock and records nothing.  Spans sit
in host code only, never inside a function JAX traces, and bracket
code that runs anyway: they add no synchronisation with the device.
So `sim.fetch` and `margin.fetch` hold the host's wait for the device
as well as the copy.

Span names, with their counts in brackets.  The roots, one per entry
call: `aldram.profile` [modules], `aldram.evaluate_system` [rows,
policies], `aldram.evaluate_dynamic` [scenarios, policies].  Nested
in them:

  sim.prep       stream packing: SimSpec's split of a batched Trace,
                 SimEngine's packing and reorder plan [streams,
                 requests: the packed [streams, n] slots; with rank
                 timings ranks, bank_groups; for a `KVSpec` ops: the
                 YCSB operations with a line among the streams']
  sim.dispatch   the launch of a synthesis or replay program [of a
                 replay, prepass_lanes: the (trace, policy) pairs of
                 its FR-FCFS plan that the `frfcfs_blocks` kernel
                 permutes, prepass_scan_lanes: those a `lax.scan`
                 permutes]
  sim.fetch      the replay's results turned into numpy arrays
  margin.fetch   the wait for one margin dispatch and the copy of its
                 pass envelopes to the host (of its dense grids, for
                 `MarginEngine.margins`) [bytes copied, evals: the
                 margins the dispatch evaluated]
  margin.reduce  the host's part of the envelopes (bank, module,
                 refresh interval) and the combo selection
"""

from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

_lock = threading.Lock()
_local = threading.local()
_roots = 0
_agg: dict[str, dict] = {}


class _Off:
    """The span outside a trace: does nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def count(self, **counts):
        pass


_OFF = _Off()


class _Span:
    __slots__ = ("name", "counts", "child_ns", "_ann", "_t0")

    def __init__(self, name: str, counts: dict):
        self.name, self.counts, self.child_ns = name, counts, 0

    def __enter__(self):
        self._ann = TraceAnnotation(self.name, **self.counts)
        self._ann.__enter__()
        _stack().append(self)
        self._t0 = time.perf_counter_ns()
        return self

    def count(self, **counts):
        """Add to the span's counts (known only inside the block)."""
        for k, v in counts.items():
            self.counts[k] = self.counts.get(k, 0) + v
        self._ann.set_metadata(**{k: self.counts[k] for k in counts})

    def __exit__(self, *exc):
        global _roots
        dt = time.perf_counter_ns() - self._t0
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1].child_ns += dt
        with _lock:
            a = _agg.setdefault(self.name, {"n": 0, "total_ns": 0,
                                            "self_ns": 0, "counts": {}})
            a["n"] += 1
            a["total_ns"] += dt
            a["self_ns"] += dt - self.child_ns
            for k, v in self.counts.items():
                a["counts"][k] = a["counts"].get(k, 0) + v
            _roots += not stack
        self._ann.__exit__(*exc)
        return False


def _stack() -> list:
    """The calling thread's open spans, innermost last."""
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def span(name: str, **counts):
    """Context manager: a span `name` around the block while a profiler
    trace runs (with `counts` as its event stats), nothing otherwise.
    The object it yields takes further counts: `s.count(rows=6)`."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Span(name, counts)


def summary() -> dict:
    """{"roots": int, "spans": {name: {"n", "total_s", "self_s",
    <count>...}}} of every span closed since the last `clear()`."""
    with _lock:
        return {"roots": _roots, "spans": {
            name: {"n": a["n"], "total_s": a["total_ns"] * 1e-9,
                   "self_s": a["self_ns"] * 1e-9, **a["counts"]}
            for name, a in _agg.items()}}


def clear() -> None:
    """Forget every aggregate (spans still open record when they close)."""
    global _roots
    with _lock:
        _roots = 0
        _agg.clear()


__all__ = ["span", "summary", "clear"]
