"""Host spans and counters of the program, off by default.

    from repro import obs
    obs.reset(); obs.enable()
    ...                      # solves, replays
    obs.disable()
    obs.snapshot()  # {"spans": {name: {calls, total_s, self_s}},
                    #  "counters": {name: n}}

`span(name)` is a context manager.  While tracing is on, it enters
`jax.profiler.TraceAnnotation("repro." + name)`, so a running profiler
records the span on its host plane, on the same clock as the device's
operations, and it adds its host duration to an aggregate per name:
calls, total time, and self time (total less the time of the spans
opened inside it).  Only the aggregates are kept, never one record per
call, so a long-lived process holds a bounded store.  `count(name, n)`
adds to a counter.

While tracing is off (the default), `span` returns one shared no-op
context and `count` returns at once: each costs one check of a module
global, with no clock read, allocation or string formatting.
"""
from __future__ import annotations

import threading
import time

import jax

_on = False
_lock = threading.Lock()
_local = threading.local()       # per thread: the stack of open spans
_spans: dict = {}                # name -> [calls, total ns, self ns]
_counters: dict = {}


class _Off:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Span:
    __slots__ = ("name", "ann", "t0", "child_ns")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        self.ann = jax.profiler.TraceAnnotation("repro." + self.name)
        self.ann.__enter__()
        self.child_ns = 0
        stack.append(self)
        self.t0 = time.perf_counter_ns()
        return None

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self.t0
        stack = _local.stack
        stack.pop()
        self.ann.__exit__(*exc)
        if stack:
            stack[-1].child_ns += dt
        with _lock:
            rec = _spans.get(self.name)
            if rec is None:
                rec = _spans[self.name] = [0, 0, 0]
            rec[0] += 1
            rec[1] += dt
            rec[2] += dt - self.child_ns
        return False


def span(name: str):
    """A context timing the block as span `name` (a no-op while off)."""
    if not _on:
        return _OFF
    return _Span(name)


def count(name: str, n: int = 1) -> None:
    """Add `n` to counter `name` (nothing while off)."""
    if not _on:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def reset() -> None:
    """Drop every aggregate and counter."""
    with _lock:
        _spans.clear()
        _counters.clear()


def snapshot() -> dict:
    """The aggregates so far, as plain numbers."""
    with _lock:
        return {"spans": {k: {"calls": c, "total_s": t / 1e9,
                              "self_s": s / 1e9}
                          for k, (c, t, s) in _spans.items()},
                "counters": dict(_counters)}
