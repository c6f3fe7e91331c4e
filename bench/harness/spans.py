"""Host spans of the benchmark's own, around its calls into the program,
and a count of XLA compiles (from JAX's monitoring events)."""
from __future__ import annotations

import contextlib
import time
from collections import defaultdict

import jax


class Spans:
    """Durations per span name, in seconds.  With `annotate`, each span
    also goes into the profiler's trace as `bench.<name>`, on the same
    clock as the device's operations."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.seconds = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = (jax.profiler.TraceAnnotation(f"bench.{name}")
               if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with ann:
            yield
        self.seconds[name].append(time.perf_counter() - t0)

    def clear(self):
        self.seconds.clear()


class CompileLog:
    """Counts backend compiles and persistent-cache hits, process-wide
    (register once per process)."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
