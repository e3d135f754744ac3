"""What the benchmark observes of a run from outside the program: its own
spans (host clock, and the same spans as annotations in the profiler's
trace), JAX's compile events, and the device's memory counters."""
from __future__ import annotations

import contextlib
import time

import jax


class Spans:
    """Spans recorded from the benchmark's own files, around the calls
    into the program. Kept in memory; ``rows`` is [name, start_s, end_s].
    With ``annotate`` every span is also a ``TraceAnnotation`` under its
    base name (the part before ``:``), so the trace reduction can join the
    two lists in order and attribute idle gaps to them."""

    def __init__(self, annotate=False):
        self.rows = []
        self.annotate = annotate

    @contextlib.contextmanager
    def span(self, name):
        row = [name, time.perf_counter(), None]
        self.rows.append(row)
        ctx = (jax.profiler.TraceAnnotation(name.split(":")[0])
               if self.annotate else contextlib.nullcontext())
        with ctx:
            try:
                yield row
            finally:
                row[2] = time.perf_counter()

    def durations(self, name):
        return [r[2] - r[1] for r in self.rows
                if r[0] == name and r[2] is not None]


class CompileMeter:
    """Compile requests, persistent-cache hits and misses and seconds in
    the backend compiler (a cache hit counts its retrieval), read off
    ``jax.monitoring``; eager ops and jitted programs count alike."""

    def __init__(self):
        self.requests = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def snapshot(self):
        return {"requests": self.requests, "hits": self.hits,
                "misses": self.misses, "seconds": round(self.seconds, 3)}


def memory(devices):
    """(peak bytes on the fullest device, bytes in use on the fullest)."""
    stats = [d.memory_stats() or {} for d in devices]
    return (max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
            max(int(s.get("bytes_in_use", 0)) for s in stats))


def release():
    """Drop what the process no longer references from the device."""
    import gc

    gc.collect()
    jax.clear_caches()
    gc.collect()
