"""Chrome trace-event recording — per-request lifecycle spans the
serving engine emits at quantum/step boundaries, exported as the JSON
object format Perfetto / chrome://tracing load directly (reference:
the chrome-trace exporter of the paddle profiler,
``python/paddle/profiler/profiler.py`` — unverified, SURVEY.md §0; the
event schema is the Trace Event Format's ``X``/``i``/``C``/``M``
phases).

Hot-path-safe by construction: recording one event is an epoch
subtraction plus one append into a BOUNDED ring — when ``max_events``
is reached the OLDEST event makes room and is counted as dropped (the
drop counter is exported in the trace metadata), so an always-on
recorder holds the newest events however long the process lives.
Nothing here imports jax or touches device values.

Timestamps are microseconds relative to the recorder's epoch
(``time.perf_counter`` at construction), so traces start near t=0 and
the engine can pass through the very ``perf_counter`` stamps it
already takes at step boundaries.

ONE recorder per process (:meth:`TraceRecorder.process`) takes every
host span of the program — ``paddle_tpu.profiler.RecordEvent`` appends
an ``X`` row per span (``args``: its ``id``, the ``parent`` span open on
the same thread when it began, the identifiers handed in, and the
compile events charged to it) — and, for an engine built with
``trace=True``, the per-request lifecycle events of ``ServingObs``.
``PERF.md`` section 3 lists the span names.
"""
from __future__ import annotations

import collections
import itertools
import json
import threading
import time

__all__ = ["TraceRecorder", "validate_chrome_trace",
           "load_chrome_trace"]

_PID = 1  # single-process traces: one pid, tracks are tids


class TraceRecorder:
    """Bounded trace-event buffer.

    Event kinds (all take ``t``/``t0``/``t1`` as perf_counter seconds,
    converted to epoch-relative µs):

    - :meth:`complete` — an ``X`` span (name, start, duration).
    - :meth:`instant` — an ``i`` thread-scoped marker.
    - :meth:`counter` — a ``C`` sampled-values track (dict of series).
    - :meth:`span` — an ``X`` row of the program's own host spans
      (what ``RecordEvent`` appends), :meth:`spans` reads them back.
    - :meth:`thread_name` — an ``M`` metadata record naming a track
      (kept beside the ring, so eviction never un-names a track).
    """

    _process = None
    # tracks of the program's host spans: one per thread, numbered from
    # here up so they never collide with the engine (0) / slot (1..) tracks
    _THREAD_TID0 = 1000

    def __init__(self, max_events=65536, epoch=None):
        self.max_events = int(max_events)
        self.epoch = time.perf_counter() if epoch is None else float(epoch)
        self.events = collections.deque(maxlen=self.max_events)
        self.dropped = 0
        self._names = {}       # tid -> track name
        self._thread_tids = {}  # threading.get_ident() -> tid
        self._next_tid = itertools.count(self._THREAD_TID0)
        self._ids = itertools.count(1)

    @classmethod
    def process(cls):
        """The process's one recorder (built on first use)."""
        if cls._process is None:
            cls._process = cls()
        return cls._process

    def __len__(self):
        return len(self.events)

    def clear(self):
        """Forget every event and the drop count (tracks keep their
        names; span ids keep counting, so an old id never comes back)."""
        self.events.clear()
        self.dropped = 0

    def _us(self, t):
        return round((float(t) - self.epoch) * 1e6, 3)

    def _push(self, ev):
        if len(self.events) >= self.max_events:
            self.dropped += 1  # the deque lets its oldest event go
        self.events.append(ev)

    def thread_name(self, tid, name):
        """Name a track (idempotent)."""
        self._names.setdefault(int(tid), str(name))

    def thread_tid(self):
        """The calling thread's own track, named after the thread."""
        ident = threading.get_ident()
        tid = self._thread_tids.get(ident)
        if tid is None:
            # a count hands each number out once, whichever threads ask
            tid = self._thread_tids[ident] = next(self._next_tid)
            self._names[tid] = threading.current_thread().name
        return tid

    def next_id(self):
        """A span id no other span of this recorder has had."""
        return next(self._ids)

    def span(self, name, t0, t1, span_id=None, parent=None, args=None):
        """One host span of the program on the calling thread's track:
        an ``X`` row whose ``args`` (the dict handed in, kept) carry its
        ``id``, its ``parent`` (the span open on this thread when it
        began, else None) and whatever else the caller put there.
        Returns the id."""
        if args is None:
            args = {}
        args["id"] = span_id = next(self._ids) if span_id is None \
            else span_id
        args["parent"] = parent
        self._push({"name": name, "ph": "X", "pid": _PID,
                    "tid": self.thread_tid(),
                    "ts": max((t0 - self.epoch) * 1e6, 0.0),
                    "dur": max((t1 - t0) * 1e6, 0.0), "args": args})
        return span_id

    def spans(self, name=None):
        """The program's span rows still in the ring, oldest first (the
        ``X`` events that carry an ``id``); ``name`` keeps one name."""
        return [e for e in list(self.events)
                if e["ph"] == "X" and "id" in e.get("args", ())
                and (name is None or e["name"] == name)]

    def complete(self, name, t0, t1, tid=0, args=None):
        ev = {"name": str(name), "ph": "X", "pid": _PID,
              "tid": int(tid), "ts": self._us(t0),
              "dur": max(round((float(t1) - float(t0)) * 1e6, 3), 0.0)}
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def instant(self, name, t, tid=0, args=None):
        ev = {"name": str(name), "ph": "i", "s": "t", "pid": _PID,
              "tid": int(tid), "ts": self._us(t)}
        if args:
            ev["args"] = dict(args)
        self._push(ev)

    def counter(self, name, t, values, tid=0):
        self._push({"name": str(name), "ph": "C", "pid": _PID,
                    "tid": int(tid), "ts": self._us(t),
                    "args": {k: float(v) for k, v in values.items()}})

    # -- export ------------------------------------------------------------
    def chrome_trace(self):
        """The JSON Object Format: ``traceEvents`` + metadata.
        Events sorted by (ts, tid) — loaders do not require order, but
        determinism keeps golden comparisons byte-stable."""
        evs = [{"name": "thread_name", "ph": "M", "pid": _PID,
                "tid": tid, "args": {"name": name}}
               for tid, name in self._names.items()] + list(self.events)
        evs.sort(key=lambda e: (e.get("ts", -1.0), e["tid"], e["name"]))
        return {
            "traceEvents": evs,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": "paddle_tpu.obs",
                "dropped_events": self.dropped,
            },
        }

    def save(self, path):
        obj = self.chrome_trace()
        validate_chrome_trace(obj)
        with open(path, "w") as f:
            json.dump(obj, f, sort_keys=True)
        return path


_REQUIRED_BY_PHASE = {
    "X": ("ts", "dur"),
    "i": ("ts",),
    "C": ("ts", "args"),
    "M": ("args",),
}


def validate_chrome_trace(obj):
    """Schema check for the subset of the Trace Event Format this
    recorder emits; raises ValueError with the first offending event.
    Used by :meth:`TraceRecorder.save`, the CLI, and the round-trip
    test."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("not a chrome trace: missing 'traceEvents'")
    for i, ev in enumerate(obj["traceEvents"]):
        ctx = f"traceEvents[{i}] = {ev!r}"
        for k in ("name", "ph", "pid", "tid"):
            if k not in ev:
                raise ValueError(f"{ctx}: missing {k!r}")
        ph = ev["ph"]
        if ph not in _REQUIRED_BY_PHASE:
            raise ValueError(f"{ctx}: unsupported phase {ph!r}")
        for k in _REQUIRED_BY_PHASE[ph]:
            if k not in ev:
                raise ValueError(f"{ctx}: phase {ph!r} missing {k!r}")
        if "ts" in ev and (not isinstance(ev["ts"], (int, float))
                           or ev["ts"] < 0):
            raise ValueError(f"{ctx}: ts must be a non-negative number")
        if ph == "X" and (not isinstance(ev["dur"], (int, float))
                          or ev["dur"] < 0):
            raise ValueError(f"{ctx}: dur must be a non-negative number")
        if ph == "i" and ev.get("s", "t") not in ("t", "p", "g"):
            raise ValueError(f"{ctx}: instant scope must be t|p|g")
    return obj


def load_chrome_trace(path):
    """Load + validate a saved trace; returns the dict."""
    with open(path) as f:
        obj = json.load(f)
    return validate_chrome_trace(obj)
