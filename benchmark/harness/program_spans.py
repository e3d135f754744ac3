"""The program's own host spans, read from outside: what the per-layer
metrics named after them share.

Since PR 26 the program records a row for every host region it spans
(``paddle_tpu.profiler.RecordEvent``: name, start and duration on
``time.perf_counter``, its own ``id``, the ``parent`` span that was open on
the same thread when it began, and, on a step span, the compile events JAX
raised while it was open) into the process's one recorder
(``paddle_tpu.obs.trace.TraceRecorder.process()``), with or without a
profiler session. ``PERF.md`` section 3 lists the names. The kinds hand no
span of the program to ``obs``, so a reader fetches the rows itself and
bounds them to the window BY COUNT from what ``obs`` already holds: the
window is the last thing the program does in the process, so its steps are
the last ``n`` of their name.

A program without the recorder (a parent commit of PR 26) has no rows:
every function here then returns nothing and no reader raises.

A STEP is a list of rows: one row, or the two the engine records for a
decode quantum, which it dispatches and collects in two halves (``half`` =
``dispatch`` then ``collect`` in their args; ``engine.step`` likewise).
Seconds are what a span's own two stamps give, a step's are its rows'
added. Self time is a duration minus what the direct children cover;
children are sequential on one thread, so their durations add.
"""
from __future__ import annotations

import statistics

STAGES = ("trace", "lower", "backend", "cache_load")


def rows():
    """Every span still in the program's recorder, in the order they
    ended, as {name, id, parent, start_s, seconds, args}."""
    try:
        from paddle_tpu.obs.trace import TraceRecorder
    except ImportError:
        return []
    process = getattr(TraceRecorder, "process", None)
    if process is None:
        return []
    return from_events(process().events)


def from_events(events):
    """The same, from a list of Chrome trace events."""
    out = []
    for e in list(events):
        args = e.get("args") or {}
        if e.get("ph") != "X" or "id" not in args:
            continue
        out.append({"name": e["name"], "id": args["id"],
                    "parent": args.get("parent"),
                    "start_s": e["ts"] * 1e-6, "seconds": e["dur"] * 1e-6,
                    "args": args})
    return out


def last(all_rows, name, n):
    """The last ``n`` steps called ``name`` (fewer if fewer are held),
    each a list of rows: a collect half joins the dispatch half before
    it; one whose dispatch half the ring has let go is left out."""
    steps = []
    for r in all_rows:
        if r["name"] != name:
            continue
        if r["args"].get("half") != "collect":
            steps.append([r])
        elif steps and steps[-1][-1]["args"].get("half") == "dispatch":
            steps[-1].append(r)
    return steps[max(len(steps) - int(n), 0):] if n and n > 0 else []


def seconds(step):
    return sum(r["seconds"] for r in step)


def children(all_rows, step, name=None):
    """The direct children of a step's rows, in the order they ended."""
    ids = {r["id"] for r in step}
    return [r for r in all_rows if r["parent"] in ids
            and (name is None or r["name"] == name)]


def self_seconds(step, all_rows):
    """A step's duration less what its direct children cover."""
    return seconds(step) - seconds(children(all_rows, step))


def covered_share(step, all_rows):
    """The share of a step that its direct children cover."""
    if seconds(step) <= 0:
        return None
    return 1.0 - self_seconds(step, all_rows) / seconds(step)


def window_steps(obs, all_rows=None):
    """(rows, the window's steps) for the kind ``obs`` is of: the last
    ``mixed_steps`` ``engine.mixed`` and the last ``decode_quanta``
    ``engine.decode`` steps of a serving window (one dispatch a quantum
    while ``multi_quantum`` is 1, as in the cells), the last
    ``len(dispatch_seconds)`` ``train.run_steps`` steps of a training one.
    Keys: ``mixed``, ``decode``, ``train``; a kind without such steps, or
    a program without rows, gives empty lists."""
    all_rows = rows() if all_rows is None else all_rows
    steps = obs.get("engine_steps") or {}
    return all_rows, {
        "mixed": last(all_rows, "engine.mixed", steps.get("mixed_steps", 0)),
        "decode": last(all_rows, "engine.decode",
                       steps.get("decode_quanta", 0)),
        "train": last(all_rows, "train.run_steps",
                      len(obs.get("dispatch_seconds") or ())),
    }


def window_requests(obs, all_rows=None):
    """The ``request.queued`` rows of the window's requests: the last
    ``batches`` x ``batch`` of them."""
    all_rows = rows() if all_rows is None else all_rows
    if "engine_steps" not in obs:
        return []
    n = int(obs.get("batches", 0)) * int(obs.get("batch", 0))
    return [step[0] for step in last(all_rows, "request.queued", n)]


def per_step_ms(all_rows, steps, names):
    """Per step, the milliseconds of the spans called one of ``names``
    anywhere under its rows; None where there is no step."""
    if not steps:
        return None
    by_id = {r["id"]: r for r in all_rows}
    step_of = {r["id"]: k for k, s in enumerate(steps) for r in s}
    out = [0.0] * len(steps)
    for r in all_rows:
        if r["name"] not in names:
            continue
        up = r["parent"]
        while up is not None and up not in step_of:
            up = by_id[up]["parent"] if up in by_id else None
        if up is not None:
            out[step_of[up]] += 1e3 * r["seconds"]
    return out


def median_ms(values):
    return statistics.median(values) if values else None


def compile_seconds(steps):
    """Seconds JAX spent tracing, lowering, compiling and loading from its
    cache while these steps' spans were open, by stage. The program keeps
    the four disjoint (a load is taken off the backend event that spans
    it), so they add."""
    return {st: sum(r["args"].get(f"compile_{st}_s", 0.0)
                    for s in steps for r in s) for st in STAGES}


def compile_requests(steps):
    """Executables JAX asked for while these steps' spans were open."""
    return sum(r["args"].get("compile_requests", 0)
               for s in steps for r in s)
