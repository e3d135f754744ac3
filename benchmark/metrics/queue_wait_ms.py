"""Scheduler: median time the window's requests waited in the queue, submit
to admission: the program's ``request.queued`` spans (the last ``batches`` x
``batch`` of them), on the program's own clock. With 16 slots for a closed
batch of 16 it is the time to the first pump; an open-loop cell will stand
on it."""
from benchmark.harness import program_spans


def read(obs):
    queued = program_spans.window_requests(obs)
    return program_spans.median_ms([1e3 * r["seconds"] for r in queued])
