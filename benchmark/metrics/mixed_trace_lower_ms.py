"""Engine step: milliseconds per mixed prefill step that JAX itself reports
spending on tracing and on lowering to MLIR (``jax.monitoring`` durations,
charged by the program to the ``engine.mixed`` span that was open), over the
window's mixed steps. The eager step pays it for every op it has not kept in
memory; what the same rows hold of cache loads and backend compiles is in
``PERF.md`` section 5."""
from benchmark.harness import program_spans


def read(obs):
    _, steps = program_spans.window_steps(obs)
    if not steps["mixed"]:
        return None
    seconds = program_spans.compile_seconds(steps["mixed"])
    return 1e3 * (seconds["trace"] + seconds["lower"]) / len(steps["mixed"])
