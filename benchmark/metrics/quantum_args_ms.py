"""Engine step: median time of a decode quantum's uploads: the program's
``engine.decode.args`` span (``_quantum_args``: the block tables, lengths,
last tokens and masks put on the device before every dispatch)."""
from benchmark.harness import program_spans


def read(obs):
    rows, steps = program_spans.window_steps(obs)
    return program_spans.median_ms(program_spans.per_step_ms(
        rows, steps["decode"], ("engine.decode.args",)))
