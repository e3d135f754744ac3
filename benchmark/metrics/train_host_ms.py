"""Trainer: median host time of a dispatch: the program's ``train.args``
(placing the batch, the key, the scalars) plus ``train.enqueue`` (the jitted
call until it returns) under each ``train.run_steps`` span of the window.
The device does not wait for it while a dispatch is in flight."""
from benchmark.harness import program_spans


def read(obs):
    rows, steps = program_spans.window_steps(obs)
    return program_spans.median_ms(program_spans.per_step_ms(
        rows, steps["train"], ("train.args", "train.enqueue")))
