"""Engine step: median, per mixed prefill step of the window, of the time
inside the model's forward: the program's ``engine.mixed.forward`` spans
under each ``engine.mixed`` span (the draft's forward too, where there is
one). What a jitted mixed step (ROADMAP S1) has to shorten."""
from benchmark.harness import program_spans


def read(obs):
    rows, steps = program_spans.window_steps(obs)
    return program_spans.median_ms(program_spans.per_step_ms(
        rows, steps["mixed"], ("engine.mixed.forward",)))
