"""Engine step: executables JAX asked for (a persistent-cache load or a
backend compile) while a decode quantum of the window was open, as the
program charged them to its ``engine.decode`` spans. 0 once warm: then every
compile request of the window belongs to the mixed steps."""
from benchmark.harness import program_spans


def read(obs):
    _, steps = program_spans.window_steps(obs)
    if not steps["decode"]:
        return None
    return program_spans.compile_requests(steps["decode"])
