"""Engine step: median, per decode quantum of the window, of the host's own
part: the program's ``engine.decode`` spans (the dispatch half and the
collect half) less the ``engine.decode.sync`` span (the wait for the
device): growing the block tables, the uploads, the jitted call until it
returns, the token loop and the accounting. The device is idle for about
that long between quanta (ROADMAP S4)."""
from benchmark.harness import program_spans


def read(obs):
    rows, steps = program_spans.window_steps(obs)
    sync = program_spans.per_step_ms(rows, steps["decode"],
                                     ("engine.decode.sync",))
    if sync is None:
        return None
    return program_spans.median_ms(
        [1e3 * program_spans.seconds(s) - w
         for s, w in zip(steps["decode"], sync)])
