"""Engine step: of the window's decode quanta, the share the engine
dispatched AHEAD: enqueued, on the device-resident outputs of the quantum
before, while that one was still to be read back, so the host's half of a
pump ran beside the device and not between two programs. The program marks
such a quantum ``ahead=1`` in the args of the dispatch half's
``engine.decode`` row. Every quantum of a closed batch but the first after
its mixed steps can be one. A program without the mark (before PR 48) reads
0; a window without decode steps reads nothing."""
from benchmark.harness import program_spans


def read(obs):
    _, steps = program_spans.window_steps(obs)
    if not steps["decode"]:
        return None
    ahead = sum(bool(s[0]["args"].get("ahead")) for s in steps["decode"])
    return 100.0 * ahead / len(steps["decode"])
