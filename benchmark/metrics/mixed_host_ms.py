"""Engine step: median, per mixed prefill step of the window, of the host's
own part: the program's ``door.pump`` row that ran the step less the
``engine.mixed.forward`` spans under it (the device's time for the step):
admission (``engine.admit``), ``prepare``, ``select``, ``emit`` and the
door. The mixed step's counterpart of ``quantum_host_ms``; the device is
idle for about that long a mixed step (ROADMAP S4)."""
from benchmark.harness import program_spans


def read(obs):
    rows, steps = program_spans.window_steps(obs)
    forward = program_spans.per_step_ms(rows, steps["mixed"],
                                        ("engine.mixed.forward",))
    if forward is None:
        return None
    by_id = {r["id"]: r for r in rows}
    host = []
    for step, ms in zip(steps["mixed"], forward):
        up = step[0]
        while up is not None and up["name"] != "door.pump":
            up = by_id.get(up["parent"])
        if up is not None:  # the ring may have let the pump's row go
            host.append(1e3 * up["seconds"] - ms)
    return program_spans.median_ms(host)
