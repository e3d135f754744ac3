"""Device: bytes the traced decode steps must read — the weights outside the
routed experts once a step, each expert the program's counter says got a row,
the keys and values each layer attends (a full layer every live position, a
window layer the last ``sliding_window``: the ``full_keys`` / ``window_keys``
of the program's ``engine.decode`` spans) — over the device time of the
jitted decode quantum, against the chip's memory bandwidth. For the window /
full attention expert family only."""
from benchmark.harness import counts_afmoe as counts
from benchmark.harness import program_spans

PROGRAM = "jit_quantum"  # the engine's jitted decode step, as the trace names it
KEYS = ("moe_experts_touched", "moe_layer_steps", "window_keys", "full_keys")


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("engine_steps") \
            or not counts.is_family(obs["config"]):
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    _, steps = program_spans.window_steps(obs)
    rows = [r["args"] for step in steps["decode"] for r in step
            if all(k in r["args"] for k in KEYS)]
    if seconds <= 0 or not rows:
        return None
    cfg = obs["config"]
    got = {k: sum(a[k] for a in rows) for k in KEYS}
    expert_layers = (int(cfg["num_hidden_layers"])
                     - int(cfg["num_dense_layers"]))
    nbytes = counts.decode_bytes_needed(
        cfg, got["moe_layer_steps"] // expert_layers,
        got["moe_experts_touched"], got["full_keys"], got["window_keys"])
    return 100.0 * nbytes / seconds / obs["peaks"]["hbm_bytes_per_s"]
