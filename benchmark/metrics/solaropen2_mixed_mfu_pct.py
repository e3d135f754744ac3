"""Engine step: operations the traced mixed prefill steps need, the WHOLE
step's: 2 x the weights a token multiplies (the routed experts' by the rows
the program COUNTED on its ``engine.mixed`` spans, scaled to the step's valid
positions), the delta rule AS WRITTEN (7 operations a state element, not
the chunked algorithm's solve), the GQA layer's causal pairs
(``counts_solar_open2.prefill_flops``), over the device time of the jitted
mixed step, against the chip's bf16 peak. For the Solar-Open2 family
only."""
from benchmark.harness import counts_solar_open2 as counts
from benchmark.harness import program_spans

PROGRAM = "jit_mixed"  # the engine's jitted mixed step, as the trace names it


def read(obs):
    trace = obs.get("trace")
    if not trace or not obs.get("engine_steps") \
            or not counts.is_family(obs["config"]):
        return None
    seconds = trace["module_seconds"].get(PROGRAM, 0.0)
    _, steps = program_spans.window_steps(obs)
    spans = [s[0]["args"] for s in steps["mixed"] if "moe_rows" in s[0]["args"]]
    if seconds <= 0 or not spans:
        return None
    slots = int(obs["config"]["engine"]["num_slots"])
    tokens = routed = 0.0
    for a in spans:
        positions = slots * a["bucket"]
        valid = positions - a["padded_tokens"]
        tokens += valid
        routed += a["moe_rows"] * valid / positions
    flops = counts.prefill_flops(
        obs["config"], tokens, routed, obs["batches"] * obs["batch"],
        obs["prompt_len"])
    return 100.0 * flops / seconds / obs["peaks"]["bf16_flops"]
