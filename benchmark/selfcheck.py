"""The benchmark's check of itself, on the CPU, in seconds:

    python benchmark/selfcheck.py             # the yardstick's arithmetic
    python benchmark/selfcheck.py --rehearse  # both traffic kinds end to end

Without arguments: the trace reduction on a small trace recorded on a TPU
v5e (two jitted programs, three runs each: known busy, idle and per-op
seconds), the operation and byte counters against hand counts for the
configurations, every ``workloads`` entry resolving to files that exist,
and every per-layer metric's ``moves`` being an end-to-end metric of each
cell that reports it.

``--rehearse`` drives both traffic kinds through the program at a toy size
(``benchmark/rehearsal.json``: a toy configuration, traffic file and cell
added beside the real ones exactly as a later PR would add its own), Pallas
kernels in interpret mode, and prints counts only: no metric line, because a
number from a CPU run is never a device metric. ``run.py`` itself stays
strict and fails without a TPU.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def check(cond, what):
    if not cond:
        raise AssertionError(what)
    print("ok  ", what)


def close(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


# ------------------------------------------------------------ the yardstick
def check_trace():
    from benchmark.harness import xplane

    path = os.path.join(HERE, "data", "three_matmul.xplane.pb")
    rows = [["batch", 0, 0]] + [["pump:quantum", 0, 0], ["pump:mixed", 0, 0]] * 3
    t = xplane.reduce_trace(path, rows, window_span="none")
    # summed by hand from the 18 events (ns): the matmul fusions
    # 2 x (11547 + 11573 + 12621) + (11547 + 11574 + 12621) = 107224;
    # copy-done 3161 + 3161 + 3165 = 9487; copy-start 3 x 13 = 39;
    # jit_tiny's fusion 7305 + 7076 + 7268 = 21649; in all 138399. The
    # window runs from the first op (41829017) to the last's end (107539801)
    check(t["devices"] == 1 and t["device_events"] == 18,
          "trace: one device plane, 18 op events")
    check(close(t["busy_s"], 138.399e-6), "trace: busy 138.399 us")
    check(close(t["window_s"], 65.710784e-3),
          "trace: window 65.710784 ms (first op to last)")
    ops = t["op_seconds"]
    check(close(ops["jit_quantum/convolution_tanh_fusion"], 107.224e-6)
          and close(ops["jit_tiny/broadcast_add_fusion"], 21.649e-6)
          and close(ops["jit_quantum/copy-done"], 9.487e-6)
          and close(ops["jit_quantum/copy-start"], 0.039e-6),
          "trace: per-op seconds under <program>/<op>")
    check(close(sum(t["idle_by_span"].values()) + t["busy_s"], t["window_s"]),
          "trace: idle by span + busy = window")
    check(t["idle_by_span"]["pump:mixed"] > t["idle_by_span"]["pump:quantum"]
          > 0, "trace: gaps fall to the innermost span")
    check(t["collective_s"] == 0.0, "trace: no collective on one chip")
    check(xplane.op_name("%all-gather-start.3 = (bf16[8]) all-gather-start("
                         "bf16[4] %p)") == "all-gather-start"
          and xplane.module_name("jit_quantum(123)") == "jit_quantum",
          "trace: op and program names")


def check_counts():
    from benchmark.harness import counts

    load = load_run().load_json
    mistral = load("benchmark", "configs", "mistral-7b-l3.json")
    qwen = load("benchmark", "configs", "qwen2-7b-l22.json")
    # Mistral-7B layer: q 4096x4096, k and v 4096x1024, o 4096x4096,
    # gate/up/down 3 x 4096x14336
    check(counts.layer_matmul_params(mistral) == 218_103_808,
          "counts: Mistral layer multiplies 218,103,808 weights")
    # Qwen2-7B layer: q 3584x3584, k and v 3584x512, o 3584x3584,
    # 3 x 3584x18944
    check(counts.layer_matmul_params(qwen) == 233_046_016,
          "counts: Qwen2 layer multiplies 233,046,016 weights")
    # S 8192, window 4096: 4096*4097/2 + 4096*4096 pairs
    check(counts.attended_pairs(8192, 4096) == 25_167_872,
          "counts: 25,167,872 attended pairs at S 8192, window 4096")
    check(counts.attended_pairs(8192) == 8192 * 8193 // 2,
          "counts: full causal pairs")
    # per token forward: 3 layers x (2 x 218,103,808 + 4 x 4096 x 3072.25
    # = 486,543,360) + 2 x 4096 x 32000 head = 1,721,774,080; x 3 for
    # forward + backward
    check(counts.train_flops_per_token(mistral, 8192) == 3 * 1_721_774_080,
          "counts: 5,165,322,240 training operations per token (l3, S 8192)")
    k = counts.flash_kernel_flops(mistral, 1, 8192)
    check(sum(k.values()) == 18 * 4096 * 25_167_872 * 3,
          "counts: flash kernels 4 + 6 + 8 operations per pair, head, lane")
    # decode: 22 layers x (233,046,016 + 2 x 3584 norms + 4608 biases)
    # + 3584 final norm + 3584 x 152064 head, 2 bytes each
    check(counts.weight_bytes_per_token_step(qwen)
          == 2 * (22 * (233_046_016 + 7168 + 4608) + 3584 + 3584 * 152064),
          "counts: 11,344,539,648 weight bytes per decode step (l22)")
    # one stream, prompt 128, 256 new: 255 decode steps seeing 129..383 keys
    check(counts.decode_context_tokens(128, 256) == sum(range(129, 384)),
          "counts: 65,280 keys attended per stream over its decode steps")
    f, b = counts.paged_attention_needs(qwen, 16, 128, 256)
    check(f == 4 * 28 * 128 * 65_280 * 16 * 22
          and b == 2 * 4 * 128 * 2 * 65_280 * 16 * 22,
          "counts: paged attention operations and bytes per closed batch")
    from benchmark.harness import peaks
    check(peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
          and peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9,
          "peaks: TPU v5 lite 197 TFLOP/s, 819 GB/s")
    try:
        peaks.peaks_for("TPU v9")
    except KeyError:
        check(True, "peaks: an unknown device is an error")
    else:
        check(False, "peaks: an unknown device is an error")


def check_index():
    run = load_run()
    bench = run.load_json("BENCHMARK.json")
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        cell, entry, config, traffic, limits = run.resolve(bench, w["name"])
        for folder, name in (("families", config["family"]),
                             ("kinds", traffic["kind"])):
            check(os.path.isfile(os.path.join(HERE, folder, name + ".py")),
                  f"index: {w['name']} -> {folder}/{name}.py")
        check(set(entry["reduced"]) == set(config["reduced_from"]),
              f"index: {entry['name']} lists what its file reduces")
        mine = {m["name"] for m in run.metrics_of(bench, cell, "end_to_end")}
        check("setup_s" in mine and len(mine) >= 2,
              f"index: {w['name']} reports setup_s and another metric")
        layer = run.metrics_of(bench, cell, "per_layer")
        check(len(layer) >= 1, f"index: {w['name']} has per-layer metrics")
        for m in layer:
            check(os.path.isfile(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"))
                  and m["moves"] in mine and m["moves"] in e2e,
                  f"index: {m['name']} has a reader and moves "
                  f"{m['moves']} of {w['name']}")
        check(math.isfinite(sum(limits["limits"].values())),
              f"index: {w['name']} has its limits")
    used = {w["config"] for w in bench["workloads"]}
    check(used == {c["name"] for c in bench["configs"]},
          "index: every configuration has a cell")


# ------------------------------------------------------------ the rehearsal
def load_run():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(HERE, "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    return run


def rehearse_cell(run, index, workload, seed, trace, control):
    """One toy cell through ``run.run_cell`` on the CPU: the whole of a run
    but the look for a chip and the profiler's trace. Returns the result
    object, stripped of every metric: counts only."""
    import jax

    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_pallas_force": True})  # kernels, interpreted
    args = argparse.Namespace(workload=workload, seed=seed, seconds=2.0,
                              trace=trace, control=control)
    dev = jax.devices()[0]
    out = run.run_cell(
        index, args, [dev],
        {"platform": dev.platform, "kind": dev.device_kind, "count": 1},
        {"bf16_flops": float("nan"), "hbm_bytes_per_s": float("nan")},
        tracing=False)
    out["metrics_read"] = sorted(out.pop("metrics"))
    return out


def rehearsal_index(run):
    """``rehearsal.json`` with the real index's per-layer metrics, so a
    traced rehearsal calls every reader that would run on the chip."""
    index = run.load_json("benchmark", "rehearsal.json")
    real = run.load_json("BENCHMARK.json")
    index["end_to_end"] = []
    index["per_layer"] = [dict(m, workloads=[w["name"]
                                             for w in index["workloads"]])
                          for m in real["per_layer"]]
    return index


def rehearse(seed):
    import jax

    jax.config.update("jax_platforms", "cpu")
    run = load_run()
    index = rehearsal_index(run)
    ok = True
    for w in index["workloads"]:
        for trace in (0, 1):
            out = rehearse_cell(run, index, w["name"], seed, trace,
                                control=1 - trace)
            print(json.dumps(dict(out, rehearsed=w["name"], trace=trace)),
                  flush=True)
            ok = ok and out["correct"] and not out.get("control_correct")
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seed", type=int, default=2147483659)
    args = ap.parse_args(argv)
    if args.rehearse:
        return 0 if rehearse(args.seed) else 1
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    check_trace()
    check_counts()
    check_index()
    print("selfcheck passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
