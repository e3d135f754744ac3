"""The benchmark's one command:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

It reads ``BENCHMARK.json`` at the root of the checkout, finds the cell's
configuration file, traffic file (``benchmark/traffic/<traffic>.json``) and
limits (``benchmark/cells/<workload>.json``), and hands them to the traffic
KIND named in the traffic file (``benchmark/kinds/<kind>.py``) with the model
FAMILY named in the configuration file (``benchmark/families/<family>.py``).
With ``--trace 1`` each per-layer metric of the cell is read by the file of
its own name under ``benchmark/metrics/``. Nothing here names a cell, a
model or a metric: a later PR adds files and ``BENCHMARK.json`` entries.

No chip, no result: without a TPU, or with another number of chips than the
cell asks for, it exits non-zero and prints no result line. ``--control 1``
(never passed by the driver) also runs the control: the reference in the
program's place, one precision lower, which has to come out not correct.

The last line of standard output is the result object; earlier lines are
JSON too (what was built, the window, every number compared beside its
limit).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:  # runnable from a bare copy of the checkout
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_by_name(folder, name):
    """The module ``benchmark/<folder>/<name>.py`` (names may hold ``.``
    and ``-``, so by path, not by import)."""
    path = os.path.join(HERE, folder, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"{folder} {name!r}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{folder}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench, workload):
    """A ``workloads`` entry -> (cell, config entry, config, traffic, limits)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return (cell, entry, load_json(entry["file"]),
            load_json("benchmark", "traffic", cell["traffic"] + ".json"),
            load_json("benchmark", "cells", workload + ".json"))


def metrics_of(bench, cell, tier):
    """The cell's metrics of one tier (``end_to_end`` | ``per_layer``)."""
    e2e = [m for m in bench["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if tier == "end_to_end":
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell["name"] in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


class Context:
    """What a traffic kind is handed, and the few services it calls back."""

    def __init__(self, args, cell, config, traffic, family, devices):
        from benchmark.harness import probe

        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.control = bool(args.control)
        self.config, self.traffic = config, traffic
        self.family, self.devices = family, devices
        self.spans = probe.Spans(annotate=self.trace)
        self.meter = probe.CompileMeter()
        self.setup_s = None
        self._trace_dir = os.path.join(ROOT, ".bench_trace", cell["name"])
        self.trace_seconds = {}

    @staticmethod
    def log(obj):
        """One JSON line, stamped with the seconds since process start."""
        print(json.dumps(dict(obj, t=round(time.perf_counter() - T_PROCESS,
                                           2))), flush=True)

    def setup_done(self, now):
        """Called at the first measured operation."""
        self.setup_s = now - T_PROCESS

    def start_trace(self):
        import jax

        shutil.rmtree(self._trace_dir, ignore_errors=True)
        t0 = time.perf_counter()
        jax.profiler.start_trace(self._trace_dir)
        self.trace_seconds["start"] = time.perf_counter() - t0

    def stop_trace(self):
        import jax

        from benchmark.harness import xplane

        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        t1 = time.perf_counter()
        path = xplane.find_xplane(self._trace_dir)
        size = os.path.getsize(path)
        reduced = xplane.reduce_trace(path, self.spans.rows)
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        self.trace_seconds.update(stop=t1 - t0,
                                  reduce=time.perf_counter() - t1)
        self.log({"trace_seconds": self.trace_seconds, "xplane_bytes": size,
                  "device_events": reduced["device_events"]})
        return reduced


def enable_compile_cache():
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says if
    it is set (JAX reads it itself), else the fixed ``.jax_cache/`` of this
    checkout. Every program is kept, the small eager ones too."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def judge(compared, limits):
    """Each number beside its own limit; an unlisted number is an error."""
    for c in compared:
        c["limit"] = limits[c["name"]]
        c["ok"] = bool(c["value"] <= c["limit"])  # NaN fails
    return all(c["ok"] for c in compared)


def run_cell(bench, args, devices, device, peak_row, tracing=True):
    """Everything of a run after the look for a chip: build, warm up,
    measure, compare, reduce. Prints the earlier lines and returns the
    result object. ``tracing=False`` (the CPU rehearsal and tests only)
    takes no profiler trace, so a traced run reads spans and counters
    alone."""
    from benchmark.harness import xplane

    cell, _, config, traffic, limits = resolve(bench, args.workload)
    family = load_by_name("families", config["family"])
    kind = load_by_name("kinds", traffic["kind"])
    ctx = Context(args, cell, config, traffic, family, devices)
    if not tracing:
        ctx.spans.annotate = False
        ctx.start_trace = ctx.stop_trace = lambda: None
    out = kind.run(ctx)

    correct = judge(out["compared"], limits["limits"])
    Context.log({"compared": out["compared"]})
    control_correct = None
    if out.get("control") is not None:
        control_correct = judge(out["control"], limits["limits"])
        Context.log({"control_compared": out["control"],
                     "control_correct": control_correct})
    correct = correct and out["failed"] == 0

    obs = dict(out["observations"], config=config, traffic=traffic,
               peaks=peak_row, cell=cell)
    trace = obs.get("trace")
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": {},
              "device": dict(device, memory_peak_bytes=int(
                  out["memory_peak_bytes"]))}
    if control_correct is not None:
        result["control_correct"] = control_correct
    if args.trace:
        for m in metrics_of(bench, cell, "per_layer"):
            value = load_by_name("metrics", m["name"]).read(obs)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        if trace is not None:
            result["device"].update(busy_s=trace["busy_s"],
                                    window_s=trace["window_s"])
            result["breakdown"] = xplane.breakdown(trace)
    else:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in metrics_of(bench, cell, "end_to_end"):
            if values.get(m["name"]) is None:
                raise RuntimeError(f"the run gave no {m['name']}")
            result["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                            "unit": m["unit"]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    cell = resolve(bench, args.workload)[0]
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("the program under test (paddle_tpu/) is not in this "
              "checkout: nothing to measure", file=sys.stderr)
        return 3

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if device["platform"] != "tpu" or device["count"] != cell["chips"]:
        print(f"{cell['name']} needs {cell['chips']} TPU chip(s); jax found "
              f"{device}. No chip, no result.", file=sys.stderr)
        return 2
    from benchmark.harness import peaks

    peak_row = peaks.peaks_for(device["kind"])
    Context.log({"cell": cell["name"], "seed": args.seed, "device": device,
                 "compile_cache_dir": enable_compile_cache()})
    print(json.dumps(run_cell(bench, args, devices, device, peak_row)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
