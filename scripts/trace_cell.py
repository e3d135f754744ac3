"""One TRACED run of a benchmark cell whose device trace is KEPT and read by
the program's own reader (``paddle_tpu.profiler.load_profiler_result``):

    python scripts/trace_cell.py --workload <cell> --seed <n> [--out DIR]

``benchmark/run.py`` reduces its trace and deletes it; this wrapper runs
the same cell through the same ``run_cell`` (one batch, or eight
dispatches, the cell's own traffic) with the trace taken by
``paddle_tpu.profiler.Profiler`` into ``DIR/trace`` (default
``chiprun_out/traces/<cell>``), prints the reader's tables and writes
``DIR/profile.json``: the reader's result, the benchmark reducer's sums
over the SAME file beside it (``benchmark/harness/xplane.reduce_trace``:
the two must agree), what tracing cost (``trace_seconds``,
``xplane_bytes``, the reader's and the reducer's own seconds) and the
mesh's ``axis_groups``. The plain reference's comparison is SKIPPED
(``correct`` is not what this run is for; the result line says
``reference_skipped``). Nothing under ``benchmark/`` is edited.

A scope is HLO metadata, which JAX's persistent compile cache does not
key on: an executable loaded from a cache entry an OLDER tree compiled
carries that tree's scopes. Point ``JAX_COMPILATION_CACHE_DIR`` at a
directory only this tree has written; the ``programs`` table's ``scoped``
column shows a stale executable at a glance.

``--rehearse`` (CPU, no chip): the toy cells of ``benchmark/rehearsal.json``
through the same path; a CPU trace has no device plane, so the reader's
tables are not printed, only that every step ran.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    ap.add_argument("--keep-trace", type=int, default=0,
                    help="1: leave DIR/trace in place (it can be large)")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    out_dir = args.out or os.path.join(ROOT, "chiprun_out", "traces",
                                       args.workload)
    os.makedirs(out_dir, exist_ok=True)

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    import benchmark.run as run
    from benchmark.harness import xplane

    import paddle_tpu as paddle
    from paddle_tpu.parallel import mesh as mesh_state
    from paddle_tpu.profiler import Profiler, load_profiler_result, reader

    trace_dir = os.path.join(out_dir, "trace")
    profile = {"cell": args.workload, "seed": args.seed}

    def start_trace(self):
        shutil.rmtree(trace_dir, ignore_errors=True)
        t0 = time.perf_counter()
        self._profiler = Profiler(log_dir=trace_dir)
        self._profiler.start()
        self.trace_seconds["start"] = time.perf_counter() - t0

    def stop_trace(self):
        t0 = time.perf_counter()
        self._profiler.stop()
        t1 = time.perf_counter()
        axes = mesh_state.axis_groups()
        profile.update(mesh_axes=axes)
        try:
            path = reader.find_xplane(trace_dir)
            profile["xplane_bytes"] = os.path.getsize(path)
            reduced = xplane.reduce_trace(path, self.spans.rows)
        except (FileNotFoundError, ValueError) as e:  # the CPU rehearsal
            profile["error"] = repr(e)
            return None
        t2 = time.perf_counter()
        # the reader twice: over the benchmark's window (what its reducer
        # saw, for the comparison) and as an operator calls it
        same_window = reader.load(path, mesh_axes=axes, window_span="window")
        t3 = time.perf_counter()
        result = load_profiler_result(trace_dir, mesh_axes=axes)
        self.trace_seconds.update(stop=t1 - t0, reduce=t2 - t1)
        by_program = {}
        for key, v in reduced["op_seconds"].items():
            p = key.split("/", 1)[0]
            by_program[p] = by_program.get(p, 0.0) + v
        profile.update(
            trace_seconds=self.trace_seconds, reader_seconds=t3 - t2,
            reducer_seconds=t2 - t1, result=result.to_dict(),
            same_window=same_window.to_dict(),
            reduced={k: reduced[k] for k in (
                "devices", "window_s", "busy_s", "idle_by_span",
                "collective_s", "collective_exposed_s", "device_events",
                "module_seconds")},
            reduced_op_seconds_by_program=by_program)
        print(result.tables(), flush=True)
        self.log({"trace_seconds": self.trace_seconds,
                  "xplane_bytes": profile["xplane_bytes"],
                  "reader_seconds": t3 - t2,
                  "device_events": reduced["device_events"]})
        return reduced

    run.Context.start_trace = start_trace
    run.Context.stop_trace = stop_trace

    load = run.load_by_name

    def load_patched(folder, name):
        mod = load(folder, name)
        if folder == "families":
            import numpy as np

            ref = mod.reference
            if hasattr(ref, "gap_below_best"):
                ref.gap_below_best = lambda *a, **k: (np.zeros(1), None)
            if hasattr(ref, "train_steps"):
                ref.train_steps = lambda *a, **k: None
        if folder == "kinds" and hasattr(mod, "compare"):
            mod.compare = lambda seen, ref: []
        return mod

    run.load_by_name = load_patched

    ns = argparse.Namespace(workload=args.workload, seed=args.seed,
                            seconds=51.0, trace=1, control=0)
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.rehearse:
        sys.path.insert(0, os.path.join(ROOT, "benchmark"))
        import selfcheck

        paddle.set_flags({"FLAGS_pallas_force": True})
        bench = selfcheck.rehearsal_index(run)
        ns.seconds, devices = 2.0, devices[:1]
        peak_row = {"bf16_flops": float("nan"),
                    "hbm_bytes_per_s": float("nan")}
    else:
        from benchmark.harness import peaks

        bench = run.load_json("BENCHMARK.json")
        cell = run.resolve(bench, args.workload)[0]
        if device["platform"] != "tpu" or device["count"] != cell["chips"]:
            print(f"{cell['name']} needs {cell['chips']} TPU chip(s); jax "
                  f"found {device}", file=sys.stderr)
            return 2
        peak_row = peaks.peaks_for(device["kind"])
        run.Context.log({"cell": cell["name"], "seed": args.seed,
                         "device": device,
                         "compile_cache_dir": run.enable_compile_cache()})
    result = run.run_cell(bench, ns, devices, device, peak_row)
    result["reference_skipped"] = True
    profile["result_line"] = result
    with open(os.path.join(out_dir, "profile.json"), "w") as f:
        json.dump(profile, f)
    if not args.keep_trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
