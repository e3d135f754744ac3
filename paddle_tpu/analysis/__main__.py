"""Graph-audit CLI::

    python -m paddle_tpu.analysis                    # audit every recipe
    python -m paddle_tpu.analysis --recipe NAME      # just one
    python -m paddle_tpu.analysis --check            # enforce budgets
    python -m paddle_tpu.analysis --fingerprint      # compare goldens
    python -m paddle_tpu.analysis --update-goldens   # regenerate them
    python -m paddle_tpu.analysis --cost [--chip v5e]  # roofline gate
    python -m paddle_tpu.analysis --json             # machine-readable

Audits the registered recipes (see .recipes) — lowering + compiling
each program and printing the collective census, remat events, dtype
findings, donation coverage, memory estimate, and sharding layout.
``--check`` additionally enforces each recipe's budget,
``--fingerprint`` compares each live fingerprint against its golden
(tests/goldens/<recipe>.json, or ``--goldens-dir``), and ``--cost``
prints the static cost table (FLOPs, bytes, intensity, roofline floor
on ``--chip``) while gating that both cost sources populated and agree
within the pinned band; any of the three exits non-zero on a
violation/drift (scripts/check_graphs.sh runs all of them plus the
linter). What a dispatch takes on the chip is the benchmark's to
measure (``benchmark/run.py``, ``PERF.md``), not this table's. After an
INTENTIONAL graph change run ``--update-goldens`` and review the
goldens' git diff. Source linting is the sibling CLI:
``python -m paddle_tpu.analysis.lint paddle_tpu/ scripts/``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from . import recipes
from .budget import BudgetViolation
from .cost import AGREEMENT_BAND, CHIP_SPECS, DEFAULT_CHIP, roofline
from .fingerprint import (
    FingerprintMismatch, check_recipe_fingerprint, fingerprint_report,
    save_golden,
)


def _cost_gate(report, chip):
    """Roofline/table lines + gate verdict for one audited recipe.
    ``"ok"`` requires BOTH cost sources populated and the cross-source
    flops ratio inside :data:`AGREEMENT_BAND`; anything else is the
    violation line (the caller counts it as a failure)."""
    c = getattr(report, "cost", None)
    lines = []
    if c is None or c.flops is None:
        return ("no cost view (neither cost_analysis nor a jaxpr)",
                lines)
    rl = roofline(c.flops, c.bytes_accessed, chip=chip)
    lines.append(
        f"  roofline [{rl.chip.name}]: intensity {rl.intensity:.2f} "
        f"FLOP/B ({rl.bound}-bound, ridge "
        f"{rl.chip.ridge_intensity:.0f}), device floor "
        f"{rl.device_floor_s * 1e6:.2f} us/dispatch")
    if c.xla is None:
        return "cost source missing: no XLA cost_analysis", lines
    if c.jaxpr is None:
        return "cost source missing: no jaxpr walk", lines
    if not c.agreement_ok():
        return (f"cross-source flops ratio {c.flops_ratio:.3f} outside "
                f"the pinned band {AGREEMENT_BAND}", lines)
    return "ok", lines


def _report_json(name, report, ok, violations, fp_status=None,
                 cost_status=None, chip=None):
    out = {
        "recipe": name,
        "budget_ok": ok,
        "violations": violations,
        "collectives": {
            k: {"count": report.collectives[k].count,
                "bytes": report.collectives[k].bytes}
            for k in sorted(report.collectives)
        },
        "involuntary_remat": len(report.remat_events),
        "f32_matmuls_from_bf16": (
            len(report.dtype.f32_compute)
            if report.dtype is not None else None),
        "bf16_to_f32_upcasts": (
            report.dtype.upcasts if report.dtype is not None else None),
        "donated_args": report.donation.donated_count,
        "undonated_donatable_bytes": report.donation.undonated_bytes,
    }
    if report.memory is not None:
        out["memory"] = {
            "compiler": report.memory.compiler,
            "peak_live_bytes": report.memory.peak_live_bytes,
        }
    if report.sharding is not None:
        out["sharding"] = report.sharding.summary_dict()
    cost = getattr(report, "cost", None)
    if cost is not None and cost.source is not None:
        out["cost"] = {
            "source": cost.source,
            "flops": cost.flops,
            "bytes_accessed": cost.bytes_accessed,
            "arithmetic_intensity": cost.arithmetic_intensity,
            "flops_ratio": cost.flops_ratio,
            "n_partitions": cost.n_partitions,
        }
        if chip is not None:
            rl = roofline(cost.flops, cost.bytes_accessed, chip=chip)
            out["cost"]["roofline"] = {
                "chip": rl.chip.name, "bound": rl.bound,
                "device_floor_us": rl.device_floor_s * 1e6,
            }
    if cost_status is not None:
        out["cost_gate"] = cost_status
    if fp_status is not None:
        out["fingerprint"] = fp_status
    return out


_REEXEC_GUARD = "_PADDLE_TPU_ANALYSIS_REEXEC"


def _ensure_mesh_devices(argv, need=8):
    """The TP x ZeRO recipes need an 8-device mesh. Counting the devices
    initializes the jax backend, so on a too-small host platform the
    only way to grow it is to re-exec ourselves with the conftest trick
    (--xla_force_host_platform_device_count) set in the environment.
    Inert on machines that already expose enough devices."""
    import jax

    if jax.device_count() >= need or os.environ.get(_REEXEC_GUARD):
        return
    flag = f"--xla_force_host_platform_device_count={need}"
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    env[_REEXEC_GUARD] = "1"
    cmd = [sys.executable, "-m", "paddle_tpu.analysis"] + list(
        argv if argv is not None else sys.argv[1:])
    os.execve(sys.executable, cmd, env)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="jaxpr/StableHLO graph auditor over the registered "
                    "recipe programs")
    ap.add_argument("--recipe", action="append", default=None,
                    choices=sorted(recipes.RECIPES),
                    help="recipe(s) to audit (default: all)")
    ap.add_argument("--check", action="store_true",
                    help="enforce each recipe's budget; exit 1 on any "
                         "violation")
    ap.add_argument("--fingerprint", action="store_true",
                    help="compare each recipe's live fingerprint "
                         "against its checked-in golden; exit 1 on "
                         "drift")
    ap.add_argument("--update-goldens", action="store_true",
                    help="write each audited recipe's fingerprint as "
                         "the new golden (review the git diff!)")
    ap.add_argument("--goldens-dir", default=None,
                    help="golden directory (default: tests/goldens)")
    ap.add_argument("--cost", action="store_true",
                    help="print the static cost/roofline table and "
                         "gate cross-source agreement; exit 1 when a "
                         "source is missing or the flops ratio leaves "
                         "the pinned band")
    ap.add_argument("--chip", default=DEFAULT_CHIP,
                    choices=sorted(CHIP_SPECS),
                    help="chip spec for the roofline floor "
                         f"(default: {DEFAULT_CHIP})")
    ap.add_argument("--json", action="store_true",
                    help="one JSON object per recipe on stdout "
                         "(sorted keys)")
    args = ap.parse_args(argv)

    names = args.recipe or sorted(recipes.RECIPES)
    _ensure_mesh_devices(argv)
    failures = 0
    for name in names:
        recipe = recipes.build(name)
        try:
            ok, violations = True, []
            if args.check:
                try:
                    report = recipe.check()
                except BudgetViolation as e:
                    report = e.report
                    ok, violations = False, e.violations
                    failures += 1
            else:
                report = recipe.audit()

            cost_status, cost_lines = None, []
            if args.cost:
                cost_status, cost_lines = _cost_gate(report, args.chip)
                if cost_status != "ok":
                    failures += 1

            fp_status, fp_diff = None, []
            if args.update_goldens:
                path = save_golden(
                    fingerprint_report(report, name=name), name,
                    goldens_dir=args.goldens_dir)
                fp_status = f"golden updated: {path}"
            elif args.fingerprint:
                try:
                    check_recipe_fingerprint(
                        name, report, goldens_dir=args.goldens_dir)
                    fp_status = "ok"
                except FingerprintMismatch as e:
                    fp_status = "drift"
                    fp_diff = e.diff
                    failures += 1

            if args.json:
                print(json.dumps(
                    _report_json(
                        name, report, ok, violations,
                        fp_status=(fp_status if not fp_diff else
                                   {"status": fp_status,
                                    "diff": fp_diff}),
                        cost_status=cost_status,
                        chip=args.chip if args.cost else None),
                    sort_keys=True))
            else:
                print(report.summary())
                if args.check:
                    print(f"  budget [{recipe.budget.name}]: "
                          + ("OK" if ok else "VIOLATED"))
                    for ln in violations:
                        print(f"    ! {ln}")
                if cost_status is not None:
                    for ln in cost_lines:
                        print(ln)
                    print("  cost gate: "
                          + ("OK" if cost_status == "ok"
                             else f"FAILED — {cost_status}"))
                if fp_status is not None:
                    print(f"  fingerprint: "
                          + ("OK" if fp_status == "ok" else fp_status))
                    for ln in fp_diff:
                        print(f"    ! {ln}")
                print()
        finally:
            recipe.close()
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
