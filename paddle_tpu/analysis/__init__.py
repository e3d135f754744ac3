"""paddle_tpu.analysis — static auditing of compiled programs and
framework source (the CINN-style compiler-level verification layer of
PAPER.md's blueprint, grown from tests/test_zero_ir.py's one-off IR
string checks into a first-class subsystem).

Five layers:

1. **IR audit passes** over any jitted callable's jaxpr / StableHLO /
   compiled HLO: collective-communication census
   (:func:`collective_census`), involuntary-remat detection
   (:func:`detect_involuntary_remat`), dtype-promotion audit
   (:func:`audit_dtype_promotion`), buffer-donation audit
   (:func:`audit_donation`), host-sync census
   (:func:`host_sync_census` — python callbacks / infeed / outfeed in
   the compiled module), static memory estimation
   (:func:`analyze_memory` — XLA buffer-assignment stats plus a
   backend-independent jaxpr liveness walk), sharding-layout audit
   (:func:`audit_sharding` — per-arg ``mhlo.sharding`` attrs) — all
   run at once by :func:`audit`.
2. **Budgets**: :class:`Budget` + :func:`check_budget` enforce
   declarative per-recipe expectations ("0 remat fallbacks, <=N
   all-gathers, 0 f32 matmuls, everything donated, peak live bytes
   bounded, no replicated weight leaves"); the real recipes live in
   :mod:`.recipes`.
3. **Graph fingerprints**: :mod:`.fingerprint` freezes each recipe's
   full audit summary behind a golden (``tests/goldens/<name>.json``)
   compared in tier-1 — the drift gate that catches silent graph
   changes budgets are too coarse for.
4. **Source linter**: ``python -m paddle_tpu.analysis.lint paddle_tpu/
   scripts/`` flags tracer hazards in the framework source itself
   (host syncs in jit-reachable code, Python control flow on traced
   values, np.* on tensors, mutable default args).
5. **Static cost model & roofline**: :mod:`.cost` — per-program
   FLOP/byte accounting from BOTH XLA's ``cost_analysis()`` and a
   backend-independent jaxpr walker (:func:`analyze_cost` cross-checks
   them against the pinned agreement band), chip rooflines
   (:func:`roofline` — arithmetic intensity, memory/compute-bound,
   the ``max(flops/peak, bytes/bw)`` device-time floor). ``--cost``
   gates every recipe's cross-source agreement; the per-recipe caps
   ride the budgets and the exact numbers ride the golden fingerprints.

None of this measures speed: times, rates and idle shares come from
``benchmark/run.py`` on the chip (``PERF_LEDGER.jsonl``, ``PERF.md``).

CLI: ``python -m paddle_tpu.analysis`` audits the registered recipes
(``--check`` enforces budgets, ``--fingerprint`` compares goldens,
``--update-goldens`` regenerates them, ``--cost`` prints the
roofline table and gates cross-source agreement).
"""
from .ir import LoweredTarget, lower_target, capture_compile_stderr
from .collectives import (
    COLLECTIVE_KINDS, CollectiveStats, collective_census,
    reduce_scatter_pattern,
)
from .remat import RematEvent, detect_involuntary_remat
from .dtypes import DtypeReport, F32ComputeEvent, audit_dtype_promotion
from .donation import ArgDonation, DonationReport, audit_donation
from .hostsync import HostSyncStats, host_sync_census
from .memory import (
    LivenessStats, MemoryReport, analyze_memory, compiled_memory_stats,
    jaxpr_liveness,
)
from .sharding import ArgSharding, ShardingReport, audit_sharding
from .fingerprint import (
    FINGERPRINT_VERSION, FingerprintMismatch, check_recipe_fingerprint,
    compare_fingerprint, fingerprint_report, load_golden, save_golden,
)
from .budget import (
    AuditReport, Budget, BudgetViolation, audit, check_budget,
)
from .recipes import RECIPES, Recipe, build as build_recipe, \
    run as run_recipe
from .lint import LintViolation, lint_paths, lint_source
from .cost import (
    AGREEMENT_BAND, CHIP_SPECS, ChipSpec, CostReport, CostStats,
    RooflineReport, analyze_cost, jaxpr_cost, roofline, xla_cost_stats,
)

__all__ = [
    # ir
    "LoweredTarget", "lower_target", "capture_compile_stderr",
    # passes
    "COLLECTIVE_KINDS", "CollectiveStats", "collective_census",
    "reduce_scatter_pattern", "RematEvent", "detect_involuntary_remat",
    "DtypeReport", "F32ComputeEvent", "audit_dtype_promotion",
    "ArgDonation", "DonationReport", "audit_donation",
    "HostSyncStats", "host_sync_census",
    "LivenessStats", "MemoryReport", "analyze_memory",
    "compiled_memory_stats", "jaxpr_liveness",
    "ArgSharding", "ShardingReport", "audit_sharding",
    # fingerprints
    "FINGERPRINT_VERSION", "FingerprintMismatch",
    "check_recipe_fingerprint", "compare_fingerprint",
    "fingerprint_report", "load_golden", "save_golden",
    # budgets
    "AuditReport", "Budget", "BudgetViolation", "audit", "check_budget",
    "RECIPES", "Recipe", "build_recipe", "run_recipe",
    # linter
    "LintViolation", "lint_paths", "lint_source",
    # cost model & roofline
    "AGREEMENT_BAND", "CHIP_SPECS", "ChipSpec", "CostReport",
    "CostStats", "RooflineReport", "analyze_cost", "jaxpr_cost",
    "roofline", "xla_cost_stats",
]
