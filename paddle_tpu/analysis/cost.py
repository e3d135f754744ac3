"""Static cost model — FLOP/byte accounting and the roofline sentinel.

The audit stack could already say what a compiled program *is*
(collectives, dtypes, donation, memory); nothing said what it should
*cost*. This pass produces that number twice, from two independent
sources, and cross-checks them:

1. **Compiler-reported** (:func:`xla_cost_stats`): XLA's own
   ``compiled.cost_analysis()`` — flops / bytes-accessed /
   transcendentals for the program as actually optimized (post-fusion,
   post-partitioning). Two backend quirks this module normalizes away:
   the result arrives as a one-element list on current jax, and an
   SPMD-partitioned module reports ONE shard's cost (the report
   carries ``n_partitions`` from the executable's input shardings and
   scales by it for the cross-check). Absent / partial fields degrade
   to the jaxpr walker (``source="jaxpr"``) instead of raising — the
   same defensive posture as :func:`.memory.compiled_memory_stats`.
2. **Backend-independent** (:func:`jaxpr_cost`): a walker over the
   ClosedJaxpr with the same sub-jaxpr recursion as the dtype taint
   pass — ``dot_general``/``conv_general_dilated`` contraction
   counting, elementwise/reduce flops, transcendental census, and
   per-equation operand+result byte traffic. Loop semantics are
   explicit: XLA's cost analysis counts a while/scan body ONCE
   (verified on the tier-1 backend: a 10-trip scan of a 1024-flop dot
   reports 1029 flops), so the walker computes BOTH views —
   ``unroll_loops=False`` mirrors XLA for the cross-check, and
   ``unroll_loops=True`` multiplies scan bodies by their trip count
   for the number the device actually executes (the roofline input).

Cross-check: ``CostReport.flops_ratio`` = static-jaxpr flops over
``n_partitions``-scaled XLA flops; :data:`AGREEMENT_BAND` pins the
acceptable band, and fingerprints freeze the per-recipe ratio so it
can only drift with a reviewed golden diff.

**Roofline** (:func:`roofline`): against a :class:`ChipSpec` (peak
FLOP/s reusing :mod:`paddle_tpu.profiler.mfu`'s table + an HBM
bandwidth column), classify the program memory- vs compute-bound by
arithmetic intensity vs the ridge point and predict the device-time
floor ``max(flops/peak, bytes/bw)``. What a dispatch takes above that
floor is measured on the chip by the benchmark (``quantum_host_ms`` and
the idle shares: PERF.md section 5), never estimated here.

Budgets cap the result per recipe (``max_flops_per_token``,
``max_hbm_bytes_per_token``, ``min_arithmetic_intensity`` over
``cost_tokens_per_dispatch`` tokens) and the fingerprint carries the
cost section, so FLOP/byte drift gates exactly like collective or
memory drift.
"""
from __future__ import annotations

import jax

from .dtypes import _sub_jaxprs
from .memory import _aval_bytes

__all__ = [
    "AGREEMENT_BAND", "CHIP_SPECS", "ChipSpec", "CostReport",
    "CostStats", "RooflineReport", "analyze_cost", "jaxpr_cost",
    "roofline", "xla_cost_stats",
]

#: pinned cross-source band: static-jaxpr flops over partition-scaled
#: XLA flops must land here for every audited recipe (fingerprints
#: freeze the exact per-recipe ratio; this is the coarse sanity gate).
#: The walker counts the traced program, XLA counts the optimized one,
#: and the partition scaling assumes compute splits evenly across the
#: mesh — exact for pure TP, approximate for hybrid TP x ZeRO where
#: gathered params duplicate some work per shard. Audited ratios:
#: 0.88-1.00 on single-device micro-cases and serving quanta, 0.249 on
#: the tp2 x zero4 train step (its golden's since PR 22, when kernels
#: began to run per shard; 0.51 before) — the band bounds all of that
#: with margin while still catching an order-of-magnitude miscount.
AGREEMENT_BAND = (0.2, 2.5)


class CostStats:
    """One source's cost numbers for one program."""

    __slots__ = ("flops", "bytes_accessed", "transcendentals", "source")

    def __init__(self, flops, bytes_accessed, transcendentals, source):
        self.flops = float(flops)
        self.bytes_accessed = float(bytes_accessed)
        self.transcendentals = float(transcendentals)
        #: "xla" (compiler-reported) or "jaxpr" (walker)
        self.source = source

    def __repr__(self):
        return (f"CostStats({self.source}: {self.flops:,.0f} flops, "
                f"{self.bytes_accessed:,.0f} B, "
                f"{self.transcendentals:,.0f} transc)")


# ------------------------------------------------------------- sources
def _n_partitions(compiled):
    """Device count of the executable's input shardings (1 when the
    hook is missing/odd — single-device is the safe reading)."""
    try:
        leaves = jax.tree_util.tree_leaves(compiled.input_shardings)
        for s in leaves:
            n = len(s.device_set)
            if n >= 1:
                return int(n)
    except Exception:
        pass
    return 1


def xla_cost_stats(compiled):
    """XLA's ``cost_analysis()`` as :class:`CostStats` (per-partition
    numbers, see :func:`_n_partitions`), or ``None`` when the hook is
    absent, raises, or omits flops / bytes-accessed — the caller then
    degrades to the jaxpr walker instead of failing the audit."""
    ca = getattr(compiled, "cost_analysis", None)
    if ca is None:
        return None
    try:
        stats = ca()
    except Exception:
        return None
    if isinstance(stats, (list, tuple)):
        stats = stats[0] if stats else None
    if not isinstance(stats, dict):
        return None
    flops = stats.get("flops")
    byts = stats.get("bytes accessed")
    if not isinstance(flops, (int, float)) \
            or not isinstance(byts, (int, float)) \
            or isinstance(flops, bool) or isinstance(byts, bool):
        return None  # partial analysis: degrade, don't guess
    transc = stats.get("transcendentals")
    if not isinstance(transc, (int, float)) or isinstance(transc, bool):
        transc = 0.0
    return CostStats(flops, byts, transc, source="xla")


# equations whose flop cost is ~0 (data movement / metadata); their
# byte traffic still counts
_FREE_PRIMS = frozenset({
    "broadcast_in_dim", "reshape", "transpose", "squeeze",
    "expand_dims", "convert_element_type", "bitcast_convert_type",
    "slice", "dynamic_slice", "dynamic_update_slice", "concatenate",
    "gather", "scatter", "pad", "rev", "iota", "copy", "device_put",
    "stop_gradient", "select_and_scatter_add", "split",
})

# one transcendental per output element, tracked SEPARATELY from flops
# (mirrors XLA's 'transcendentals' field)
_TRANSCENDENTAL_PRIMS = frozenset({
    "exp", "exp2", "expm1", "log", "log1p", "log2", "tanh", "sin",
    "cos", "tan", "asin", "acos", "atan", "atan2", "sinh", "cosh",
    "asinh", "acosh", "atanh", "logistic", "erf", "erfc", "erf_inv",
    "rsqrt", "sqrt", "cbrt", "pow", "digamma", "lgamma",
})

# reductions cost ~one flop per INPUT element
_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_and", "reduce_or", "reduce_xor", "argmax", "argmin",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
    "reduce_window_sum", "reduce_window_max", "reduce_window_min",
})

# loop-carrying primitives whose body cost multiplies by trip count in
# the unrolled (device-work) view; everything else recurses x1
_SCAN_PRIMS = ("scan",)


def _elems(v):
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except (TypeError, ValueError):  # polymorphic dim
            return 0
    return n


def _dot_flops(eqn):
    """2 * out_elems * K for a dot_general (K = contracted extent)."""
    out_elems = _elems(eqn.outvars[0])
    lhs_aval = getattr(eqn.invars[0], "aval", None)
    dnums = eqn.params.get("dimension_numbers")
    k = 1
    try:
        (lhs_contract, _), _ = dnums
        for d in lhs_contract:
            k *= int(lhs_aval.shape[d])
    except Exception:
        k = 1
    return 2.0 * out_elems * k


def _conv_flops(eqn):
    """2 * out_elems * (Cin/groups * prod(kernel spatial)) — the rhs
    holds exactly those factors besides its out-feature dim."""
    out_elems = _elems(eqn.outvars[0])
    rhs_elems = _elems(eqn.invars[1])
    rhs_aval = getattr(eqn.invars[1], "aval", None)
    out_ch = 1
    try:
        dn = eqn.params.get("dimension_numbers")
        out_ch = int(rhs_aval.shape[dn.rhs_spec[0]])
    except Exception:
        shape = getattr(rhs_aval, "shape", None) or (1,)
        out_ch = max(int(max(shape)), 1)
    per_out = rhs_elems / max(out_ch, 1)
    return 2.0 * out_elems * per_out


def _leaf_cost(eqn):
    """(flops, transcendentals) for one sub-jaxpr-free equation."""
    prim = eqn.primitive.name
    if prim == "dot_general":
        return _dot_flops(eqn), 0.0
    if prim == "conv_general_dilated":
        return _conv_flops(eqn), 0.0
    if prim in _FREE_PRIMS:
        return 0.0, 0.0
    if prim in _TRANSCENDENTAL_PRIMS:
        return 0.0, float(_elems(eqn.outvars[0]))
    if prim in _REDUCE_PRIMS:
        return float(max(_elems(v) for v in eqn.invars)
                     if eqn.invars else 0), 0.0
    # default: one flop per output element (elementwise arithmetic,
    # comparisons, selects, integer ops, rng bit generation, ...)
    return float(sum(_elems(v) for v in eqn.outvars)), 0.0


def _walk_cost(jaxpr, unroll_loops):
    flops = byts = transc = 0.0
    for eqn in jaxpr.eqns:
        subs = _sub_jaxprs(eqn)
        if subs:
            trips = 1
            if unroll_loops and eqn.primitive.name in _SCAN_PRIMS:
                try:
                    trips = max(int(eqn.params.get("length", 1)), 1)
                except (TypeError, ValueError):
                    trips = 1
            elif eqn.primitive.name == "shard_map":
                # a manual region's body is ONE member's work, in both
                # views: every member of its mesh runs it
                trips = int(eqn.params["mesh"].size)
            # cond/switch branches all exist in the compiled module, so
            # both views SUM them (like XLA); while trip counts are
            # unknowable statically, so the unrolled view floors at x1
            for _closed, sub in subs:
                sf, sb, st = _walk_cost(sub, unroll_loops)
                flops += trips * sf
                byts += trips * sb
                transc += trips * st
            continue
        ef, et = _leaf_cost(eqn)
        flops += ef
        transc += et
        byts += sum(_aval_bytes(v) for v in eqn.invars)
        byts += sum(_aval_bytes(v) for v in eqn.outvars)
    return flops, byts, transc


def jaxpr_cost(closed_jaxpr, unroll_loops=True):
    """Walk a ClosedJaxpr; returns :class:`CostStats`
    (``source="jaxpr"``). ``unroll_loops=True`` (default) multiplies
    scan bodies by their trip count — the work the device executes per
    dispatch; ``False`` counts each body once, mirroring XLA's
    cost-analysis convention for the cross-check."""
    f, b, t = _walk_cost(closed_jaxpr.jaxpr, unroll_loops)
    return CostStats(f, b, t, source="jaxpr")


# -------------------------------------------------------------- report
class CostReport:
    """Both sources for one program plus the cross-check.

    ``flops`` / ``bytes_accessed`` / ``transcendentals`` are the
    PREFERRED per-dispatch numbers: the trip-unrolled jaxpr walk when
    available (device work, backend-independent), else partition-scaled
    XLA. ``flops_ratio`` cross-checks the two where both exist —
    static (body-once) jaxpr flops over ``n_partitions * xla.flops`` —
    and ``agreement_ok`` gates it against :data:`AGREEMENT_BAND`.
    """

    __slots__ = ("xla", "jaxpr", "jaxpr_static", "n_partitions")

    def __init__(self, xla, jaxpr, jaxpr_static, n_partitions=1):
        #: CostStats from cost_analysis() (per-partition) or None
        self.xla = xla
        #: CostStats from the trip-unrolled walker, or None
        self.jaxpr = jaxpr
        #: CostStats from the body-once walker (XLA convention), or None
        self.jaxpr_static = jaxpr_static
        self.n_partitions = int(n_partitions)

    @property
    def source(self):
        """Where the preferred numbers come from: "jaxpr" when the
        walker ran (the per-dispatch view), "xla" when only the
        compiler report exists, None when neither."""
        if self.jaxpr is not None:
            return "jaxpr"
        if self.xla is not None:
            return "xla"
        return None

    @property
    def flops(self):
        if self.jaxpr is not None:
            return self.jaxpr.flops
        if self.xla is not None:
            return self.xla.flops * self.n_partitions
        return None

    @property
    def bytes_accessed(self):
        if self.jaxpr is not None:
            return self.jaxpr.bytes_accessed
        if self.xla is not None:
            return self.xla.bytes_accessed * self.n_partitions
        return None

    @property
    def transcendentals(self):
        if self.jaxpr is not None:
            return self.jaxpr.transcendentals
        if self.xla is not None:
            return self.xla.transcendentals * self.n_partitions
        return None

    @property
    def arithmetic_intensity(self):
        f, b = self.flops, self.bytes_accessed
        if f is None or not b:
            return None
        return f / b

    @property
    def flops_ratio(self):
        """Static jaxpr flops / partition-scaled XLA flops (None when
        either source is missing or zero)."""
        if self.jaxpr_static is None or self.xla is None:
            return None
        denom = self.xla.flops * self.n_partitions
        if denom <= 0.0 or self.jaxpr_static.flops <= 0.0:
            return None
        return self.jaxpr_static.flops / denom

    def agreement_ok(self, band=AGREEMENT_BAND):
        """True/False when both sources exist, None when the
        cross-check is inapplicable (single-source report)."""
        r = self.flops_ratio
        if r is None:
            return None
        return band[0] <= r <= band[1]

    def per_token(self, tokens):
        """(flops_per_token, bytes_per_token) over ``tokens`` tokens
        per dispatch (None fields when the view is missing)."""
        t = max(int(tokens), 1)
        f, b = self.flops, self.bytes_accessed
        return (None if f is None else f / t,
                None if b is None else b / t)

    def summary_lines(self):
        if self.source is None:
            return ["  cost: (no view)"]
        ratio = self.flops_ratio
        line = (f"  cost [{self.source}]: {self.flops:,.0f} flops, "
                f"{self.bytes_accessed:,.0f} B accessed")
        ai = self.arithmetic_intensity
        if ai is not None:
            line += f", intensity {ai:.2f}"
        lines = [line]
        if ratio is not None:
            lines.append(
                f"  cost cross-check: jaxpr/xla flops ratio "
                f"{ratio:.3f} (x{self.n_partitions} partitions)"
                + ("" if self.agreement_ok() else
                   f" OUTSIDE band {AGREEMENT_BAND}"))
        return lines


def analyze_cost(lowered_target, jaxpr=None):
    """Both cost views over a :class:`~.ir.LoweredTarget`; returns
    :class:`CostReport`. Pass ``jaxpr`` when the caller already traced
    it (audit() shares the dtype pass's trace). Never raises: a target
    with no usable view yields an empty report."""
    try:
        compiled = lowered_target.compiled()
    except Exception:
        compiled = None
    xla = xla_cost_stats(compiled) if compiled is not None else None
    nparts = _n_partitions(compiled) if compiled is not None else 1
    if jaxpr is None:
        try:
            jaxpr = lowered_target.jaxpr()
        except Exception:
            jaxpr = None
    jx = jx_static = None
    if jaxpr is not None:
        try:
            jx = jaxpr_cost(jaxpr, unroll_loops=True)
            jx_static = jaxpr_cost(jaxpr, unroll_loops=False)
        except Exception:
            jx = jx_static = None
    return CostReport(xla, jx, jx_static, n_partitions=nparts)


# ------------------------------------------------------------ roofline
class ChipSpec:
    """Peak FLOP/s + HBM bandwidth for one chip (the roofline axes)."""

    __slots__ = ("name", "peak_flops", "hbm_bytes_per_sec")

    def __init__(self, name, peak_flops, hbm_bytes_per_sec):
        self.name = name
        self.peak_flops = float(peak_flops)
        self.hbm_bytes_per_sec = float(hbm_bytes_per_sec)

    @property
    def ridge_intensity(self):
        """FLOP/byte above which the chip is compute-bound."""
        return self.peak_flops / self.hbm_bytes_per_sec

    def __repr__(self):
        return (f"ChipSpec({self.name!r}, {self.peak_flops:.3g} FLOP/s,"
                f" {self.hbm_bytes_per_sec:.3g} B/s)")


def _chip_specs():
    # peak column shared with profiler.mfu's table (one source of
    # truth for FLOP/s); the HBM column is this module's addition
    # (public spec sheets, bytes/sec)
    from ..profiler.mfu import _PEAKS

    bw = {
        "v2": 700e9,
        "v3": 900e9,
        "v4": 1228e9,
        "v5e": 819e9,
        "v5p": 2765e9,
        "v6e": 1638e9,
    }
    alias = {"v5 lite": "v5e", "v6 lite": "v6e"}
    specs = {}
    for kind, peak in _PEAKS.items():
        key = alias.get(kind, kind)
        if key in bw and key not in specs:
            specs[key] = ChipSpec(key, peak, bw[key])
    return specs


#: chip roofline table; extend/override by constructing a ChipSpec
CHIP_SPECS = _chip_specs()

#: default spec for CLI/bench floors (current-generation efficiency
#: part; every consumer takes a chip override)
DEFAULT_CHIP = "v5e"


class RooflineReport:
    """One program placed on one chip's roofline."""

    __slots__ = ("chip", "flops", "bytes_accessed", "intensity",
                 "bound", "device_floor_s")

    def __init__(self, chip, flops, bytes_accessed, intensity, bound,
                 device_floor_s):
        self.chip = chip
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        #: achieved FLOP/byte (0 when byte traffic is unknown)
        self.intensity = intensity
        #: "compute" | "memory"
        self.bound = bound
        #: max(flops/peak, bytes/bw) — the time the device CANNOT beat
        self.device_floor_s = device_floor_s

    def __repr__(self):
        return (f"RooflineReport({self.chip.name}: "
                f"{self.bound}-bound, intensity {self.intensity:.2f} "
                f"vs ridge {self.chip.ridge_intensity:.1f}, floor "
                f"{self.device_floor_s * 1e6:.2f} us)")


def roofline(flops, bytes_accessed, chip=DEFAULT_CHIP):
    """Place (flops, bytes) on ``chip``'s roofline; returns
    :class:`RooflineReport`. ``chip`` is a :class:`ChipSpec` or a key
    of :data:`CHIP_SPECS`."""
    spec = chip if isinstance(chip, ChipSpec) else CHIP_SPECS[chip]
    flops = float(flops)
    byts = float(bytes_accessed)
    intensity = (flops / byts) if byts > 0 else 0.0
    bound = ("compute" if intensity >= spec.ridge_intensity
             else "memory")
    floor = max(flops / spec.peak_flops,
                byts / spec.hbm_bytes_per_sec)
    return RooflineReport(spec, flops, byts, intensity, bound, floor)
