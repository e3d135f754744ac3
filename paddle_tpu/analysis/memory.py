"""Static peak-memory estimation — the HBM dimension of the audit.

Two complementary views, both computed at compile time (no execution):

1. **Compiler-reported** (:func:`compiled_memory_stats`): XLA's own
   buffer-assignment numbers via ``compiled.memory_analysis()`` —
   temp / argument / output / alias bytes for the program as actually
   scheduled. Honest (it IS the allocator's plan) but backend-shaped:
   the CPU tier-1 numbers differ from a TPU's, so budgets pin the
   tier-1 backend and a device run re-pins its own goldens.
2. **Backend-independent** (:func:`jaxpr_liveness`): a liveness walk
   over the ClosedJaxpr — every buffer is born at its defining
   equation, dies after its last use, undonated inputs and all outputs
   live for the whole program — yielding peak live bytes, the largest
   single buffer, and what donation saves (peak without donation minus
   peak with). This is the number a *refactor* moves: it only depends
   on the traced program, not on XLA's scheduling of it, so it drifts
   exactly when the graph drifts.

Both are surfaced on :class:`~.budget.AuditReport` as ``.memory`` and
capped by the ``max_temp_bytes`` / ``max_peak_live_bytes`` /
``max_output_bytes`` Budget fields.
"""
from __future__ import annotations

from jax.extend.core import Var

__all__ = [
    "LivenessStats", "MemoryReport", "analyze_memory",
    "compiled_memory_stats", "jaxpr_liveness",
]

_INLINE_CALL_PRIMS = ("jit", "closed_call", "core_call")


def _aval_bytes(v):
    """Static byte size of a var/literal's aval (0 for tokens and
    abstract-shaped values)."""
    aval = getattr(v, "aval", None)
    if aval is None:
        return 0
    dtype = getattr(aval, "dtype", None)
    shape = getattr(aval, "shape", None)
    if dtype is None or shape is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except (TypeError, ValueError):  # polymorphic dim
            return 0
    return n * dtype.itemsize


class LivenessStats:
    """Backend-independent liveness numbers for one jaxpr."""

    __slots__ = ("peak_live_bytes", "peak_live_bytes_no_donation",
                 "largest_buffer_bytes", "n_buffers", "input_bytes",
                 "output_bytes")

    def __init__(self, peak_live_bytes, peak_live_bytes_no_donation,
                 largest_buffer_bytes, n_buffers, input_bytes,
                 output_bytes):
        self.peak_live_bytes = peak_live_bytes
        self.peak_live_bytes_no_donation = peak_live_bytes_no_donation
        self.largest_buffer_bytes = largest_buffer_bytes
        self.n_buffers = n_buffers
        self.input_bytes = input_bytes
        self.output_bytes = output_bytes

    @property
    def donation_savings_bytes(self):
        """Peak-live bytes donation saves (0 when nothing is donated or
        the donated inputs die after the peak anyway)."""
        return self.peak_live_bytes_no_donation - self.peak_live_bytes

    def __repr__(self):
        return (f"LivenessStats(peak={self.peak_live_bytes:,}B, "
                f"largest={self.largest_buffer_bytes:,}B, "
                f"donation_saves={self.donation_savings_bytes:,}B)")


def _inline_single_call(jaxpr, donated_vars):
    """Descend through a jaxpr that is one big jit/call eqn (the shape
    ``jax.make_jaxpr(jax.jit(f))`` produces) so the walk sees the real
    body; translates the donated-invar set positionally."""
    while len(jaxpr.eqns) == 1 \
            and jaxpr.eqns[0].primitive.name in _INLINE_CALL_PRIMS:
        eqn = jaxpr.eqns[0]
        closed = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        sub = getattr(closed, "jaxpr", closed)
        if sub is None or not hasattr(sub, "invars") \
                or len(sub.invars) != len(eqn.invars):
            break
        donated_vars = {
            sv for sv, ev in zip(sub.invars, eqn.invars)
            if ev in donated_vars
        }
        jaxpr = sub
    return jaxpr, donated_vars


def jaxpr_liveness(closed_jaxpr, donated=()):
    """Liveness walk over ``closed_jaxpr``; ``donated`` is the set of
    top-level input indices whose buffers the program may reuse (from
    the donation audit). Returns :class:`LivenessStats`.

    Model: equations run in program order; a value is live from its
    defining equation through its last use. Undonated inputs, consts,
    and program outputs are live for the entire program (the caller
    retains them / XLA must materialize them); donated inputs die at
    their last use. Peak is the max over equations of the live-byte
    sum, with an equation's inputs and outputs live simultaneously
    (the op reads and writes in one step).
    """
    jaxpr = closed_jaxpr.jaxpr
    donated_vars = {
        jaxpr.invars[i] for i in donated if i < len(jaxpr.invars)
    }
    jaxpr, donated_vars = _inline_single_call(jaxpr, donated_vars)

    n_eqns = len(jaxpr.eqns)
    birth = {}   # var -> eqn index it is defined at (-1 for inputs)
    last_use = {}
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        birth[v] = -1
        last_use[v] = -1
    for i, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if isinstance(v, Var) and v in birth:
                last_use[v] = i
        for v in eqn.outvars:
            birth[v] = i
            last_use[v] = i
    # whole-program lifetimes: outputs, consts, undonated inputs
    for v in jaxpr.outvars:
        if isinstance(v, Var) and v in birth:
            last_use[v] = n_eqns
    for v in list(jaxpr.invars) + list(jaxpr.constvars):
        if v not in donated_vars:
            last_use[v] = n_eqns

    sizes = {v: _aval_bytes(v) for v in birth}
    invar_set = set(jaxpr.invars)
    input_bytes = sum(sizes[v] for v in invar_set)
    output_bytes = sum(
        _aval_bytes(v) for v in jaxpr.outvars if hasattr(v, "aval"))

    def peak(honor_donation):
        # sweep a diff array over eqn steps 0..n_eqns-1
        delta = [0] * (n_eqns + 2)
        for v, b in birth.items():
            end = last_use[v]
            if not honor_donation and v in invar_set:
                end = n_eqns
            start = max(b, 0)
            end = max(end, start)  # dead values live through their eqn
            delta[start] += sizes[v]
            delta[min(end, n_eqns) + 1] -= sizes[v]
        best = cur = 0
        for i in range(n_eqns + 1):
            cur += delta[i]
            best = max(best, cur)
        return best

    with_don = peak(True)
    without_don = peak(False)
    return LivenessStats(
        peak_live_bytes=with_don,
        peak_live_bytes_no_donation=max(without_don, with_don),
        largest_buffer_bytes=max(sizes.values(), default=0),
        n_buffers=len(sizes),
        input_bytes=input_bytes,
        output_bytes=output_bytes,
    )


def compiled_memory_stats(compiled):
    """XLA buffer-assignment numbers for a compiled executable, as a
    plain dict (``None`` when the backend offers no
    ``memory_analysis`` — the audit then relies on the liveness walk
    alone)."""
    ma = getattr(compiled, "memory_analysis", None)
    if ma is None:
        return None
    try:
        stats = ma()
    except Exception:
        return None
    if stats is None:
        return None
    out = {}
    for field in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        val = getattr(stats, field, None)
        if val is not None:
            out[field.replace("_size_in_bytes", "_bytes")] = int(val)
    return out or None


class MemoryReport:
    """Both memory views for one lowered target. ``compiler`` is the
    dict from :func:`compiled_memory_stats` (or None); ``liveness`` is
    :class:`LivenessStats` (or None when the target has no jaxpr)."""

    __slots__ = ("compiler", "liveness")

    def __init__(self, compiler, liveness):
        self.compiler = compiler
        self.liveness = liveness

    @property
    def temp_bytes(self):
        return None if self.compiler is None else \
            self.compiler.get("temp_bytes")

    @property
    def output_bytes(self):
        return None if self.compiler is None else \
            self.compiler.get("output_bytes")

    @property
    def peak_live_bytes(self):
        return None if self.liveness is None else \
            self.liveness.peak_live_bytes

    def summary_lines(self):
        lines = []
        if self.compiler is not None:
            lines.append("  memory (compiler): " + ", ".join(
                f"{k.replace('_bytes', '')} {v:,} B"
                for k, v in sorted(self.compiler.items())))
        if self.liveness is not None:
            lv = self.liveness
            lines.append(
                f"  memory (liveness): peak live {lv.peak_live_bytes:,}"
                f" B, largest buffer {lv.largest_buffer_bytes:,} B, "
                f"donation saves {lv.donation_savings_bytes:,} B")
        return lines


def analyze_memory(lowered_target, donated_indices=(), jaxpr=None):
    """Run both memory views over a :class:`~.ir.LoweredTarget`;
    returns :class:`MemoryReport`. ``donated_indices`` come from the
    donation audit (the args whose StableHLO attrs mark them donated),
    so the liveness walk frees exactly the buffers XLA may reuse.
    Pass ``jaxpr`` when the caller already traced it (audit() shares
    the dtype pass's trace) to skip the re-trace."""
    compiler = compiled_memory_stats(lowered_target.compiled())
    if jaxpr is None:
        try:
            jaxpr = lowered_target.jaxpr()
        except Exception:
            jaxpr = None
    liveness = (jaxpr_liveness(jaxpr, donated=donated_indices)
                if jaxpr is not None else None)
    return MemoryReport(compiler, liveness)
