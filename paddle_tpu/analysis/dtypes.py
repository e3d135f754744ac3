"""Dtype-promotion auditor.

A bf16 training/serving graph loses its MXU rate the moment one matmul
silently runs in f32 — usually an upstream ``convert_element_type``
someone added for numerical comfort that then taints the whole
contraction. The auditor walks the ClosedJaxpr (pre-partitioning, so
op provenance is still legible) with a taint dataflow:

- taint sources: bf16 inputs and bf16 consts (params, activations);
- propagation: any equation with a tainted operand taints its outputs,
  recursing through pjit / scan / while / cond / checkpoint /
  custom-grad sub-jaxprs by positional operand alignment;
- violations: ``dot_general`` / ``conv_general_dilated`` equations
  whose OUTPUT is f32 while a tainted (bf16-origin) value feeds them —
  i.e. compute that should have stayed on the bf16 path but got
  promoted.

Intentional f32 islands (loss logsumexp, optimizer master math on f32
state) don't trip it: their inputs are either untainted f32 state or
the flagged op set is matmul/conv only, not elementwise.

A SECOND, independent taint runs for int8 sources (quantized serving:
int8 weights, int8 KV pools): every matmul/conv reachable from an int8
input/const is collected in ``DtypeReport.int8_compute`` — the
POSITIVE evidence a quantized graph actually feeds its contractions
from int8 storage (budgets assert a MINIMUM via ``min_int8_matmuls``,
the inverse direction of the f32 cap). Kept out of the fingerprint
dict so pre-int8 goldens stay byte-identical.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.extend.core import Literal, Var

__all__ = ["DtypeReport", "F32ComputeEvent", "audit_dtype_promotion"]

_COMPUTE_PRIMS = ("dot_general", "conv_general_dilated")
_SOURCE_DTYPES = (jnp.bfloat16, jnp.float16)
_I8_SOURCE_DTYPES = (jnp.int8,)


class F32ComputeEvent:
    """One f32 matmul/conv reachable from a low-precision source."""

    __slots__ = ("primitive", "out_shape", "in_dtypes", "path")

    def __init__(self, primitive, out_shape, in_dtypes, path):
        self.primitive = primitive
        self.out_shape = tuple(out_shape)
        self.in_dtypes = tuple(in_dtypes)
        self.path = path  # e.g. "pjit/scan" — enclosing sub-jaxpr chain

    def __repr__(self):
        return (f"F32ComputeEvent({self.primitive} -> "
                f"f32{list(self.out_shape)} from {self.in_dtypes} "
                f"at {self.path or '<top>'})")


class DtypeReport:
    __slots__ = ("f32_compute", "upcasts", "int8_compute")

    def __init__(self, f32_compute, upcasts, int8_compute=None):
        #: list[F32ComputeEvent]
        self.f32_compute = f32_compute
        #: count of bf16/f16 -> f32 convert_element_type equations
        self.upcasts = upcasts
        #: list[F32ComputeEvent] — matmuls/convs fed (transitively)
        #: from int8 storage; evidence the quantized path is live
        self.int8_compute = int8_compute if int8_compute is not None \
            else []


def _sub_jaxprs(eqn):
    """Every (sub_jaxpr, operand_alignment) pair nested in an equation's
    params. Alignment maps sub-jaxpr invars to eqn invars positionally
    from the END (scan: consts+carry+xs vs consts+init+xs line up 1:1;
    cond: branches take eqn.invars[1:]; pjit: exact)."""
    out = []
    for v in eqn.params.values():
        vals = v if isinstance(v, (list, tuple)) else [v]
        for item in vals:
            jx = getattr(item, "jaxpr", item)
            if hasattr(jx, "eqns") and hasattr(jx, "invars"):
                out.append((item, jx))
    return out


def _walk(jaxpr, tainted, events, path, seen_upcasts,
          i8_tainted=None, i8_events=None):
    if i8_tainted is None:
        i8_tainted = set()
    for eqn in jaxpr.eqns:
        in_taint = [
            (isinstance(v, Var) and v in tainted)
            or _is_source_lit(v)
            for v in eqn.invars
        ]
        any_taint = any(in_taint)
        in_i8 = [
            isinstance(v, Var) and v in i8_tainted
            for v in eqn.invars
        ]
        any_i8 = any(in_i8)
        prim = eqn.primitive.name

        if prim == "convert_element_type":
            src = _aval(eqn.invars[0])
            dst = _aval(eqn.outvars[0])
            if (src is not None and dst is not None
                    and src.dtype in _SOURCE_DTYPES
                    and dst.dtype == jnp.float32):
                seen_upcasts[0] += 1

        if prim in _COMPUTE_PRIMS and any_taint:
            out_aval = _aval(eqn.outvars[0])
            if out_aval is not None and out_aval.dtype == jnp.float32:
                events.append(F32ComputeEvent(
                    primitive=prim,
                    out_shape=out_aval.shape,
                    in_dtypes=[
                        str(_aval(v).dtype) if _aval(v) is not None else "?"
                        for v in eqn.invars
                    ],
                    path=path,
                ))

        if prim in _COMPUTE_PRIMS and any_i8 and i8_events is not None:
            out_aval = _aval(eqn.outvars[0])
            i8_events.append(F32ComputeEvent(
                primitive=prim,
                out_shape=(out_aval.shape if out_aval is not None
                           else ()),
                in_dtypes=[
                    str(_aval(v).dtype) if _aval(v) is not None else "?"
                    for v in eqn.invars
                ],
                path=path,
            ))

        for closed, sub in _sub_jaxprs(eqn):
            sub_taint = set()
            sub_i8 = set()
            # align sub invars with eqn invars from the end: leading
            # extras on either side are consts/predicates
            n = min(len(sub.invars), len(eqn.invars))
            for sv, ev, et, e8 in zip(sub.invars[-n:], eqn.invars[-n:],
                                      in_taint[-n:], in_i8[-n:]):
                if et or _is_source_lit(ev):
                    sub_taint.add(sv)
                if e8:
                    sub_i8.add(sv)
            # consts of a closed jaxpr can be bf16 arrays too
            consts = getattr(closed, "consts", None) or []
            for cv, c in zip(getattr(sub, "constvars", []), consts):
                if getattr(c, "dtype", None) in _SOURCE_DTYPES:
                    sub_taint.add(cv)
                if getattr(c, "dtype", None) in _I8_SOURCE_DTYPES:
                    sub_i8.add(cv)
            sub_path = f"{path}/{prim}" if path else prim
            _walk(sub, sub_taint, events, sub_path, seen_upcasts,
                  sub_i8, i8_events)
            # outputs of a sub-jaxpr-carrying eqn: tainted if any input
            # was (conservative but local)

        if any_taint:
            tainted.update(eqn.outvars)
        if any_i8:
            i8_tainted.update(eqn.outvars)


def _aval(v):
    return getattr(v, "aval", None)


def _is_source_lit(v):
    if not isinstance(v, Literal):
        return False
    a = _aval(v)
    return a is not None and getattr(a, "dtype", None) in _SOURCE_DTYPES


def audit_dtype_promotion(closed_jaxpr):
    """Run the taint walk over a ClosedJaxpr; returns
    :class:`DtypeReport`. Taint sources are every bf16/f16 input and
    const (f32-promotion direction) and every int8 input and const
    (quantized-compute evidence direction)."""
    jaxpr = closed_jaxpr.jaxpr
    tainted = set()
    i8_tainted = set()
    for v in jaxpr.invars:
        a = _aval(v)
        dt = getattr(a, "dtype", None) if a is not None else None
        if dt in _SOURCE_DTYPES:
            tainted.add(v)
        if dt in _I8_SOURCE_DTYPES:
            i8_tainted.add(v)
    for cv, c in zip(jaxpr.constvars, closed_jaxpr.consts):
        if getattr(c, "dtype", None) in _SOURCE_DTYPES:
            tainted.add(cv)
        if getattr(c, "dtype", None) in _I8_SOURCE_DTYPES:
            i8_tainted.add(cv)
    events = []
    i8_events = []
    upcasts = [0]
    _walk(jaxpr, tainted, events, "", upcasts, i8_tainted, i8_events)
    return DtypeReport(events, upcasts[0], i8_events)
