"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle seconds, seconds per device operation
under ``<jitted program>/<op or kernel>``, idle gaps attributed to the
benchmark's own spans, and collective time that no compute hides.

A TPU device plane (``/device:TPU:n``) carries the lines ``XLA Modules``
(one event per executed program), ``XLA Ops`` (the operations the core ran,
one at a time) and ``Async XLA Ops`` (copies and collectives in flight).
Busy is the union of ``XLA Ops`` events. Host spans are the benchmark's
``TraceAnnotation``s on the host plane, on the same clock as the device
(to within about a millisecond). Checked by ``selfcheck.py`` against a small
recorded trace with known sums. Needs nothing but ``jax`` and ``numpy``.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

_OP = re.compile(r"^%?([A-Za-z0-9_\-.]+?)(?:\.\d+)?(?:\s|=|$)")
# ops that only contain other ops of the same line: counting them would
# count their bodies twice (busy time is a union and is not affected)
_CONTAINERS = {"while", "conditional", "call"}
_COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast|psum|pmax|pmin|ppermute|all_gather|all_to_all)")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def module_name(event_name):
    """``jit_quantum(1569...)`` -> ``jit_quantum``."""
    return event_name.split("(")[0]


def _merge(starts, ends):
    """Union of intervals as sorted, disjoint (starts, ends)."""
    if len(starts) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts, float)[order], np.asarray(ends, float)[order]
    reach = np.maximum.accumulate(e)
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


class Cover:
    """A union of intervals that answers how much of [a, b] it covers."""

    def __init__(self, starts, ends):
        self.s, self.e = _merge(starts, ends)
        self.cum = np.concatenate([[0.0], np.cumsum(self.e - self.s)])

    def total(self):
        return float(self.cum[-1])

    def _before(self, t):
        """Covered length to the left of each ``t``."""
        i = np.searchsorted(self.s, t, side="right") - 1
        j = np.maximum(i, 0)
        part = np.clip(t - self.s[j], 0.0, self.e[j] - self.s[j])
        return np.where(i >= 0, self.cum[j] + part, 0.0)

    def within(self, a, b):
        a, b = np.asarray(a, float), np.asarray(b, float)
        if len(self.s) == 0:
            return np.zeros_like(a)
        return self._before(b) - self._before(a)


def _innermost(spans, w0, w1):
    """Disjoint segments of [w0, w1] labelled by the shortest span that
    covers them (``outside-spans`` where none does)."""
    cuts = sorted({w0, w1, *[min(max(t, w0), w1)
                             for _, s, e in spans for t in (s, e)]})
    out = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        mid = (a + b) / 2
        covering = [(e - s, n) for n, s, e in spans if s <= mid < e]
        out.append((min(covering)[1] if covering else "outside-spans", a, b))
    return out


def reduce_trace(path, span_rows=(), window_span="window"):
    """``span_rows``: the benchmark's [name, start, end] rows recorded while
    the trace ran, in order; an annotation in the trace carries the part of
    the name before ``:``, and the k-th annotation of a base name takes the
    k-th row's full name. The span named ``window_span`` bounds the window
    (else the device events do). All seconds are averages over the device
    planes.
    """
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    base_rows = {}
    for name, _, _ in span_rows:
        base_rows.setdefault(name.split(":")[0], []).append(name)
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(":")[0] in base_rows:
                    spans.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    spans.sort(key=lambda r: r[1])
    seen, labelled = {}, []
    for name, s, e in spans:
        base = name.split(":")[0]
        k = seen.get(base, 0)
        seen[base] = k + 1
        names = base_rows[base]
        if ":" not in name and k < len(names):
            name = names[k]
        labelled.append((name, s, e))

    devices = []
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        mods = [(module_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                for e in lines["XLA Modules"].events] \
            if "XLA Modules" in lines else []
        mods.sort(key=lambda r: r[1])
        ops = [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
               for e in lines["XLA Ops"].events]
        pending = [(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                   for e in lines["Async XLA Ops"].events] \
            if "Async XLA Ops" in lines else []
        devices.append((plane.name, mods, ops, pending))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane with an 'XLA Ops' line")

    win = [(s, e) for n, s, e in labelled if n == window_span]
    if win:
        w0, w1 = win[0]
    else:
        w0 = min(o[1] for _, _, ops, _ in devices for o in ops)
        w1 = max(o[2] for _, _, ops, _ in devices for o in ops)
    inner = [r for r in labelled if r[0] != window_span]
    segments = _innermost(inner, w0, w1)

    n = len(devices)
    busy = 0.0
    op_s, mod_s, idle = {}, {}, {}
    coll_total = coll_exposed = 0.0
    for _, mods, ops, pending in devices:
        names = np.array([o[0] for o in ops], dtype=object)
        s = np.clip(np.array([o[1] for o in ops], float), w0, w1)
        e = np.clip(np.array([o[2] for o in ops], float), w0, w1)
        dur = e - s
        cover = Cover(s, e)
        busy += cover.total()
        m_start = np.array([m[1] for m in mods], float)
        m_end = np.array([m[2] for m in mods], float)
        which = np.searchsorted(m_start, [o[1] for o in ops], "right") - 1
        for i in np.flatnonzero(dur > 0):
            if names[i] in _CONTAINERS:
                continue
            j = which[i]
            inside = j >= 0 and ops[i][1] < m_end[j]
            key = f"{mods[j][0] if inside else 'no-module'}/{names[i]}"
            op_s[key] = op_s.get(key, 0.0) + dur[i]
        for name, a, b in mods:
            a, b = min(max(a, w0), w1), min(max(b, w0), w1)
            mod_s[name] = mod_s.get(name, 0.0) + (b - a)
        for label, a, b in segments:
            gap = (b - a) - float(cover.within(a, b))
            idle[label] = idle.get(label, 0.0) + gap
        is_coll = np.array([bool(_COLLECTIVE.match(x)) for x in names], bool)
        body = np.array([x not in _CONTAINERS for x in names], bool)
        c_iv = [(a, b) for nm, a, b in pending if _COLLECTIVE.match(nm)]
        c_s = np.concatenate([s[is_coll], np.clip([a for a, _ in c_iv],
                                                  w0, w1)])
        c_e = np.concatenate([e[is_coll], np.clip([b for _, b in c_iv],
                                                  w0, w1)])
        coll = Cover(c_s, c_e)
        compute = Cover(s[~is_coll & body], e[~is_coll & body])
        coll_total += coll.total()
        coll_exposed += coll.total() - float(
            np.sum(compute.within(coll.s, coll.e)))

    ns = 1e-9 / n
    return {
        "devices": n,
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * ns,
        "op_seconds": {k: float(v) * ns for k, v in op_s.items()},
        "module_seconds": {k: float(v) * ns for k, v in mod_s.items()},
        "idle_by_span": {k: float(v) * ns for k, v in idle.items()},
        "collective_s": coll_total * ns,
        "collective_exposed_s": coll_exposed * ns,
        "device_events": sum(len(d[2]) for d in devices),
    }


def kernel_seconds(trace, fragment):
    """Seconds of the device operations whose name contains ``fragment``
    (a Pallas kernel shows up under its ``name=``, wrapped by ``jvp_`` or
    ``transpose_jvp_`` where autodiff made it)."""
    return sum(v for k, v in trace["op_seconds"].items()
               if fragment in k.split("/", 1)[1])


def breakdown(trace, top=10):
    ops = sorted(trace["op_seconds"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(trace["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}
