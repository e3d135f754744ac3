"""The program reads its own device trace (reference:
``paddle.profiler.load_profiler_result`` and the tables of
``Profiler.summary()``: overview, model, distributed, operator, kernel).

:func:`load` takes the ``.xplane.pb`` that a ``jax.profiler`` session (a
:class:`~paddle_tpu.profiler.Profiler`) wrote and returns one
:class:`ProfilerResult` with four tables (and the rows under them):

- ``programs``: per XLA module (``jit_mixed``, ``jit_quantum``,
  ``jit_multi_step_fn``, ...) its calls, its device seconds, and how many
  of its instructions carry a scope of the vocabulary
  (:data:`~paddle_tpu.profiler.scopes.SCOPES`): an executable loaded from
  a compile cache that an older tree wrote shows here at a glance.
- ``scopes``: per program, device seconds by (innermost vocabulary scope,
  phase ``fwd`` | ``bwd``) and under each by operation base name
  (``fusion``, ``copy``, ``ragged-dot``, a kernel's name).
  ``unscoped`` is the rest. Container operations (``while``,
  ``conditional``, ``call``) only hold other operations of the same line
  and are left out, so a program's scope seconds add up to its operation
  seconds exactly.
- ``ops``: under both, per instruction: its program, ``op_name``, scope,
  phase, calls and seconds (the drill-down; :meth:`tables` prints the
  base names only).
- ``collectives``: per (mesh axis, kind, scope) the seconds a collective
  was in flight and the seconds of those that no compute hid (collective
  intervals minus compute intervals per chip, the definition of the
  benchmark's ``collective_exposed_pct``). The axis comes from the
  instruction's ``replica_groups`` (the trace's event name IS the
  instruction's HLO text) matched against ``mesh_axes``, ``{axis: groups
  of partition ids}`` as ``parallel.mesh.axis_groups`` gives them.
- ``idle_gaps``: device idle seconds by the innermost
  :class:`~paddle_tpu.profiler.RecordEvent` span open on the host at that
  moment (:data:`~paddle_tpu.profiler.scopes.SPANS`, found on the
  ``/host:`` planes, which share the device planes' clock;
  ``outside-spans`` for the rest).

**Where an operation's scope comes from**: the programs' HLO protos,
which the profiler writes on the trace's ``/host:metadata`` plane (one a
program, keyed by the ``program_id`` that every device operation's event
metadata carries): each instruction's ``metadata.op_name`` is its name
stack (``jit(quantum)/jit(main)/attn.proj/dot_general``). The event's name
is the instruction's HLO text, so its first word finds the instruction.
``jax.profiler.ProfileData`` exposes neither the protos nor an event
metadata's stats, so the file is read by the small protobuf wire reader
below (standard library and numpy): one pass gives events, programs and
host spans on one clock. The stat ``tf_op`` beside ``program_id`` holds
the same string and is NOT read: it is empty wherever the compiler made
the instruction itself, and only the proto says what such an instruction
works for. The rules, in this order (:func:`resolve_scopes`): an
instruction's own ``op_name``; a fusion whose root carries none (a
tuple, a scatter or a convert the compiler put there) is charged whole to
the scope most of its body's instructions carry; an instruction with
neither (the compiler's prefetch of a weight, ``copy-start`` /
``copy-done`` / ``slice-done``, a relayout ``copy``, a ``bitcast``) to
the scope of what consumes it, else of what it consumes. XLA keeps ONE
``op_name`` a fusion (its root's): a fusion is charged whole to that
scope, and :attr:`ProfilerResult.mixed_fusions` says how many seconds
ran in fusions whose bodies span several scopes.

All seconds are averages over the device planes, as the benchmark's
reducer has them. Imported lazily: ``import paddle_tpu.serving`` does not
import this module.
"""
from __future__ import annotations

import glob
import os
import re
import struct

import numpy as np

from .scopes import COMPILER_NAMES, SCOPES, SPANS

__all__ = ["load", "ProfilerResult", "find_xplane", "scope_of",
           "resolve_scopes", "replica_groups", "axis_of", "collective_of"]

CONTAINERS = frozenset({"while", "conditional", "call"})
_BASE = re.compile(r"^%?([A-Za-z0-9_\-.]+?)(?:\.\d+)?(?:\s|=|$)")
_COLLECTIVE = re.compile(
    r"\s(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(?:-start|-done)?\(")
_GROUPS = re.compile(r"(?:replica_groups|source_target_pairs)="
                     r"(\{(?:\{[\d,\s]*\},?\s*)*\}"
                     r"|\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")
_WORD = re.compile(r"[A-Za-z0-9_.\-]+")
_OPERAND = re.compile(r"%[A-Za-z0-9_.\-]+")
# what holds or passes other instructions' values and does no work of its own:
# it takes no scope and hands none on
_PLUMBING = CONTAINERS | {"tuple", "get-tuple-element", "parameter"}


# ---------------------------------------------------------------- wire


def _fields(buf, pos, end):
    """The fields of one protobuf message in ``buf[pos:end]``: (number,
    value), a varint as an int, a fixed64 as its 8 bytes, a
    length-delimited field as its (start, end) in ``buf``."""
    while pos < end:
        key = buf[pos]
        pos += 1
        if key >= 0x80:
            key &= 0x7F
            shift = 7
            while True:
                b = buf[pos]
                pos += 1
                key |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
        wire = key & 7
        if wire == 0:
            val = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                val |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, val
        elif wire == 2:
            n = shift = 0
            while True:
                b = buf[pos]
                pos += 1
                n |= (b & 0x7F) << shift
                if b < 0x80:
                    break
                shift += 7
            yield key >> 3, (pos, pos + n)
            pos += n
        elif wire == 1:
            yield key >> 3, buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            yield key >> 3, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stats(buf, spans, stat_names):
    """XStat messages -> {stat name: value}."""
    out = {}
    for start, end in spans:
        name = value = None
        for f, v in _fields(buf, start, end):
            if f == 1:
                name = stat_names.get(v)
            elif f in (3, 4):
                value = v
            elif f == 2:
                value = struct.unpack("<d", v)[0]
            elif f == 5:
                value = _text(buf, v)
            elif f == 6:  # bytes (an HLO proto): its place in ``buf``
                value = v
            elif f == 7:  # a string interned as a stat-metadata name
                value = stat_names.get(v)
        if name is not None:
            out[name] = value
    return out


class _Plane:
    """One XPlane: its name, its lines by name, and per event metadata
    id the (name, stats) the events point at."""

    def __init__(self, buf, start, end):
        self.name, lines, emeta, smeta = "", [], [], []
        for f, v in _fields(buf, start, end):
            if f == 2:
                self.name = _text(buf, v)
            elif f == 3:
                lines.append(v)
            elif f == 4:
                emeta.append(v)
            elif f == 5:
                smeta.append(v)
        self._buf, self._lines, self._emeta, self._smeta = (
            buf, lines, emeta, smeta)

    def metadata(self):
        """{event metadata id: (name, {stat name: value})}."""
        buf, stat_names = self._buf, {}
        for start, end in self._smeta:  # map entry: 1 key, 2 XStatMetadata
            for f, v in _fields(buf, start, end):
                if f == 2:
                    sid = name = None
                    for g, w in _fields(buf, *v):
                        if g == 1:
                            sid = w
                        elif g == 2:
                            name = _text(buf, w)
                    stat_names[sid] = name
        out = {}
        for start, end in self._emeta:
            for f, v in _fields(buf, start, end):
                if f != 2:
                    continue
                mid, name, stats = None, "", []
                for g, w in _fields(buf, *v):
                    if g == 1:
                        mid = w
                    elif g == 2:
                        name = _text(buf, w)
                    elif g == 5:
                        stats.append(w)
                out[mid] = (name, _stats(buf, stats, stat_names))
        return out

    def lines(self):
        """[(line name, metadata ids, start ps, end ps)], the three as
        int64 arrays on the trace's one clock."""
        buf, out = self._buf, []
        for start, end in self._lines:
            name, t0_ns, events = "", 0, []
            for f, v in _fields(buf, start, end):
                if f == 2:
                    name = _text(buf, v)
                elif f == 3:
                    t0_ns = v
                elif f == 4:
                    events.append(v)
            rows = np.zeros((len(events), 3), np.int64)
            for k, (a, b) in enumerate(events):
                for f, v in _fields(buf, a, b):
                    if f <= 3:  # 1 metadata id, 2 offset ps, 3 duration ps
                        rows[k, f - 1] = v
                    else:
                        break  # the event's own stats: not read
            begin = rows[:, 1] + t0_ns * 1000
            out.append((name, rows[:, 0], begin, begin + rows[:, 2]))
        return out


def find_xplane(path_or_dir):
    """The newest ``.xplane.pb`` under a profiler log directory, or the
    file itself."""
    if os.path.isfile(path_or_dir):
        return path_or_dir
    found = sorted(glob.glob(os.path.join(
        path_or_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path_or_dir}")
    return found[-1]


# ---------------------------------------------------------------- names


def base_name(instruction):
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``."""
    m = _BASE.match(instruction)
    return m.group(1) if m else instruction.split(" ")[0]


def scope_of(op_name):
    """``op_name`` (an instruction's name stack) -> (innermost vocabulary
    scope or ``unscoped``, phase): ``bwd`` where any part of the path is
    a ``transpose(...)``, the backward pass and what it recomputes."""
    scope, phase = "unscoped", "fwd"
    for part in (op_name or "").split("/"):
        if part.startswith("transpose("):
            phase = "bwd"
        words = [part] if "(" not in part else _WORD.findall(part)
        for word in words:  # attn.proj, transpose(jvp(attn.proj))
            if word in SCOPES:
                scope = word
        for prefix, name in COMPILER_NAMES.items():
            if part.startswith(prefix):
                scope = name
    return scope, phase


def replica_groups(instruction):
    """The device groups of a collective's HLO text as a tuple of tuples
    of partition ids: ``replica_groups={{0,1},{2,3}}``, the iota form
    ``[2,2]<=[4]`` / ``[2,2]<=[2,2]T(1,0)``, or a permute's
    ``source_target_pairs``. None where the text has none; ``()`` for
    ``replica_groups={}`` (every device)."""
    m = _GROUPS.search(instruction)
    if not m:
        return None
    text = m.group(1)
    if text.startswith("{"):
        return tuple(tuple(int(x) for x in g.split(",") if x.strip())
                     for g in re.findall(r"\{([\d,\s]*)\}", text[1:-1]))
    shape, dims, perm = re.match(
        r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text).groups()
    shape = [int(x) for x in shape.split(",")]
    dims = [int(x) for x in dims.split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if perm:
        ids = ids.transpose([int(x) for x in perm.split(",")])
    return tuple(tuple(int(x) for x in row) for row in ids.reshape(shape))


def axis_of(groups, mesh_axes):
    """Which mesh axis (or ``a+b`` for several at once) a collective's
    groups run over: the smallest set of axes whose combined groups hold
    every one of the instruction's groups. ``mesh_axes`` is ``{axis:
    groups of partition ids}``; ``unknown`` without it or without a
    match."""
    if not mesh_axes or groups is None:
        return "unknown"
    names = list(mesh_axes)
    if not groups:
        return "+".join(names)
    # a partition's coordinate along an axis is its place in its group
    # of that axis; a collective runs over every axis along which two
    # members of one of its groups differ
    coord = {}
    for a in names:
        for g in mesh_axes[a]:
            for k, d in enumerate(g):
                coord.setdefault(int(d), {})[a] = k
    if any(d not in coord for g in groups for d in g):
        return "unknown"
    used = [a for a in names
            if any(len({coord[d][a] for d in g}) > 1 for g in groups)]
    return "+".join(used) if used else "none"


def collective_of(instruction, groups, mesh_axes):
    """(kind, mesh axis) of a collective's HLO text, None for any other
    instruction. ``groups``: ``{instruction name: its device groups}`` of
    the program's collectives: a ``-done`` has no groups of its own and
    takes those of the ``-start`` it waits for, its operand."""
    coll = _COLLECTIVE.search(instruction)
    if not coll:
        return None
    found = replica_groups(instruction)
    if found is None:
        start = _OPERAND.search(instruction, coll.end())
        found = groups.get(start.group(0)) if start else None
    return coll.group(1), axis_of(found, mesh_axes)


# ---------------------------------------------------------------- hlo


def _varints(buf, value):
    """A repeated int64 field's value: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, (pos, end) = [], value
    while pos < end:
        val = shift = 0
        while True:
            b = buf[pos]
            pos += 1
            val |= (b & 0x7F) << shift
            if b < 0x80:
                break
            shift += 7
        out.append(val)
    return out


def _hlo_program(buf, span):
    """One program's ``HloProto`` in ``buf[span]`` -> (instructions,
    computations): ``{id: [name, opcode, op_name, operand ids, called
    computation ids]}`` and ``{computation id: [instruction ids, in the
    computation's order]}``. Field numbers are ``xla/service/hlo.proto``'s."""
    instrs, comps = {}, {}
    for f, module in _fields(buf, *span):
        if f != 1:                                  # HloProto.hlo_module
            continue
        for g, comp in _fields(buf, *module):
            if g != 3:                              # .computations
                continue
            cid, ids = None, []
            for h, v in _fields(buf, *comp):
                if h == 5:                          # .id
                    cid = v
                elif h == 2:                        # .instructions
                    rec, iid = ["", "", "", [], []], None
                    for k, w in _fields(buf, *v):
                        if k == 1:
                            rec[0] = _text(buf, w)
                        elif k == 2:
                            rec[1] = _text(buf, w)
                        elif k == 7:                # .metadata.op_name
                            for m, x in _fields(buf, *w):
                                if m == 2:
                                    rec[2] = _text(buf, x)
                        elif k == 35:
                            iid = w
                        elif k == 36:
                            rec[3] += _varints(buf, w)
                        elif k == 38:
                            rec[4] += _varints(buf, w)
                    instrs[iid] = rec
                    ids.append(iid)
            comps[cid] = ids
    return instrs, comps


def _most(votes):
    """The (scope, phase) most of ``votes`` name, ties by name."""
    tally = {}
    for v in votes:
        tally[v] = tally.get(v, 0) + 1
    return min(tally, key=lambda v: (-tally[v], v)) if tally else None


def resolve_scopes(instrs, comps):
    """``{instruction name: (scope, phase, op_name, own, several)}`` of one
    program (:func:`_hlo_program`'s two tables). An instruction's own
    ``op_name`` decides; a fusion without one takes what most of its body
    carries; what is left takes what most of its users carry, else most
    of its operands (a prefetch is charged to the product that reads the
    weight, a relayout to what wanted the other order). ``own``: the first
    rule decided; ``several``: the instruction is a fusion whose body
    spans more than one scope."""
    found, several, own_ids = {}, set(), set()
    for iid, (_, opcode, op_name, _, called) in instrs.items():
        own = scope_of(op_name)
        if own[0] != "unscoped":
            own_ids.add(iid)
        if opcode == "fusion" and called:
            inside = [scope_of(instrs[k][2]) for c in called
                      for k in comps.get(c, ())]
            inside = [v for v in inside if v[0] != "unscoped"]
            if len({v[0] for v in inside}) > 1:
                several.add(iid)
            if own[0] == "unscoped" and inside:
                own = _most(inside)
        if own[0] != "unscoped":
            found[iid] = own
    users = {}
    for iid, rec in instrs.items():
        for k in rec[3]:
            users.setdefault(k, []).append(iid)
    open_ = [iid for ids in comps.values() for iid in ids
             if iid not in found and instrs[iid][1] not in _PLUMBING]
    for _ in range(8):  # a chain of copies is a few links long
        before = len(found)
        for order, edges in ((reversed(open_), users.get),
                             (open_, lambda k: instrs[k][3])):
            for iid in order:
                if iid not in found:
                    got = _most(found[k] for k in edges(iid) or ()
                                if k in found)
                    if got:
                        found[iid] = got
        if len(found) == before:
            break
    return {rec[0]: (*found.get(iid, ("unscoped", "fwd")), rec[2],
                     iid in own_ids, iid in several)
            for iid, rec in instrs.items()}


# ---------------------------------------------------------------- cover


def _merge(starts, ends):
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
    """Disjoint segments of [w0, w1], each labelled by the innermost of
    ``spans`` ((name, start, end), nested as one thread's are) open over
    it; ``outside-spans`` where none is."""
    out, stack, t = [], [], w0

    def emit(until):
        nonlocal t
        until = min(max(until, w0), w1)
        if until > t:
            out.append((stack[-1][0] if stack else "outside-spans", t, until))
            t = until

    for span in sorted(spans, key=lambda r: (r[1], -r[2])):
        while stack and stack[-1][2] <= span[1]:
            emit(stack[-1][2])
            stack.pop()
        emit(span[1])
        stack.append(span)
    while stack:
        emit(stack[-1][2])
        stack.pop()
    emit(w1)
    return out


# ---------------------------------------------------------------- result


class ProfilerResult:
    """What :func:`load` returns; the fields are plain data (seconds,
    averaged over the device planes) and :meth:`tables` prints them."""

    def __init__(self, **fields):
        self.__dict__.update(fields)

    def to_dict(self):
        d = dict(self.__dict__)
        d["scopes"] = {p: [dict(scope=s, phase=ph, seconds=row["seconds"],
                                ops=row["ops"])
                           for (s, ph), row in rows.items()]
                       for p, rows in self.scopes.items()}
        return d

    def tables(self, top_ops=4, time_unit="ms"):
        k = {"s": 1.0, "ms": 1e3, "us": 1e6}[time_unit]
        busy = self.busy_s
        lines = [
            f"device trace: {self.path}",
            f"devices {self.devices}  window {self.window_s * k:.3f} "
            f"{time_unit}  busy {busy * k:.3f}  idle "
            f"{100 * (1 - busy / self.window_s) if self.window_s else 0:.1f} %",
            "", "programs (XLA modules)",
            f"  {'program':<28}{'calls':>7}{time_unit:>12}{'ops':>7}"
            f"{'named':>8}"]
        for name, p in sorted(self.programs.items(),
                              key=lambda kv: -kv[1]["device_s"]):
            lines.append(f"  {name:<28}{p['calls']:>7}"
                         f"{p['device_s'] * k:>12.3f}{p['ops']:>7}"
                         f"{p['ops_named']:>8}")
        lines += ["", "scopes (device seconds by innermost scope and phase; "
                      "a fusion is charged whole to its root's scope)"]
        for prog, rows in sorted(
                self.scopes.items(),
                key=lambda kv: -sum(r["seconds"] for r in kv[1].values())):
            total = sum(r["seconds"] for r in rows.values())
            several = self.mixed_fusions.get(prog, 0.0)
            lines.append(f"  {prog}: {total * k:.3f} {time_unit}" + (
                f"  ({several * k:.3f} in fusions whose bodies span "
                "several scopes)" if several else ""))
            for (scope, phase), row in sorted(
                    rows.items(), key=lambda kv: -kv[1]["seconds"]):
                ops = sorted(row["ops"].items(), key=lambda kv: -kv[1])
                shown = ", ".join(f"{n} {v * k:.3f}"
                                  for n, v in ops[:top_ops])
                more = f", +{len(ops) - top_ops}" if len(ops) > top_ops else ""
                lines.append(
                    f"    {scope:<16}{phase:<5}{row['seconds'] * k:>11.3f}"
                    f"{100 * row['seconds'] / total if total else 0:>6.1f} %"
                    f"  {shown}{more}")
        if self.collectives:
            lines += ["", f"collectives (in flight {self.collective_s * k:.3f}"
                          f" {time_unit}, exposed "
                          f"{self.collective_exposed_s * k:.3f} = "
                          f"{100 * self.collective_exposed_s / self.window_s:.1f}"
                          " % of the window)",
                      f"  {'axis':<14}{'kind':<20}{'scope':<16}{'phase':<5}"
                      f"{time_unit:>11}{'exposed':>11}"]
            for c in self.collectives:
                lines.append(
                    f"  {c['axis']:<14}{c['kind']:<20}{c['scope']:<16}"
                    f"{c['phase']:<5}{c['seconds'] * k:>11.3f}"
                    f"{c['exposed_s'] * k:>11.3f}")
        lines += ["", "idle gaps (device idle by the innermost host span "
                      "open)"]
        for name, v in sorted(self.idle_gaps.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {name:<28}{v * k:>11.3f}")
        return "\n".join(lines)

    __str__ = tables


def load(path_or_dir, mesh_axes=None, window_span=None):
    """Read a saved device trace. ``mesh_axes``: ``{axis: groups of
    partition ids}`` of the mesh the traced program ran on
    (``parallel.mesh.axis_groups``), for the collectives' axis; a saved
    trace is read offline with the groups written down beside it.
    ``window_span``: the name of a host annotation that bounds the window
    (the first one of that name); else the device's operations do."""
    path = find_xplane(path_or_dir)
    with open(path, "rb") as f:
        buf = f.read()
    planes = [_Plane(buf, *v) for f, v in _fields(buf, 0, len(buf))
              if f == 1]

    wanted = set(SPANS) | ({window_span} if window_span else set())
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        names = {mid: name for mid, (name, _) in plane.metadata().items()
                 if name in wanted}
        if not names:
            continue
        for _, mids, begin, end in plane.lines():
            for k in np.flatnonzero(np.isin(mids, list(names))):
                spans.append((names[int(mids[k])], int(begin[k]),
                              int(end[k])))

    devices = []
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {name: (mids, b, e) for name, mids, b, e in plane.lines()}
        if "XLA Ops" in lines:
            devices.append((plane.metadata(), lines))
    if not devices:
        raise ValueError(f"{path}: no device plane with an 'XLA Ops' line")

    bound = [(s, e) for n, s, e in spans if n == window_span]
    if bound:
        w0, w1 = bound[0]
    else:
        w0 = min(int(ln["XLA Ops"][1].min()) for _, ln in devices)
        w1 = max(int(ln["XLA Ops"][2].max()) for _, ln in devices)
    segments = _innermost([s for s in spans if s[0] != window_span], w0, w1)
    seg_a = np.array([a for _, a, _ in segments], float)
    seg_b = np.array([b for _, _, b in segments], float)

    protos = {}  # program id -> where its HLO proto lies in buf
    for plane in planes:
        if plane.name == "/host:metadata":
            for mid, (_, stats) in plane.metadata().items():
                if isinstance(stats.get("Hlo Proto"), tuple):
                    protos[str(mid)] = stats["Hlo Proto"]
    resolved = {}

    def instruction(program, text):
        if program not in resolved:
            resolved[program] = resolve_scopes(*_hlo_program(
                buf, protos[program])) if program in protos else {}
        return resolved[program].get(
            text.split(" ", 1)[0].lstrip("%"),
            ("unscoped", "fwd", "", False, False))

    n = len(devices)
    busy = coll_total = coll_exposed = 0.0
    programs, scopes, idle, coll_rows, ops, mixed = {}, {}, {}, {}, {}, {}
    for meta, lines in devices:
        # what every event metadata id of this plane says, once
        module_of = {}  # program id -> module name
        for mid, (name, _) in meta.items():
            if re.fullmatch(r".*\(\d+\)", name):
                module_of[name[name.rindex("(") + 1:-1]] = name[
                    :name.rindex("(")]
        groups = {name.split(" ", 1)[0]: replica_groups(name)
                  for name, _ in meta.values() if _COLLECTIVE.search(name)}
        info = {}
        for mid, (name, stats) in meta.items():
            program = str(stats.get("program_id", ""))
            info[mid] = (
                module_of.get(program, f"program {program}" if program
                              else "no-module"),
                base_name(name), instruction(program, name),
                collective_of(name, groups, mesh_axes), name)

        if "XLA Modules" in lines:
            mids, b, e = lines["XLA Modules"]
            b, e = np.clip(b, w0, w1), np.clip(e, w0, w1)
            for k in range(len(mids)):
                name = meta[int(mids[k])][0].split("(")[0]
                p = programs.setdefault(name, dict(
                    calls=0, device_s=0.0, ops=set(), scoped=set()))
                p["calls"] += 1
                p["device_s"] += float(e[k] - b[k])

        mids, b, e = lines["XLA Ops"]
        b, e = np.clip(b, w0, w1).astype(float), np.clip(e, w0, w1).astype(float)
        cover = Cover(b, e)
        busy += cover.total()
        uniq, inv = np.unique(mids, return_inverse=True)
        seconds = np.bincount(inv, weights=e - b, minlength=len(uniq))
        container = np.zeros(len(uniq), bool)
        collective = np.zeros(len(uniq), bool)
        calls = np.bincount(inv, minlength=len(uniq))
        for k, mid in enumerate(uniq):
            program, base, (scope, phase, op_name, own, several), coll, \
                name = info[int(mid)]
            if base in CONTAINERS:
                container[k] = True
                continue
            collective[k] = coll is not None
            p = programs.setdefault(program, dict(
                calls=0, device_s=0.0, ops=set(), scoped=set()))
            p["ops"].add(name)
            if own:
                p["scoped"].add(name)
            if several:
                mixed[program] = mixed.get(program, 0.0) + float(seconds[k])
            row = scopes.setdefault(program, {}).setdefault(
                (scope, phase), dict(seconds=0.0, ops={}))
            row["seconds"] += float(seconds[k])
            row["ops"][base] = row["ops"].get(base, 0.0) + float(seconds[k])
            op = ops.setdefault((program, name), dict(
                program=program, instruction=name.split(" ", 1)[0],
                base=base, op_name=op_name, scope=scope, phase=phase,
                calls=0, seconds=0.0))
            op["calls"] += int(calls[k])
            op["seconds"] += float(seconds[k])
        is_coll, body = collective[inv], ~container[inv]
        coll_iv = []  # (key, start, end) of every collective interval
        for k in np.flatnonzero(is_coll):
            _, _, (scope, phase, *_), coll, _ = info[int(mids[k])]
            coll_iv.append(((coll[1], coll[0], scope, phase), b[k], e[k]))
        if "Async XLA Ops" in lines:
            amids, ab, ae = lines["Async XLA Ops"]
            ab, ae = np.clip(ab, w0, w1), np.clip(ae, w0, w1)
            for k in range(len(amids)):
                _, _, (scope, phase, *_), coll, _ = info[int(amids[k])]
                if coll:
                    coll_iv.append(((coll[1], coll[0], scope, phase),
                                    float(ab[k]), float(ae[k])))
        compute = Cover(b[~is_coll & body], e[~is_coll & body])
        every = Cover([x for _, x, _ in coll_iv], [y for _, _, y in coll_iv])
        coll_total += every.total()
        coll_exposed += every.total() - float(
            np.sum(compute.within(every.s, every.e)))
        by_key = {}
        for key, x, y in coll_iv:
            by_key.setdefault(key, ([], []))
            by_key[key][0].append(x)
            by_key[key][1].append(y)
        for key, (xs, ys) in by_key.items():
            c = Cover(xs, ys)
            row = coll_rows.setdefault(key, [0.0, 0.0])
            row[0] += c.total()
            row[1] += c.total() - float(np.sum(compute.within(c.s, c.e)))
        gaps = (seg_b - seg_a) - cover.within(seg_a, seg_b)
        for (label, _, _), gap in zip(segments, gaps):
            idle[label] = idle.get(label, 0.0) + float(gap)

    ps = 1e-12 / n
    return ProfilerResult(
        path=path, devices=n, window_s=(w1 - w0) * 1e-12, busy_s=busy * ps,
        programs={name: dict(calls=-(-p["calls"] // n),
                             device_s=p["device_s"] * ps,
                             ops=len(p["ops"]), ops_named=len(p["scoped"]))
                  for name, p in programs.items()},
        scopes={prog: {key: dict(seconds=row["seconds"] * ps,
                                 ops={o: v * ps for o, v in row["ops"].items()})
                       for key, row in rows.items()}
                for prog, rows in scopes.items()},
        collectives=[dict(axis=a, kind=kind, scope=scope, phase=phase,
                          seconds=row[0] * ps, exposed_s=row[1] * ps)
                     for (a, kind, scope, phase), row in sorted(
                         coll_rows.items(), key=lambda kv: -kv[1][0])],
        ops=sorted((dict(op, calls=-(-op["calls"] // n),
                         seconds=op["seconds"] * ps)
                    for op in ops.values()), key=lambda o: -o["seconds"]),
        collective_s=coll_total * ps, collective_exposed_s=coll_exposed * ps,
        mixed_fusions={prog: v * ps for prog, v in mixed.items()},
        idle_gaps={name: v * ps for name, v in idle.items()})
