"""Sharding-layout auditor over lowered StableHLO.

The partitioner can only keep a TP x ZeRO layout honest if the entry
arguments actually CARRY their shardings — a refactor that drops a
``NamedSharding`` (or a state-init path that stops threading the axis)
silently replicates the leaf on every device, multiplying its HBM cost
by the mesh size, and tier-1 numerics stay green. The StableHLO entry
signature records each argument's layout as a Shardy ``sdy.sharding``
attribute over a module-level ``sdy.mesh`` (the installed JAX's
partitioner), or as a GSPMD ``mhlo.sharding`` string::

    sdy.mesh @mesh = <["mp"=2, "sharding"=4]>
    %arg3: tensor<64x128xf32>
        {sdy.sharding = #sdy.sharding<@mesh, [{"mp", "sharding"}, {}]>}
    %arg3: tensor<64x128xf32>
        {mhlo.sharding = "{devices=[2,1,4]<=[8] last_tile_dim_replicate}"}

so the audit parses the attrs per argument and classifies each as
sharded or fully replicated (no attr, no dim split over an axis larger
than 1, ``{replicated}``, ``{maximal ...}``, or a tile assignment
whose data dims are all 1). Declarative
expectations ride on the Budget:

- ``max_replicated_param_bytes``: no fully-replicated donatable leaf
  (param/optimizer-state/buffer) above N bytes — small norm scales may
  replicate by design, a weight matrix or its moments may not;
- ``min_sharded_params``: at least K donatable leaves must be sharded
  (the ZeRO axis is actually present on the state, not just on paper).
"""
from __future__ import annotations

import re

from .donation import _ARG_HEAD_RE, _scan_attrs, _tensor_bytes

__all__ = ["ArgSharding", "ShardingReport", "audit_sharding"]

_SHARDING_ATTR_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')
_DEVICES_RE = re.compile(r"devices=\[([\d,]+)\]")
_SDY_ATTR_RE = re.compile(
    r"sdy\.sharding\s*=\s*(#sdy\.sharding<@(\w+),\s*\[([^\]]*)\][^>]*>)")
_SDY_MESH_RE = re.compile(r"sdy\.mesh\s+@(\w+)\s*=\s*<\[([^\]]*)\]")
_SDY_AXIS_SIZE_RE = re.compile(r'"([^"]+)"\s*=\s*(\d+)')
_SDY_AXIS_RE = re.compile(r'"([^"]+)"')


def _sdy_replicated(dims, axis_sizes):
    """A Shardy dim list (``{"mp", "sharding"}, {}``) is replicated
    when no dim names an axis larger than 1 (an axis the mesh decl
    does not list is taken as splitting)."""
    return all(axis_sizes.get(a, 2) <= 1
               for a in _SDY_AXIS_RE.findall(dims))


def _classify(attr):
    """``(replicated, unknown)`` for one sharding attr. ``replicated``
    is True when the attr describes a fully-replicated (or single-
    device-owned) layout; tile assignments that split at least one data
    dimension count as sharded. ``unknown`` flags syntax the parser
    didn't recognize: it is still CLASSIFIED replicated — a parser gap
    can only make the audit stricter, never hide a replicated leaf —
    but counted separately so a report (and its fingerprint) can tell
    "parser gap" apart from "actually replicated"."""
    if attr is None or attr == "" or "replicated}" in attr.replace(
            "last_tile_dim_replicate}", ""):
        return True, False
    if "maximal" in attr:
        return True, False
    m = _DEVICES_RE.search(attr)
    if m is None:
        # unknown syntax: strict-but-counted (see docstring)
        return True, True
    dims = [int(d) for d in m.group(1).split(",")]
    if "last_tile_dim_replicate" in attr and len(dims) > 1:
        dims = dims[:-1]  # trailing dim is the replication group
    return all(d == 1 for d in dims), False


class ArgSharding:
    """One entry argument's layout: byte size, the raw sharding attr
    (``""`` when the argument carries none), the replicated verdict,
    and whether that verdict came from UNRECOGNIZED attr syntax (the
    strict fallback) rather than a parsed layout."""

    __slots__ = ("index", "nbytes", "spec", "replicated", "unknown")

    def __init__(self, index, nbytes, spec, replicated, unknown=False):
        self.index = index
        self.nbytes = nbytes
        self.spec = spec
        self.replicated = replicated
        self.unknown = unknown

    def __repr__(self):
        kind = "replicated" if self.replicated else "sharded"
        if self.unknown:
            kind += " (unknown syntax)"
        return (f"ArgSharding(arg{self.index}, {self.nbytes}B, {kind}"
                + (f", {self.spec!r}" if self.spec else "") + ")")


class ShardingReport:
    """Per-argument layouts for one entry signature. ``n_donatable``
    (when the target declares it) marks how many LEADING args are
    param/state/buffer leaves — the set the sharding expectations
    range over."""

    __slots__ = ("args", "n_donatable")

    def __init__(self, args, n_donatable=None):
        self.args = args
        self.n_donatable = n_donatable

    def _donatable(self):
        limit = self.n_donatable
        if limit is None:
            limit = len(self.args)
        return [a for a in self.args if a.index < limit]

    @property
    def sharded_count(self):
        return sum(1 for a in self.args if not a.replicated)

    @property
    def sharded_param_count(self):
        return sum(1 for a in self._donatable() if not a.replicated)

    def replicated_params(self, min_bytes=0):
        """Fully-replicated donatable leaves at or above ``min_bytes``,
        largest first — the candidates a budget flags."""
        out = [a for a in self._donatable()
               if a.replicated and a.nbytes >= min_bytes]
        return sorted(out, key=lambda a: (-a.nbytes, a.index))

    @property
    def max_replicated_param_bytes(self):
        reps = self.replicated_params()
        return reps[0].nbytes if reps else 0

    @property
    def unknown_count(self):
        """Args whose sharding attr the parser did not recognize (they
        are classified replicated — the strict fallback — but a nonzero
        count means 'parser gap', not 'actually replicated')."""
        return sum(1 for a in self.args if a.unknown)

    def summary_dict(self):
        """Stable scalar summary (fingerprint + CLI material). The
        ``unknown_shardings`` key appears ONLY when nonzero: fingerprint
        comparison flags any new key as drift, so an always-present key
        would invalidate every existing golden for the common (fully
        parsed) case."""
        out = {
            "n_args": len(self.args),
            "n_sharded": self.sharded_count,
            "n_sharded_params": self.sharded_param_count,
            "max_replicated_param_bytes":
                self.max_replicated_param_bytes,
        }
        if self.unknown_count:
            out["unknown_shardings"] = self.unknown_count
        return out


def audit_sharding(stablehlo_text, n_donatable=None):
    """Parse @main's per-argument sharding attributes into a
    :class:`ShardingReport` (same signature walk as the donation
    audit, so arg indices line up between the two reports)."""
    meshes = {
        name: {a: int(n) for a, n in _SDY_AXIS_SIZE_RE.findall(axes)}
        for name, axes in _SDY_MESH_RE.findall(stablehlo_text)}
    seen = {}
    for m in _ARG_HEAD_RE.finditer(stablehlo_text):
        idx = int(m.group(1))
        if idx in seen:  # inner funcs reuse %argN; keep the entry's
            continue
        attrs = _scan_attrs(stablehlo_text, m.end())
        sdy = _SDY_ATTR_RE.search(attrs)
        if sdy:
            spec = sdy.group(1)
            replicated, unknown = _sdy_replicated(
                sdy.group(3), meshes.get(sdy.group(2), {})), False
        else:
            sm = _SHARDING_ATTR_RE.search(attrs)
            spec = sm.group(1) if sm else ""
            replicated, unknown = _classify(spec)
        seen[idx] = ArgSharding(
            idx, _tensor_bytes(m.group(2)), spec, replicated,
            unknown=unknown)
    args = [seen[i] for i in sorted(seen)]
    return ShardingReport(args, n_donatable=n_donatable)
