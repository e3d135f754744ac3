"""Shared helpers for the Pallas kernel tier."""
from __future__ import annotations

import re

import jax

from ...parallel import mesh as mesh_state
from ...parallel.mesh import data_axes  # noqa: F401  (the kernels' import)


def interpret_mode():
    """Pallas kernels run in interpret mode off-TPU (CPU test suite)."""
    return jax.default_backend() != "tpu"


def use_pallas_kernels():
    """The rule every route to a Pallas kernel outside training follows
    (attention over the serving pools, the routed experts' products): the
    kernels where the backend is a TPU (or ``FLAGS_pallas_force`` sends a
    CPU test through the interpreter), unless ``FLAGS_use_pallas_kernels``
    is off."""
    from ...core.flags import get_flags

    flags = get_flags(["FLAGS_use_pallas_kernels", "FLAGS_pallas_force"])
    return flags["FLAGS_use_pallas_kernels"] and (
        jax.default_backend() == "tpu" or flags["FLAGS_pallas_force"])


_programs_counted = {}   # counter name -> the trace it last counted


def count_traced_program(counter, path):
    """Raise ``counter{path}`` ONCE for the program being traced, however
    many layers ask: a route is static per compiled program."""
    trace = jax.core.get_opaque_trace_state()
    if _programs_counted.get(counter.name) != trace:
        _programs_counted[counter.name] = trace
        counter.inc(path=path)


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def per_shard(kernel, in_specs, out_specs):
    """Mosaic kernels cannot be partitioned by GSPMD ("wrap the call in
    a shard_map" — the TPU lowering refuses them under a mesh of more
    than one device, whatever the operands' layout). So under an
    installed mesh every kernel entry point runs its kernel PER SHARD,
    split over the dims whose work is independent (batch rows over the
    data axes, heads over ``mp``, the rows of a sequence-split hidden
    stream over the axis its owner names) and replicated over the rest.
    With no mesh, or already inside a shard_map body, it is the kernel
    itself."""
    mesh = mesh_state.get_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return kernel
    return jax.shard_map(kernel, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def head_axis(*head_counts: int):
    """``"mp"`` when every head count divides over it, else None."""
    mp = mesh_state.mesh_axis_size("mp")
    return "mp" if mp > 1 and all(h % mp == 0 for h in head_counts) \
        else None


_PALLAS_OP = re.compile(r'op_name="[^"]*?([A-Za-z0-9_]+)\)*/pallas_call"')


def compiled_kernel_names(hlo_text: str) -> set[str]:
    """The ``name=`` of every Pallas kernel that is in a COMPILED TPU
    program's text (``compiled.as_text()``) as a Mosaic custom call —
    the proof that a kernel ran there and not a reference path (which
    interpret mode, off-TPU, would lower to plain HLO instead)."""
    names = set()
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            m = _PALLAS_OP.search(line)
            names.add(m.group(1) if m else "<unnamed>")
    return names
