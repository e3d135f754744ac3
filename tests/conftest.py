"""Test config: force an 8-device virtual CPU platform.

The reference's distributed CI spawns N processes on one host
(SURVEY.md §4); the TPU-native analog is cheaper — one process with 8
virtual CPU devices, so every mesh/sharding test runs anywhere.
Must run before any jax backend is initialized.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


@pytest.fixture(autouse=True)
def _no_mesh_leak():
    """A test that dies mid-run with the global mesh installed must not
    shard-pollute every later test's device_put (seen: the hybrid
    TP/CP train tests leaking a dp4xmp2 mesh into single-device
    tests, which then fail batch-divisibility checks)."""
    yield
    from paddle_tpu.parallel import mesh as mesh_state

    if mesh_state.has_mesh():
        mesh_state.set_mesh(None)


@pytest.fixture(autouse=True)
def _no_pallas_force_leak():
    """``benchmark/selfcheck.py::rehearse_cell`` forces the kernel routes
    and does not put the flag back; a later test of the same worker then
    lowers through the interpreter's kernels (seen: the jaxpr walk of
    ``tests/test_serving_counts.py`` meeting a DMA semaphore whenever
    xdist ran a ``*_benchmark.py`` file before it)."""
    yield
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_pallas_force": False})


@pytest.fixture
def pallas_forced():
    """The Pallas kernel routes taken off-TPU (through the interpreter)
    for one test."""
    import paddle_tpu as paddle

    paddle.set_flags({"FLAGS_pallas_force": True})
    yield
    paddle.set_flags({"FLAGS_pallas_force": False})


@pytest.fixture
def chunk_programs():
    """A reader of ``serving_chunk_attention_programs_total``: the
    process's count of traced mixed programs, by ``path``."""
    from paddle_tpu.nlp.paged_attention import chunk_attention_programs

    counter = chunk_attention_programs()
    return lambda: {p: counter.value(path=p) for p in ("xla", "kernel")}


@pytest.fixture
def toy(request):
    """(configuration, model, get_leaf) of the toy of the family whose row
    the test's module names ``ROW`` (``tests/family_harness.py`` builds it
    once a worker)."""
    from family_harness import toy as build

    return build(request.module.ROW.name)
