"""The program reads its own device trace (ISSUE 37):
``paddle_tpu.profiler.load_profiler_result`` on two traces recorded on a
TPU v5e (the benchmark's ``three_matmul`` trace, which has no named scope,
read only; and ``tests/data/scoped_steps.xplane.pb``, recorded by
``scripts/record_scoped_trace.py``: scopes of the vocabulary, a ``while``
body, a backward pass and the program's host spans), the names it parses
(scopes and phases out of an ``op_name``, device groups out of an
instruction's text, the mesh axis they run over), the rules that give an
instruction of a program's HLO proto its scope, and the three ways to the
tables: the function, ``Profiler.summary()`` and the CLI. No chip.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.profiler import Profiler, load_profiler_result, reader
from paddle_tpu.profiler.scopes import SCOPES, SPANS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLAIN = os.path.join(ROOT, "benchmark", "data", "three_matmul.xplane.pb")
SCOPED = os.path.join(ROOT, "tests", "data", "scoped_steps.xplane.pb")
# sharding x mp, mp minor: the mesh cell's layout on one 2x2 host
MESH = {"sharding": [[0, 2], [1, 3]], "mp": [[0, 1], [2, 3]]}


def scope_total(result, program):
    return sum(row["seconds"] for row in result.scopes[program].values())


def op_total(result, program):
    return sum(op["seconds"] for op in result.ops
               if op["program"] == program)


# ------------------------------------------- (a) the benchmark's trace
@pytest.fixture(scope="module")
def plain():
    return load_profiler_result(PLAIN)


def test_plain_trace_programs_and_sums(plain):
    """Two programs, three calls each; per program the scope seconds ARE
    the operation seconds; the reducer of the benchmark reads the same
    file to the same sums."""
    assert {p: v["calls"] for p, v in plain.programs.items()} == {
        "jit_quantum": 3, "jit_tiny": 3}
    for program in plain.programs:
        assert scope_total(plain, program) == pytest.approx(
            op_total(plain, program), rel=1e-12)
        assert plain.programs[program]["ops_named"] == 0
    sys.path.insert(0, ROOT)
    from benchmark.harness import xplane

    reduced = xplane.reduce_trace(PLAIN)
    for program in plain.programs:
        theirs = sum(v for k, v in reduced["op_seconds"].items()
                     if k.startswith(program + "/"))
        assert scope_total(plain, program) == pytest.approx(theirs, rel=5e-3)
    assert plain.busy_s == pytest.approx(reduced["busy_s"], rel=1e-3)
    assert plain.window_s == pytest.approx(reduced["window_s"], rel=1e-6)


def test_plain_trace_names_its_fusions(plain):
    fusions = [op for op in plain.ops
               if op["base"] == "convolution_tanh_fusion"]
    assert len(fusions) == 3
    for op in fusions:
        assert op["program"] == "jit_quantum"
        assert op["op_name"] == "jit(quantum)/dot_general"
        assert (op["scope"], op["phase"]) == ("unscoped", "fwd")
        assert op["calls"] == 3
    assert set(plain.scopes["jit_quantum"]) == {("unscoped", "fwd")}
    assert set(plain.scopes["jit_quantum"][("unscoped", "fwd")]["ops"]) == {
        "convolution_tanh_fusion", "copy-start", "copy-done"}
    assert plain.mixed_fusions == {}


def test_plain_trace_idle_adds_up(plain):
    """Its host annotations are the benchmark's, not the program's: all
    of the idle time is outside the program's spans, and it is the window
    less the busy time."""
    assert set(plain.idle_gaps) == {"outside-spans"}
    assert sum(plain.idle_gaps.values()) == pytest.approx(
        plain.window_s - plain.busy_s, abs=1e-9)
    assert plain.collectives == [] and plain.collective_s == 0.0


# ------------------------------------------------ (b) the scoped trace
@pytest.fixture(scope="module")
def scoped():
    return load_profiler_result(SCOPED)


def test_scoped_trace_is_small():
    assert os.path.getsize(SCOPED) < 100_000


def test_scoped_trace_sums_are_pinned(scoped):
    """Recorded once on a v5e: the seconds are the file's, to the
    picosecond the reader adds them in."""
    assert scoped.devices == 1
    assert {p: v["calls"] for p, v in scoped.programs.items()} == {
        "jit_decode": 3, "jit_train": 1}
    for program, p in scoped.programs.items():
        assert scope_total(scoped, program) == pytest.approx(
            op_total(scoped, program), rel=1e-12)
        assert p["ops_named"] > 0
    got = {p: round(scope_total(scoped, p) * 1e6, 3)
           for p in scoped.programs}
    assert got == PINNED_PROGRAM_US
    assert round(scoped.busy_s * 1e6, 3) == PINNED_BUSY_US
    assert sum(scoped.idle_gaps.values()) == pytest.approx(
        scoped.window_s - scoped.busy_s, abs=1e-9)


# microseconds, from the one recording in tests/data/
PINNED_PROGRAM_US = {"jit_decode": 188.888, "jit_train": 27.957}
PINNED_BUSY_US = 217.022


def test_scoped_trace_scopes_phases_and_the_loop(scoped):
    """The decode program's products read their scopes, the ``while``
    itself is not counted beside its body, and the train program's
    backward reads ``bwd`` under the scope of the forward it transposes.
    XLA fused the argmax into the head's product and the update into the
    backward: each fusion is charged whole to its root's scope, and the
    table says how many seconds ran in such fusions."""
    decode = scoped.scopes["jit_decode"]
    assert {s for s, _ in decode} >= {"attn.proj", "mlp", "head"}
    assert all(phase == "fwd" for _, phase in decode)
    assert not any(op["base"] in reader.CONTAINERS for op in scoped.ops)
    # three iterations of the body a call, three calls
    body = [op for op in scoped.ops
            if op["program"] == "jit_decode" and op["scope"] == "mlp"]
    assert body and max(op["calls"] for op in body) == 9
    named = [op for op in body if op["op_name"]]
    assert named and all("while/body" in op["op_name"] for op in named)
    train = scoped.scopes["jit_train"]
    assert ("mlp", "bwd") in train and ("mlp", "fwd") in train
    share = decode.get(("unscoped", "fwd"), {"seconds": 0.0})["seconds"]
    assert share < 0.05 * scope_total(scoped, "jit_decode")
    head = next(op for op in scoped.ops if op["scope"] == "head")
    assert scoped.mixed_fusions["jit_decode"] == pytest.approx(
        head["seconds"])
    assert "in fusions whose bodies span several scopes" in scoped.tables()


def test_scoped_trace_charges_what_the_compiler_made(scoped):
    """An instruction without an ``op_name`` (the relayout ``copy`` in
    the loop's body, a ``copy-start``) is charged to what consumes it."""
    made = [op for op in scoped.ops if not op["op_name"]]
    assert {op["base"] for op in made} == {"copy", "copy-start",
                                           "copy-done"}
    copy = next(op for op in made if op["base"] == "copy")
    assert (copy["scope"], copy["calls"]) == ("mlp", 9)
    assert scoped.programs["jit_decode"]["ops_named"] == 3


def test_scoped_trace_idle_by_program_span(scoped):
    """The device's idle time lands under the innermost host span open:
    the enqueue and the sync of each pump, the pumps' own rest, and
    outside every span the sleeps between pumps."""
    assert set(scoped.idle_gaps) <= set(SPANS) | {"outside-spans"}
    assert {"engine.decode.enqueue", "engine.decode.sync",
            "outside-spans"} <= set(scoped.idle_gaps)
    assert scoped.idle_gaps["outside-spans"] > 3 * 0.002 * 0.9


# ------------------------------------------------------- (c) the names
@pytest.mark.parametrize("op_name,want", [
    ("jit(quantum)/jit(main)/while/body/attn.proj/dot_general",
     ("attn.proj", "fwd")),
    ("jit(multi_step_fn)/while/body/jvp(mlp)/mul", ("mlp", "fwd")),
    ("jit(multi_step_fn)/while/body/transpose(jvp(attn.window))/"
     "flash_attention_bwd_dq", ("attn.window", "bwd")),
    ("jit(f)/transpose(jvp(checkpoint))/rematted_computation/norm/mul",
     ("norm", "bwd")),
    ("jit(mixed)/moe.experts/moe.dispatch/sort", ("moe.dispatch", "fwd")),
    ("jit(mixed)/mla/attn.proj/dot_general", ("attn.proj", "fwd")),
    ("jit(mixed)/attn.projection/add", ("unscoped", "fwd")),
    ("jit(quantum)/dot_general", ("unscoped", "fwd")),
    ("", ("unscoped", "fwd")),
])
def test_scope_of_an_op_name(op_name, want):
    assert reader.scope_of(op_name) == want


def test_a_name_the_compiler_wrote_over_an_op_name():
    """The TPU compiler expands ``ragged_dot`` into calls of its own whose
    ``op_name`` is ``ragged-dot-none`` / ``ragged-dot-metadata``."""
    assert reader.scope_of("ragged-dot-none") == ("moe.products", "fwd")
    assert reader.scope_of("ragged-dot-metadata") == ("moe.products", "fwd")
    assert reader.scope_of("ragged") == ("unscoped", "fwd")


def test_resolve_scopes_by_hand():
    """Own ``op_name`` first; a fusion without one takes what most of its
    body carries; a prefetch takes its consumer's scope, a write-back its
    producer's; plumbing takes none and passes none on."""
    instrs = {
        1: ["w", "parameter", "", [], []],
        2: ["copy-start.1", "copy-start", "", [1], []],
        3: ["copy-done.1", "copy-done", "", [2], []],
        4: ["fusion.1", "fusion", "jit(f)/mlp/dot_general", [3], [10]],
        5: ["fusion.2", "fusion", "", [4], [11]],
        6: ["copy.7", "copy", "", [5], []],
        7: ["tuple.1", "tuple", "", [6], []],
        8: ["add.3", "add", "jit(f)/add", [1], []],
        20: ["dot.1", "dot", "jit(f)/mlp/dot_general", [], []],
        21: ["a.1", "add", "jit(f)/transpose(jvp(norm))/add", [], []],
        22: ["b.1", "multiply", "jit(f)/transpose(jvp(norm))/mul", [], []],
        23: ["c.1", "convert", "jit(f)/head/convert_element_type", [], []],
        24: ["t.1", "tuple", "", [21, 22, 23], []],
    }
    comps = {0: [1, 2, 3, 4, 5, 6, 7, 8], 10: [20], 11: [21, 22, 23, 24]}
    got = reader.resolve_scopes(instrs, comps)
    assert got["fusion.1"] == ("mlp", "fwd", "jit(f)/mlp/dot_general",
                               True, False)
    assert got["fusion.2"][:2] == ("norm", "bwd")
    assert got["fusion.2"][3:] == (False, True)   # not its own; several
    assert got["copy-done.1"][:2] == got["copy-start.1"][:2] == (
        "mlp", "fwd")
    assert got["copy.7"][:2] == ("norm", "bwd")
    assert got["tuple.1"][:2] == got["w"][:2] == ("unscoped", "fwd")
    assert got["add.3"][:2] == ("unscoped", "fwd")


@pytest.mark.parametrize("text,groups,axis", [
    ("%ag = bf16[8,4096]{1,0} all-gather(bf16[4,4096]{1,0} %p), "
     "channel_id=3, replica_groups={{0,1},{2,3}}, dimensions={0}, "
     "use_global_device_ids=true", ((0, 1), (2, 3)), "mp"),
    ("%ar.1 = f32[64]{0} all-reduce(f32[64]{0} %x), "
     "replica_groups={{0,2},{1,3}}, to_apply=%add",
     ((0, 2), (1, 3)), "sharding"),
    ("%psum.5 = f32[] all-reduce(f32[] %x), replica_groups={{0,1,2,3}}, "
     "to_apply=%add", ((0, 1, 2, 3),), "sharding+mp"),
    ("%ar = f32[8] all-reduce-start(f32[8] %x), replica_groups=[2,2]<=[4]",
     ((0, 1), (2, 3)), "mp"),
    ("%rs = f32[8] reduce-scatter(f32[16] %x), "
     "replica_groups=[2,2]<=[2,2]T(1,0), dimensions={0}",
     ((0, 2), (1, 3)), "sharding"),
    ("%ar = f32[8] all-reduce(f32[8] %x), replica_groups={}", (),
     "sharding+mp"),
    ("%cp = f32[8] collective-permute(f32[8] %x), "
     "source_target_pairs={{0,1},{1,0},{2,3},{3,2}}",
     ((0, 1), (1, 0), (2, 3), (3, 2)), "mp"),
    ("%fusion.3 = f32[8] fusion(f32[8] %x), kind=kLoop", None, "unknown"),
])
def test_axis_of_a_collective(text, groups, axis):
    assert reader.replica_groups(text) == groups
    assert reader.axis_of(groups, MESH) == axis
    assert reader.axis_of(groups, None) == "unknown"


def test_a_done_takes_the_axis_of_its_start():
    start = ("%collective-permute-start.17 = (bf16[32,7168], bf16[32,7168]) "
             "collective-permute-start(%slice.308), channel_id=400, "
             "source_target_pairs={{0,2},{1,3}}")
    done = ("%collective-permute-done.17 = bf16[32,7168] "
            "collective-permute-done(%collective-permute-start.17)")
    groups = {"%collective-permute-start.17": reader.replica_groups(start)}
    assert reader.collective_of(start, groups, MESH) == (
        "collective-permute", "sharding")
    assert reader.collective_of(done, groups, MESH) == (
        "collective-permute", "sharding")
    assert reader.collective_of(done, {}, MESH) == (
        "collective-permute", "unknown")
    assert reader.collective_of("%fusion.3 = f32[8] fusion(f32[8] %x)",
                                groups, MESH) is None


def test_axis_groups_of_a_mesh():
    """``parallel.mesh.axis_groups`` of a 2 x 2 mesh is the hand-made
    one: partition ids in the mesh's flat order."""
    import jax
    from jax.sharding import Mesh

    from paddle_tpu.parallel import mesh as mesh_state

    devices = np.array(jax.devices()[:4]).reshape(2, 2)
    assert mesh_state.axis_groups(Mesh(devices, ("sharding", "mp"))) == MESH
    assert mesh_state.axis_groups(
        Mesh(devices.reshape(1, 4), ("dp", "mp"))) == {"mp": [[0, 1, 2, 3]]}
    if not mesh_state.has_mesh():
        assert mesh_state.axis_groups() == {}


def test_innermost_span_segments():
    spans = [("door.pump", 10, 50), ("engine.step", 12, 48),
             ("engine.decode.enqueue", 15, 20), ("door.pump", 60, 70)]
    assert reader._innermost(spans, 0, 80) == [
        ("outside-spans", 0, 10), ("door.pump", 10, 12),
        ("engine.step", 12, 15), ("engine.decode.enqueue", 15, 20),
        ("engine.step", 20, 48), ("door.pump", 48, 50),
        ("outside-spans", 50, 60), ("door.pump", 60, 70),
        ("outside-spans", 70, 80)]
    # clipped to the window
    assert reader._innermost(spans, 16, 18) == [
        ("engine.decode.enqueue", 16, 18)]


def test_the_vocabulary_is_plain_names():
    for name in SCOPES:
        assert "/" not in name and "(" not in name and name != "unscoped"
        assert reader.scope_of(f"jit(f)/{name}/add") == (name, "fwd")


# ------------------------------------------------- the ways to the tables
def test_tables_cli_and_summary(tmp_path):
    text = load_profiler_result(SCOPED).tables()
    for heading in ("programs (XLA modules)", "scopes (", "idle gaps ("):
        assert heading in text
    out = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.obs", "profile", "--in", SCOPED],
        capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == text.strip()
    as_json = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.obs", "profile", "--in", SCOPED,
         "--format", "json"], capture_output=True, text=True, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert set(json.loads(as_json.stdout)) >= {
        "programs", "scopes", "ops", "collectives", "idle_gaps"}
    # a profiler that traced nothing keeps the step-time line alone
    prof = Profiler(timer_only=True, log_dir=str(tmp_path))
    prof.start()
    prof.step()
    prof.stop()
    assert prof.summary().startswith("steps: 1  avg:")
    assert "\n" not in prof.summary()
    # one that traced on the CPU says why it has no tables (no device
    # plane), under the same first line
    import jax.numpy as jnp

    with Profiler(log_dir=str(tmp_path / "cpu")) as prof:
        jnp.ones((8, 8)).sum().block_until_ready()
        prof.step()
    first, rest = prof.summary().split("\n", 1)
    assert first.startswith("steps: 1  avg:")
    assert rest.startswith("(no device tables:") and "XLA Ops" in rest


def test_a_directory_reads_its_newest_trace(tmp_path):
    for stamp in ("2026_01_01_00_00_00", "2026_01_02_00_00_00"):
        d = tmp_path / "plugins" / "profile" / stamp
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(
            open(SCOPED if "02" in stamp else PLAIN, "rb").read())
    assert set(load_profiler_result(str(tmp_path)).programs) == {
        "jit_decode", "jit_train"}
    with pytest.raises(FileNotFoundError):
        load_profiler_result(str(tmp_path / "nothing"))


def test_the_engine_does_not_import_the_reader():
    code = ("import sys, paddle_tpu.serving, paddle_tpu.jit.train; "
            "assert 'paddle_tpu.profiler.reader' not in sys.modules")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                         capture_output=True, text=True,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
