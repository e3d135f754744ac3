"""chip_smoke.py's control flow, pass conditions and last line, guarded
in tier-1: its phases run here at ``LlamaConfig.tiny()`` on the CPU
through the size arguments of THIS call — the script itself has no mode
that skips its device check. Plus: importing the package must leave the
XLA backend alone (one process per chip)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke
from paddle_tpu.nlp import LlamaConfig

TINY = dict(tensor_parallel=False)


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_train_phase_tiny(capsys):
    detail = chip_smoke.run_phase(
        "train", chip_smoke.train_phase,
        cfg=LlamaConfig.tiny(**TINY), batch=2, seq=64, steps=3)
    assert len(detail["losses"]) == 3
    assert detail["losses"][-1] < detail["losses"][0]
    assert detail["tpu_custom_calls"] == []  # CPU: interpret mode
    line = _last_json(capsys)
    assert line["phase"] == "train" and line["ok"] is True
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    # read off the program's own compile counters: the phase compiles
    # its step ahead of the dispatch, outside any step span
    assert line["compile_seconds"] > 0 and line["seconds"] > 0
    assert line["compile_requests"]["none"] >= 1
    assert len(line["peak_bytes_in_use"]) == 8


def test_serve_phase_tiny(capsys):
    detail = chip_smoke.run_phase(
        "serve", chip_smoke.serve_phase,
        cfg=LlamaConfig.tiny(**TINY), prompt_lens=(70, 5, 12), max_new=6,
        max_context=128)
    assert detail["finish_reasons"] == ["length"] * 3
    assert detail["engine"]["mixed_steps"] >= 2   # 70 > one 64-chunk
    assert detail["engine"]["decode_quanta"] >= 1
    found = detail["vs_sequential_oracle"]
    assert found["of"] == 6 and found["agree_prefix"] >= 1
    assert found["widest_gap_bf16_steps"] <= chip_smoke.TIE_STEPS
    line = _last_json(capsys)
    assert line["phase"] == "serve"
    # the eager mixed steps ask for their executables under engine.mixed
    assert line["compile_requests"]["mixed"] >= 1


def test_mesh_and_tp_phases_tiny():
    """The --chips 4 phases on four of the virtual CPU devices."""
    cfg = LlamaConfig.tiny(tensor_parallel=True)
    detail = chip_smoke.mesh_train_phase(
        cfg, batch=4, seq=32, steps=2, mp=2, sharding=2)
    assert max(detail["rel_diff"]) <= chip_smoke.MESH_LOSS_RTOL
    detail = chip_smoke.tp_serve_phase(
        cfg, tp=2, prompt_lens=(9, 5), max_new=6, max_context=64)
    assert len(detail["vs_tp1"]) == 2
    assert detail["bit_equal_streams"] == sum(
        f["same_stream"] for f in detail["vs_tp1"])


def test_failed_condition_raises():
    """No phase result is let through: a failed check is an exception,
    and the kernel check bites exactly when the platform is a TPU."""
    with pytest.raises(AssertionError, match="boom"):
        chip_smoke.check(False, "boom")
    chip_smoke._check_kernels(set())  # cpu: nothing to require


def test_check_greedy_ties_and_forks(monkeypatch):
    """Streams may part only at a near-tie of the reference logits: a
    token (or the other path's pick at the fork) further than TIE_STEPS
    bf16 steps below the best one fails the phase."""
    step = 2.0 ** -6                      # bf16 grid at logits in [2, 4)
    logits = np.zeros((3, 8), np.float32)
    logits[:, 0] = 3.0                    # best everywhere
    logits[1, 5] = 3.0 - step             # position 1: a one-step tie
    logits[2, 6] = 3.0 - 9 * step         # position 2: clearly worse
    monkeypatch.setattr(
        chip_smoke, "reference_gaps",
        lambda model, prompt, tokens: (3.0 - logits) / step)
    prompt = np.array([1, 2], np.int32)
    same = chip_smoke.check_greedy(None, prompt, [0, 0, 0], [0, 0, 0], "t")
    assert same["same_stream"] and same["argmax_tokens"] == 3
    tie = chip_smoke.check_greedy(None, prompt, [0, 5, 0], [0, 0, 0], "t")
    assert not tie["same_stream"] and tie["agree_prefix"] == 1
    assert tie["fork_gaps_bf16_steps"] == [1.0, 0.0]
    with pytest.raises(AssertionError, match="below the reference argmax"):
        chip_smoke.check_greedy(None, prompt, [0, 0, 6], [0, 0, 0], "t")
    with pytest.raises(AssertionError, match="no tie explains it"):
        chip_smoke.check_greedy(None, prompt, [0, 0, 0], [0, 0, 6], "t")


def test_kernel_names_from_compiled_text():
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names

    text = "\n".join([
        '%a.1 = bf16[8] custom-call(%x), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(multi_step_fn)/while/'
        'body/jvp(flash_attention_fwd)/pallas_call" stack_frame_id=9}',
        '%b.2 = bf16[8] custom-call(%x), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(f)/transpose(jvp('
        'rms_norm_bwd))/pallas_call"}',
        '%c.3 = f32[8] custom-call(%x), custom_call_target="Sharding"',
        '%d.4 = f32[8] add(%x, %x), metadata={op_name="jit(f)/add"}',
    ])
    assert compiled_kernel_names(text) == {
        "flash_attention_fwd", "rms_norm_bwd"}


def test_last_line_shape_and_cpu_refusal(capsys):
    """On this CPU the script's own entry point must refuse: non-zero,
    ``"ok": false`` — and the success line has exactly the contract's
    keys."""
    assert chip_smoke.main([]) == 1
    last = _last_json(capsys)
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "needs a TPU" in last["error"]
    ok = json.loads(chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}))
    assert ok == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


def test_import_leaves_backend_alone():
    """`import paddle_tpu` (hence the launcher parent, which lives in
    the package) must not initialise an XLA backend: on a machine whose
    chip belongs to one process, the parent would take it from the
    worker that needs it."""
    code = (
        "import paddle_tpu, paddle_tpu.distributed.launch.main\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "paddle_tpu.seed(3)\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "paddle_tpu.rand([2])\n"
        "assert xla_bridge.backends_are_initialized()\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120,
                   cwd=os.path.dirname(os.path.abspath(chip_smoke.__file__)))
