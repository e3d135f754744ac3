"""fused_linear_cross_entropy: chunked fused lm-head+CE must be
numerically identical to the unfused logits path (loss AND grads), in
and out of jit, packed and dense."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.incubate.nn.functional import fused_linear_cross_entropy


def _data(n=50, h=16, v=37, seed=0):
    r = np.random.RandomState(seed)
    hid = r.randn(n, h).astype(np.float32)
    w = (r.randn(h, v) * 0.1).astype(np.float32)
    y = r.randint(0, v, (n,)).astype(np.int64)
    return hid, w, y


def test_fused_lce_matches_unfused_loss_and_grads():
    hid_np, w_np, y_np = _data()
    # some ignored rows
    y_np[[3, 7]] = -100

    def run(fused):
        hid = paddle.to_tensor(hid_np)
        w = paddle.to_tensor(w_np)
        hid.stop_gradient = False
        w.stop_gradient = False
        if fused:
            loss = fused_linear_cross_entropy(
                hid, w, paddle.to_tensor(y_np), chunk_rows=16)
        else:
            logits = paddle.matmul(hid, w)
            loss = F.cross_entropy(logits, paddle.to_tensor(y_np))
        loss.backward()
        return float(loss), np.asarray(hid.grad._value), \
            np.asarray(w.grad._value)

    l0, gh0, gw0 = run(False)
    l1, gh1, gw1 = run(True)
    np.testing.assert_allclose(l1, l0, rtol=1e-6)
    np.testing.assert_allclose(gh1, gh0, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(gw1, gw0, rtol=1e-5, atol=1e-7)


def test_fused_lce_pads_non_divisible_rows():
    hid_np, w_np, y_np = _data(n=23)
    loss_ref = float(F.cross_entropy(
        paddle.matmul(paddle.to_tensor(hid_np), paddle.to_tensor(w_np)),
        paddle.to_tensor(y_np)))
    loss = float(fused_linear_cross_entropy(
        paddle.to_tensor(hid_np), paddle.to_tensor(w_np),
        paddle.to_tensor(y_np), chunk_rows=8))
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-6)


def test_fused_lce_bias():
    hid_np, w_np, y_np = _data(n=32)
    b_np = np.random.RandomState(5).randn(w_np.shape[1]).astype(np.float32)
    logits = paddle.matmul(paddle.to_tensor(hid_np), paddle.to_tensor(w_np)) \
        + paddle.to_tensor(b_np)
    loss_ref = float(F.cross_entropy(logits, paddle.to_tensor(y_np)))
    loss = float(fused_linear_cross_entropy(
        paddle.to_tensor(hid_np), paddle.to_tensor(w_np),
        paddle.to_tensor(y_np), bias=paddle.to_tensor(b_np), chunk_rows=8))
    np.testing.assert_allclose(loss, loss_ref, rtol=1e-6)


def test_fused_lce_under_tensor_parallel_matches_serial():
    """The fused criterion composed with TP (mp2 x dp) on the 8-device
    mesh: the llama model's mp-sharded layers + fused lm-head+CE must
    reproduce the mesh-less serial fused run AND the serial unfused run
    over 2 jitted train steps — the hybrid-parallel pretrain recipe the
    north-star config would use."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.parallel import mesh as mesh_state
    from paddle_tpu.nlp import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )
    from paddle_tpu.jit.train import JittedTrainStep

    ids_np = np.random.RandomState(0).randint(0, 128, (4, 32))

    def run(mesh, fuse):
        mesh_state.set_mesh(None)
        try:
            if mesh:
                strategy = fleet.DistributedStrategy()
                strategy.hybrid_configs = {
                    "dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
                    "sharding_degree": 1,
                }
                fleet.init(is_collective=True, strategy=strategy)
            paddle.seed(0)
            cfg = LlamaConfig.tiny(tensor_parallel=True,
                                   fuse_linear_cross_entropy=fuse)
            model = LlamaForCausalLM(cfg)
            crit = LlamaPretrainingCriterion(
                cfg, lm_head=model.lm_head if fuse else None)
            opt = paddle.optimizer.AdamW(1e-3,
                                         parameters=model.parameters())
            step = JittedTrainStep(model, lambda o, l: crit(o, l), opt)
            ids = paddle.to_tensor(ids_np)
            return [float(step(ids, ids)) for _ in range(2)]
        finally:
            # a mid-step failure must not leak the dp4xmp2 mesh into
            # later tests' device_put placements
            mesh_state.set_mesh(None)

    serial_unfused = run(False, False)
    serial_fused = run(False, True)
    tp_fused = run(True, True)
    np.testing.assert_allclose(serial_fused, serial_unfused,
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_allclose(tp_fused, serial_fused,
                               rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("packed", [False, True])
def test_llama_fused_criterion_matches_unfused_train(packed):
    """Two jitted train steps at tiny shape: fused-loss config must track
    the unfused config's losses exactly (same seed, same data)."""
    from paddle_tpu.nlp import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )
    from paddle_tpu.jit.train import JittedTrainStep

    ids_np = np.random.RandomState(0).randint(0, 128, (1 if packed else 2, 64))
    cu = np.asarray([0, 20, 45, 64], np.int32) if packed else None

    def run(fuse):
        paddle.seed(0)
        cfg = LlamaConfig.tiny(tensor_parallel=False,
                               fuse_linear_cross_entropy=fuse)
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(
            cfg, lm_head=model.lm_head if fuse else None)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
        if packed:
            cu_t = paddle.to_tensor(cu)

            def criterion(out, labels):
                return crit(out, labels, cu_seqlens=cu_t)

            import types

            orig_forward = model.forward
            model.forward = types.MethodType(
                lambda self, x: orig_forward(x, cu_seqlens=cu_t), model)
        else:
            def criterion(out, labels):
                return crit(out, labels)
        step = JittedTrainStep(model, criterion, opt)
        ids = paddle.to_tensor(ids_np)
        return [float(step(ids, ids)) for _ in range(2)]

    ref = run(False)
    got = run(True)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=1e-6)
