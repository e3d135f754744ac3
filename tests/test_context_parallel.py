"""Context parallelism (sep axis): ring attention + Ulysses parallel==serial
oracles on the 8-device virtual CPU mesh (SURVEY.md §5 long-context)."""
import numpy as np
import pytest
import jax
from jax.sharding import Mesh

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.parallel import mesh as mesh_state
from paddle_tpu.distributed.fleet.meta_parallel import (
    ring_flash_attention, ulysses_attention, sep_attention,
    split_inputs_sequence_dim,
)


@pytest.fixture(autouse=True)
def reset_mesh():
    yield
    mesh_state.set_mesh(None)


def _mk_qkv(b=2, s=64, h=4, hk=None, d=16, seed=0):
    rng = np.random.RandomState(seed)
    hk = hk or h
    q = paddle.to_tensor(rng.randn(b, s, h, d).astype("float32"))
    k = paddle.to_tensor(rng.randn(b, s, hk, d).astype("float32"))
    v = paddle.to_tensor(rng.randn(b, s, hk, d).astype("float32"))
    for t in (q, k, v):
        t.stop_gradient = False
    return q, k, v


def _sep_mesh(n=4):
    devs = np.array(jax.devices()[:n]).reshape(1, n)
    mesh = Mesh(devs, ("dp", "sep"))
    mesh_state.set_mesh(mesh)
    return mesh


@pytest.mark.parametrize("causal", [False, True])
def test_ring_equals_serial(causal):
    q, k, v = _mk_qkv()
    ref = F.scaled_dot_product_attention(q, k, v, is_causal=causal)
    _sep_mesh(4)
    out = ring_flash_attention(q, k, v, is_causal=causal)
    np.testing.assert_allclose(
        np.asarray(out._value), np.asarray(ref._value), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_equals_serial(causal):
    q, k, v = _mk_qkv()
    ref = F.scaled_dot_product_attention(q, k, v, is_causal=causal)
    _sep_mesh(4)
    out = ulysses_attention(q, k, v, is_causal=causal)
    np.testing.assert_allclose(
        np.asarray(out._value), np.asarray(ref._value), rtol=2e-5, atol=2e-5
    )


def test_ring_gqa():
    q, k, v = _mk_qkv(h=8, hk=2)
    ref = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    _sep_mesh(4)
    out = ring_flash_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(
        np.asarray(out._value), np.asarray(ref._value), rtol=2e-5, atol=2e-5
    )


@pytest.mark.parametrize("schedule", ["ring", "ulysses"])
def test_sep_attention_grads_match(schedule):
    q1, k1, v1 = _mk_qkv(seed=3)
    ref = F.scaled_dot_product_attention(q1, k1, v1, is_causal=True)
    loss1 = (ref * ref).sum()
    g_ref = paddle.grad(loss1, [q1, k1, v1])

    _sep_mesh(4)
    q2, k2, v2 = _mk_qkv(seed=3)
    out = sep_attention(q2, k2, v2, is_causal=True, schedule=schedule)
    loss2 = (out * out).sum()
    g = paddle.grad(loss2, [q2, k2, v2])
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a._value), np.asarray(b._value), rtol=1e-4, atol=1e-4
        )


def test_ulysses_head_divisibility_error():
    _sep_mesh(4)
    q, k, v = _mk_qkv(h=2, hk=2)
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v)


def test_no_mesh_falls_back_to_serial():
    q, k, v = _mk_qkv()
    out = ring_flash_attention(q, k, v, is_causal=True)
    ref = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(
        np.asarray(out._value), np.asarray(ref._value), rtol=1e-6
    )


def test_split_inputs_sequence_dim():
    _sep_mesh(4)
    x = paddle.to_tensor(np.random.randn(2, 64, 8).astype("float32"))
    y = split_inputs_sequence_dim(x)
    sh = y._value.sharding
    assert sh.spec[1] == "sep"


def test_ring_in_jit_under_mesh():
    """The ring schedule must compile inside jax.jit (train-step path)."""
    _sep_mesh(4)
    q, k, v = _mk_qkv(s=32)

    import jax.numpy as jnp

    def f(qv, kv, vv):
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.core import autograd

        with autograd.no_grad():
            out = ring_flash_attention(
                Tensor(qv, stop_gradient=True),
                Tensor(kv, stop_gradient=True),
                Tensor(vv, stop_gradient=True),
                is_causal=True,
            )
        return out._value

    jitted = jax.jit(f)
    got = jitted(q._value, k._value, v._value)
    ref = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref._value), rtol=2e-5, atol=2e-5
    )


def test_llama_ring_cp_train_matches_serial():
    """Full Llama train step with ring context parallelism over sep==2
    matches the serial step (sep axis end-to-end through the model)."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.nlp import (
        LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    )
    from paddle_tpu.jit.train import JittedTrainStep

    def losses(sep, steps=3):
        mesh_state.set_mesh(None)
        try:
            if sep > 1:
                strategy = fleet.DistributedStrategy()
                strategy.hybrid_configs = {
                    "dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                    "sep_degree": sep,
                }
                fleet.init(is_collective=True, strategy=strategy)
            paddle.seed(0)
            cfg = LlamaConfig.tiny(
                tensor_parallel=True,
                context_parallel="ring" if sep > 1 else None,
            )
            m = LlamaForCausalLM(cfg)
            crit = LlamaPretrainingCriterion()
            opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
            step = JittedTrainStep(m, lambda o, l: crit(o, l), opt)
            ids = paddle.to_tensor(
                np.random.RandomState(1).randint(0, 128, (4, 32)))
            return [float(step(ids, ids)) for _ in range(steps)]
        finally:
            # a mid-step failure must not leak the hybrid mesh into
            # later tests' device_put placements
            mesh_state.set_mesh(None)

    lp = losses(sep=2)
    ls = losses(sep=1)
    np.testing.assert_allclose(lp, ls, rtol=5e-4, atol=5e-5)


def test_custom_scale_consistent_with_and_without_mesh():
    q, k, v = _mk_qkv()
    no_mesh = ring_flash_attention(q, k, v, is_causal=True, scale=0.5)
    _sep_mesh(4)
    with_mesh = ring_flash_attention(q, k, v, is_causal=True, scale=0.5)
    np.testing.assert_allclose(
        np.asarray(with_mesh._value), np.asarray(no_mesh._value),
        rtol=2e-5, atol=2e-5,
    )


def test_split_inputs_skips_non_seq_leaves():
    _sep_mesh(4)
    batch = {
        "input_ids": paddle.to_tensor(np.zeros((2, 64), "int32")),
        "lengths": paddle.to_tensor(np.zeros((2,), "int32")),
        "mask": None,
    }
    out = split_inputs_sequence_dim(batch)
    assert out["mask"] is None
    assert out["lengths"].shape == [2]
    assert out["input_ids"]._value.sharding.spec[1] == "sep"


def test_seq_divisibility_error():
    _sep_mesh(4)
    q, k, v = _mk_qkv(s=66)
    with pytest.raises(ValueError, match="seq len"):
        ring_flash_attention(q, k, v, is_causal=True)
