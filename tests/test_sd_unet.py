"""SD-UNet conditional diffusion (BASELINE config #5): forward shapes,
training step, and the one-program jitted DDIM denoising loop."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision.models import (
    SDUNetConfig, UNet2DConditionModel, DDIMScheduler, ddim_sample,
)


def _build(b=2):
    paddle.seed(0)
    cfg = SDUNetConfig.tiny()
    unet = UNet2DConditionModel(cfg)
    rng = np.random.RandomState(0)
    lat = paddle.to_tensor(
        rng.randn(b, cfg.in_channels, cfg.sample_size,
                  cfg.sample_size).astype("f4"))
    ctx = paddle.to_tensor(
        rng.randn(b, 6, cfg.cross_attention_dim).astype("f4"))
    return cfg, unet, lat, ctx


def test_unet_forward_shape():
    cfg, unet, lat, ctx = _build()
    t = paddle.to_tensor(np.array([10, 500], "i4"))
    # one jitted program (ROADMAP D7's rule): eagerly this forward is
    # hundreds of one-primitive compiles, 94 s of tier-1 where this is 3
    out = jax.jit(unet)(lat, t, ctx)
    assert out.shape == list(lat.shape)


@pytest.mark.slow  # ~20s (full UNet fwd+bwd+opt, 3 steps); the
# forward-shape test keeps the architecture covered in tier-1 — the
# 870s ceiling forced a re-tier as the suite grew (PR 7)
def test_unet_denoising_train_step():
    cfg, unet, lat, ctx = _build()
    sched = DDIMScheduler()
    opt = paddle.optimizer.AdamW(1e-3, parameters=unet.parameters())
    rng = np.random.RandomState(1)
    noise = paddle.to_tensor(np.asarray(lat._value) * 0.0 +
                             rng.randn(*lat.shape).astype("f4"))
    t = paddle.to_tensor(np.array([100, 700], "i4"))
    losses = []
    for _ in range(4):
        eps = unet(lat, t, ctx)
        loss = ((eps - noise) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_ddim_sample_one_program():
    cfg, unet, lat, ctx = _build()
    unet.eval()
    out = ddim_sample(unet, lat, ctx, num_inference_steps=4)
    assert out.shape == list(lat.shape)
    assert np.isfinite(np.asarray(out._value)).all()
    # deterministic (eta=0): same inputs, same sample
    out2 = ddim_sample(unet, lat, ctx, num_inference_steps=4)
    np.testing.assert_allclose(
        np.asarray(out._value), np.asarray(out2._value), rtol=1e-6)


def test_scheduler_timesteps_descend():
    s = DDIMScheduler(num_train_timesteps=1000)
    ts = s.timesteps(10)
    assert len(ts) == 10 and (np.diff(ts) < 0).all()


def test_ddim_loop_cached_across_calls():
    cfg, unet, lat, ctx = _build()
    unet.eval()
    ddim_sample(unet, lat, ctx, num_inference_steps=3)
    cache = unet._ddim_loops
    assert len(cache) == 1
    ddim_sample(unet, lat, ctx, num_inference_steps=3)
    assert len(cache) == 1  # same compiled loop reused


def test_scheduler_steps_validation():
    with pytest.raises(ValueError, match="num_inference_steps"):
        DDIMScheduler(num_train_timesteps=10).timesteps(20)


def test_unet_params_all_registered():
    cfg, unet, lat, ctx = _build()
    names = [n for n, _ in unet.named_parameters()]
    assert any("down_res" in n for n in names)
    assert any("up_attn" in n for n in names)
    assert any("downsamplers" in n for n in names)
