"""Pallas kernel tests — run in interpret mode on the CPU suite, and as
real Mosaic kernels when the backend is TPU.

Covers the round-1 advisor findings: multi-head lowering legality,
bottom-right causal alignment (seq_q != seq_k), GQA, ragged lengths, and
that the functional dispatch actually selects the Pallas path.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.rms_norm import rms_norm
from paddle_tpu.ops.pallas.decode_attention import decode_attention

ATOL = 2e-5 if jax.default_backend() != "tpu" else 3e-2
GTOL = 2e-4 if jax.default_backend() != "tpu" else 3e-2


def ref_attn(q, k, v, causal):
    qf, kf, vf = [a.astype(jnp.float32) for a in (q, k, v)]
    h, hk = q.shape[2], k.shape[2]
    if h != hk:
        kf = jnp.repeat(kf, h // hk, axis=2)
        vf = jnp.repeat(vf, h // hk, axis=2)
    sc = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * sc
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf).astype(q.dtype)


@pytest.mark.parametrize(
    "sq,sk,h,hk,causal",
    [
        (128, 128, 2, 2, False),
        (128, 128, 2, 2, True),
        (100, 100, 2, 2, True),    # ragged → internal padding
        (64, 128, 2, 1, True),     # cross-len causal (bottom-right) + MQA
        (96, 200, 4, 2, False),    # ragged + GQA
        (256, 256, 4, 4, True),    # multi-block
    ],
)
def test_flash_attention_fwd_bwd(sq, sk, h, hk, causal):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, sq, h, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, sk, hk, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, sk, hk, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    ref = ref_attn(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)

    t = jnp.asarray(rng.randn(2, sq, h, 64), jnp.float32) * 0.1
    ga = jax.grad(lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=causal) * t),
                  (0, 1, 2))(q, k, v)
    gb = jax.grad(lambda q, k, v: jnp.sum(ref_attn(q, k, v, causal) * t),
                  (0, 1, 2))(q, k, v)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=GTOL)


def test_flash_attention_bottom_right_causal_matches_xla_fallback():
    """ADVICE r1: kernel was top-left aligned while the XLA fallback is
    bottom-right; they must agree when seq_q != seq_k."""
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 8, 2, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 128, 2, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = ref_attn(q, k, v, True)  # tril(k=sk-sq) — bottom-right
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_flash_attention_rejects_bad_heads():
    q = jnp.zeros((1, 16, 3, 64))
    k = jnp.zeros((1, 16, 2, 64))
    with pytest.raises(ValueError):
        flash_attention(q, k, k)


@pytest.mark.parametrize("shape", [(4, 128, 512), (3, 100, 256), (7, 64)])
def test_rms_norm_fwd_bwd(shape):
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    w = jnp.asarray(rng.randn(shape[-1]), jnp.float32)

    def ref(x, w, eps=1e-6):
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)) * w

    np.testing.assert_allclose(
        np.asarray(rms_norm(x, w)), np.asarray(ref(x, w)), atol=ATOL
    )
    t = jnp.asarray(rng.randn(*shape), jnp.float32)
    ga = jax.grad(lambda x, w: jnp.sum(rms_norm(x, w) * t), (0, 1))(x, w)
    gb = jax.grad(lambda x, w: jnp.sum(ref(x, w) * t), (0, 1))(x, w)
    for a, b in zip(ga, gb):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=GTOL)


def test_rms_norm_bf16():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 256), jnp.bfloat16)
    w = jnp.asarray(rng.randn(256), jnp.bfloat16)
    out = rms_norm(x, w)
    assert out.dtype == jnp.bfloat16
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    ref = (x.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6))
    ref = (ref.astype(jnp.bfloat16) * w).astype(jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=0.1
    )


@pytest.mark.parametrize("b,h,hk,smax", [(2, 4, 4, 256), (2, 8, 2, 300)])
def test_decode_attention(b, h, hk, smax):
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(b, h, 64), jnp.float32)
    kc = jnp.asarray(rng.randn(b, smax, hk, 64), jnp.float32)
    vc = jnp.asarray(rng.randn(b, smax, hk, 64), jnp.float32)
    lens = jnp.asarray(rng.randint(1, smax, size=(b,)), jnp.int32)
    out = decode_attention(q, kc, vc, lens)

    sc = 1 / math.sqrt(64)
    kr = jnp.repeat(kc, h // hk, axis=2)
    vr = jnp.repeat(vc, h // hk, axis=2)
    logits = jnp.einsum("bhd,bshd->bhs", q, kr) * sc
    mask = jnp.arange(smax)[None, None, :] < lens[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    ref = jnp.einsum("bhs,bshd->bhd", p, vr)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=ATOL)


def test_decode_attention_4d_query():
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 1, 4, 64), jnp.float32)
    kc = jnp.asarray(rng.randn(2, 128, 4, 64), jnp.float32)
    vc = jnp.asarray(rng.randn(2, 128, 4, 64), jnp.float32)
    lens = jnp.asarray([7, 128], jnp.int32)
    out = decode_attention(q, kc, vc, lens)
    assert out.shape == (2, 1, 4, 64)


def test_dispatch_selects_pallas_path(monkeypatch):
    """The functional surface must actually route to the kernel when the
    gate is open (round-1: silent fallback hid a broken kernel)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional.attention as attn_mod

    calls = {}
    real = attn_mod._pallas_flash

    def spy(q, k, v, causal=False):
        calls["hit"] = True
        return real(q, k, v, causal=causal)

    monkeypatch.setattr(attn_mod, "_pallas_flash", spy)
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        q = paddle.to_tensor(np.random.randn(1, 128, 2, 64).astype("float32"))
        out = attn_mod.scaled_dot_product_attention(q, q, q, is_causal=True)
        assert calls.get("hit"), "Pallas path was not selected"
        assert out.shape == [1, 128, 2, 64]
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})


def test_rms_norm_dispatch_selects_pallas(monkeypatch):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    import paddle_tpu.nn.functional.norm as norm_mod

    calls = {}
    real = norm_mod._pallas_rms_norm

    def spy(v, w, eps, **kw):
        calls["hit"] = True
        return real(v, w, eps, **kw)

    monkeypatch.setattr(norm_mod, "_pallas_rms_norm", spy)
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        x = paddle.to_tensor(np.random.randn(4, 256).astype("float32"))
        w = paddle.to_tensor(np.ones(256, "float32"))
        out = F.rms_norm(x, w)
        assert calls.get("hit"), "Pallas rms_norm path was not selected"
        ref = np.asarray(x.numpy()) / np.sqrt(
            np.mean(np.square(x.numpy()), -1, keepdims=True) + 1e-6
        )
        np.testing.assert_allclose(out.numpy(), ref, atol=1e-4)
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})


def test_rms_norm_begin_norm_axis():
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    x = paddle.to_tensor(np.random.randn(2, 3, 4).astype("float32"))
    w = paddle.to_tensor(np.random.randn(3, 4).astype("float32"))
    out = F.rms_norm(x, w, begin_norm_axis=1)
    xn = x.numpy()
    var = np.mean(np.square(xn.reshape(2, -1)), -1, keepdims=True)
    ref = (xn.reshape(2, -1) / np.sqrt(var + 1e-6)).reshape(2, 3, 4) * w.numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# varlen (packed) flash attention
# ---------------------------------------------------------------------------
def _cu(lens):
    return jnp.asarray(np.concatenate([[0], np.cumsum(lens)]).astype(np.int32))


def _varlen_ref(q, k, v, cu_q, cu_k, causal):
    from paddle_tpu.nn.functional.attention import _xla_varlen_attention

    return _xla_varlen_attention(q, k, v, cu_q, cu_k,
                                 q.shape[-1] ** -0.5, causal)


@pytest.mark.parametrize("causal", [False, True])
def test_varlen_flash_matches_masked_reference(causal):
    from paddle_tpu.ops.pallas.varlen_flash_attention import (
        varlen_flash_attention,
    )

    rng = np.random.RandomState(0)
    lens = [13, 37, 1, 77]   # ragged, incl. a length-1 sequence
    cu = _cu(lens)
    T, H, HK, D = int(cu[-1]), 4, 2, 64  # GQA group 2
    q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(T, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(T, HK, D), jnp.float32)
    out = varlen_flash_attention(q, k, v, cu, cu, causal=causal,
                                 sm_scale=D ** -0.5)
    ref = _varlen_ref(q, k, v, cu, cu, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_varlen_flash_cross_lengths(causal):
    """Unequal per-sequence q/kv lengths: bottom-right causal alignment."""
    from paddle_tpu.ops.pallas.varlen_flash_attention import (
        varlen_flash_attention,
    )

    rng = np.random.RandomState(1)
    cu_q, cu_k = _cu([9, 25, 40]), _cu([17, 25, 61])
    D = 64
    q = jnp.asarray(rng.randn(int(cu_q[-1]), 4, D), jnp.float32)
    k = jnp.asarray(rng.randn(int(cu_k[-1]), 4, D), jnp.float32)
    v = jnp.asarray(rng.randn(int(cu_k[-1]), 4, D), jnp.float32)
    out = varlen_flash_attention(q, k, v, cu_q, cu_k, causal=causal,
                                 sm_scale=D ** -0.5)
    ref = _varlen_ref(q, k, v, cu_q, cu_k, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_varlen_flash_grads_match_reference():
    from paddle_tpu.ops.pallas.varlen_flash_attention import (
        varlen_flash_attention,
    )

    rng = np.random.RandomState(2)
    cu = _cu([13, 37, 1, 77])
    T, H, HK, D = int(cu[-1]), 4, 2, 64
    q = jnp.asarray(rng.randn(T, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(T, HK, D), jnp.float32)
    v = jnp.asarray(rng.randn(T, HK, D), jnp.float32)

    def loss_pl(q, k, v):
        return (varlen_flash_attention(
            q, k, v, cu, cu, causal=True, sm_scale=D ** -0.5) ** 2).sum()

    def loss_ref(q, k, v):
        return (_varlen_ref(q, k, v, cu, cu, True) ** 2).sum()

    g_pl = jax.grad(loss_pl, (0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g_pl, g_ref):
        scale = max(1e-6, float(jnp.max(jnp.abs(b))))
        np.testing.assert_allclose(np.asarray(a) / scale,
                                   np.asarray(b) / scale,
                                   rtol=2e-4, atol=2e-4)


def test_varlen_tile_maps_skip_cross_segment_blocks():
    """The block-skip predicates (pure function): dead tiles off, interior
    tiles mask-free, boundary tiles masked."""
    from paddle_tpu.ops.pallas.varlen_flash_attention import (
        _aux_arrays, _tile_maps, _Q_PAD_SEG, _K_PAD_SEG, _REL_LO, _REL_HI,
    )

    bq = bk = 128
    cu = _cu([256, 256])  # two 256-token sequences: 4 blocks of 128
    seg_q, rel_q = _aux_arrays(cu, 512, _Q_PAD_SEG, _REL_LO, cu_other=cu)
    seg_k, rel_k = _aux_arrays(cu, 512, _K_PAD_SEG, _REL_HI)
    run, full = (np.asarray(m) for m in _tile_maps(
        seg_q, rel_q, seg_k, rel_k, bq, bk, causal=True))
    # blocks 0-1 = seq 0, blocks 2-3 = seq 1: cross-segment tiles dead
    expect_run = np.array([
        [1, 0, 0, 0],
        [1, 1, 0, 0],
        [0, 0, 1, 0],
        [0, 0, 1, 1],
    ], np.int32)
    np.testing.assert_array_equal(run, expect_run)
    # strictly-below-diagonal same-segment tiles are mask-free
    assert full[1, 0] == 1 and full[3, 2] == 1
    # diagonal tiles need the causal mask
    assert full[0, 0] == 0 and full[1, 1] == 0


def test_flash_attn_unpadded_dispatches_to_pallas(monkeypatch):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional.attention as attn_mod

    calls = {}
    real = attn_mod._pallas_varlen_flash

    def spy(q, k, v, cq, ck, causal=False, sm_scale=None,
            window_size=None):
        calls["hit"] = True
        return real(q, k, v, cq, ck, causal=causal, sm_scale=sm_scale,
                    window_size=window_size)

    monkeypatch.setattr(attn_mod, "_pallas_varlen_flash", spy)
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        rng = np.random.RandomState(3)
        cu = np.array([0, 40, 100], np.int32)
        q = paddle.to_tensor(rng.randn(100, 4, 64).astype("float32"))
        out, _ = attn_mod.flash_attn_unpadded(
            q, q, q, paddle.to_tensor(cu), paddle.to_tensor(cu),
            64, 64, scale=64 ** -0.5, causal=True)
        assert calls.get("hit"), "Pallas varlen path was not selected"
        assert out.shape == [100, 4, 64]
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})


def test_flash_sliding_window_matches_masked_reference():
    """Round-5: causal sliding-window flash (Mistral band semantics) —
    fwd AND grads must match a banded-mask XLA oracle; grid tiles
    entirely outside the band are skipped (cost O(S*window))."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    b, s, h, d, w = 2, 100, 4, 64, 17
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

    def ref(q, k, v):
        qt, kt, vt = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(d)
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(s)[None, :]
        band = (kpos <= qpos) & (kpos >= qpos - w + 1)
        logits = jnp.where(band[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, -1)
        return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)

    out = flash_attention(q, k, v, causal=True, window_size=w,
                          block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(q, k, v)),
                               rtol=2e-5, atol=2e-5)

    def loss_f(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, window_size=w,
                                       block_q=32, block_k=32) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(ref(q, k, v) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-4)

    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window_size=w)


def test_llama_sliding_window_config():
    """LlamaConfig(sliding_window=W): the model's dense path must equal
    manually-banded attention, and KV-cache decode with a window must
    refuse (rolling cache buffer not implemented)."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, sliding_window=8)
    m = LlamaForCausalLM(cfg)
    m.eval()
    paddle.seed(0)
    cfg_full = LlamaConfig.tiny(tensor_parallel=False)
    m_full = LlamaForCausalLM(cfg_full)
    m_full.eval()
    ids_np = np.random.RandomState(0).randint(0, 128, (2, 32))
    out_w = m(paddle.to_tensor(ids_np)).numpy()
    out_f = m_full(paddle.to_tensor(ids_np)).numpy()
    # same weights (same seed); early positions (inside the window)
    # agree, late positions must differ — the window genuinely cuts
    np.testing.assert_allclose(out_w[:, :8], out_f[:, :8], rtol=1e-4,
                               atol=1e-5)
    assert np.abs(out_w[:, -1] - out_f[:, -1]).max() > 1e-4

    # cache decode now rides a rolling buffer (round-5); the raising
    # combo is CHUNKED prefill (cache, offset>0, s>1)
    caches = m.init_caches(2, 16)
    with pytest.raises(NotImplementedError, match="chunked"):
        m(paddle.to_tensor(ids_np[:, :4]), caches=caches,
          position_offset=4)


def test_varlen_sliding_window_matches_reference():
    """Round-5: the varlen kernel's per-segment sliding-window band.
    Oracle: banded masked XLA attention; fwd AND grads, ragged segments
    longer and shorter than the window."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.varlen_flash_attention import (
        varlen_flash_attention,
    )
    from paddle_tpu.nn.functional.attention import _xla_varlen_attention

    rng = np.random.RandomState(6)
    lens = [50, 7, 90, 30]
    T = sum(lens)
    cu = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]).astype(np.int32))
    h, hk, d, w = 4, 2, 64, 16
    q = jnp.asarray(rng.randn(T, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(T, hk, d), jnp.float32)
    v = jnp.asarray(rng.randn(T, hk, d), jnp.float32)
    sc = d ** -0.5

    out = varlen_flash_attention(q, k, v, cu, cu, causal=True,
                                 window_size=w, block_q=128, block_k=128)
    ref = _xla_varlen_attention(q, k, v, cu, cu, sc, True, window=w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # the band must genuinely cut (segment 2 is longer than the window)
    full = varlen_flash_attention(q, k, v, cu, cu, causal=True,
                                  block_q=128, block_k=128)
    assert np.abs(np.asarray(out) - np.asarray(full)).max() > 1e-3

    def loss_f(q, k, v):
        return jnp.sum(varlen_flash_attention(
            q, k, v, cu, cu, causal=True, window_size=w,
            block_q=128, block_k=128) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(_xla_varlen_attention(
            q, k, v, cu, cu, sc, True, window=w) ** 2)

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

    with pytest.raises(ValueError, match="causal"):
        varlen_flash_attention(q, k, v, cu, cu, causal=False,
                               window_size=w)


def test_llama_packed_sliding_window_matches_per_sequence():
    """Packed + sliding_window: each packed segment's logits must equal
    that sequence forwarded ALONE through the same windowed model."""
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM

    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False,
                                          sliding_window=6))
    m.eval()
    lens = [9, 4, 14]
    rng = np.random.RandomState(7)
    segs = [rng.randint(0, 128, (ln,)) for ln in lens]
    packed = np.concatenate(segs)[None, :]
    cu = paddle.to_tensor(
        np.concatenate([[0], np.cumsum(lens)]).astype(np.int32))
    out = m(paddle.to_tensor(packed), cu_seqlens=cu).numpy()[0]
    ofs = 0
    fwd = jax.jit(m)    # the oracle: one program a length (ROADMAP D7)
    for seg in segs:
        alone = fwd(paddle.to_tensor(seg[None, :])).numpy()[0]
        np.testing.assert_allclose(out[ofs:ofs + len(seg)], alone,
                                   rtol=2e-4, atol=2e-4)
        ofs += len(seg)


# ------------------------------------------------ kernels under a mesh
# The TPU lowering refuses a Mosaic kernel inside a GSPMD program
# ("cannot be automatically partitioned"), so under an installed mesh
# every kernel entry point runs per shard inside jax.shard_map
# (ops/pallas/_utils.per_shard). Interpret mode lowers to plain HLO and
# would hide a wrong split, so parity with the mesh-less call is pinned
# here: values and gradients.
@pytest.fixture
def hybrid_mesh():
    """dp2 x sharding2 x mp2 over the 8 virtual devices."""
    from jax.sharding import Mesh
    from paddle_tpu.parallel import mesh as mesh_state

    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 1, 2),
                ("dp", "sharding", "sep", "mp"))
    mesh_state.set_mesh(mesh)
    yield mesh
    mesh_state.set_mesh(None)


def _under_mesh_matches(fn, args, argnums=None):
    """fn's output (and, given ``argnums``, its gradients), jitted
    under the installed mesh, against the same call with no mesh."""
    from paddle_tpu.parallel import mesh as mesh_state

    def loss(*a):
        return jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))

    run = jax.jit(fn if argnums is None
                  else jax.value_and_grad(loss, argnums))
    mesh = mesh_state.get_mesh()
    got = run(*args)
    assert "manual" in run.lower(*args).as_text(), \
        "kernel was not wrapped in a shard_map"
    mesh_state.set_mesh(None)
    jax.clear_caches()  # the mesh is read at trace time, not a jit key
    want = run(*args)
    assert "manual" not in run.lower(*args).as_text()
    mesh_state.set_mesh(mesh)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=GTOL)


@pytest.mark.parametrize("h,hk,b", [(4, 2, 4), (3, 1, 3)],
                         ids=["split-batch-and-heads", "indivisible"])
def test_flash_attention_per_shard_under_mesh(hybrid_mesh, h, hk, b):
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, 64, h, 64), jnp.float32)
    k = jnp.asarray(rng.randn(b, 64, hk, 64), jnp.float32)
    v = jnp.asarray(rng.randn(b, 64, hk, 64), jnp.float32)
    _under_mesh_matches(
        lambda q, k, v: flash_attention(q, k, v, causal=True),
        (q, k, v), (0, 1, 2))


def test_rms_norm_per_shard_under_mesh(hybrid_mesh):
    """dw sums over rows that live on different shards."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(4, 24, 128), jnp.float32)
    w = jnp.asarray(rng.randn(128), jnp.float32)
    _under_mesh_matches(lambda x, w: rms_norm(x, w, 1e-6), (x, w), (0, 1))


def test_decode_kernels_per_shard_under_mesh(hybrid_mesh):
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_decode_attention,
    )

    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(4, 4, 64), jnp.float32)
    kc = jnp.asarray(rng.randn(4, 48, 2, 64), jnp.float32)
    lens = jnp.asarray([7, 48, 1, 20], jnp.int32)
    _under_mesh_matches(lambda q, kc: decode_attention(q, kc, kc, lens),
                        (q, kc))
    pool = jnp.asarray(rng.randn(13, 16, 2, 64), jnp.float32)
    tables = jnp.asarray(rng.permutation(12).reshape(4, 3) + 1, jnp.int32)
    _under_mesh_matches(
        lambda q, p: paged_decode_attention(q, p, p, tables, lens),
        (q, pool))
