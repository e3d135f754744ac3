"""Paged/blocked KV-cache decode (reference: the 2.6-era serving op
block_multihead_attention + block pool — unverified, SURVEY.md §0/§2.5):
parity vs the contiguous-cache decode kernel, pool allocator semantics,
and the memory-scales-with-live-tokens claim."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.pallas.decode_attention import decode_attention
from paddle_tpu.ops.pallas.paged_attention import (
    paged_decode_attention, paged_cache_write,
)
from paddle_tpu.nlp import PagedKVCachePool


def _ragged_setup(rng, lens, h=8, hk=4, d=64, bs=32):
    """Build a contiguous cache and an equivalent paged pool."""
    b = len(lens)
    s_max = max(lens)
    kc = rng.randn(b, s_max, hk, d).astype("f4")
    vc = rng.randn(b, s_max, hk, d).astype("f4")
    for i, ln in enumerate(lens):  # zero the invalid tail for clarity
        kc[i, ln:] = 0
        vc[i, ln:] = 0
    pool = PagedKVCachePool(num_blocks=64, block_size=bs, num_kv_heads=hk,
                            head_dim=d, dtype=jnp.float32)
    kp = np.zeros((64, bs, hk, d), "f4")
    vp = np.zeros((64, bs, hk, d), "f4")
    for i, ln in enumerate(lens):
        table = pool.ensure(i, ln)
        for pos in range(ln):
            kp[table[pos // bs], pos % bs] = kc[i, pos]
            vp[table[pos // bs], pos % bs] = vc[i, pos]
    tables = pool.block_table_array(range(b))
    seq_lens = pool.seq_lens_array(range(b))
    return kc, vc, jnp.asarray(kp), jnp.asarray(vp), tables, seq_lens


def test_paged_matches_contiguous_decode():
    rng = np.random.RandomState(0)
    lens = [7, 32, 57, 128]
    h, hk, d = 8, 4, 64
    kc, vc, kp, vp, tables, seq_lens = _ragged_setup(rng, lens, h, hk, d)
    q = jnp.asarray(rng.randn(len(lens), h, d), jnp.float32)
    ref = decode_attention(q, jnp.asarray(kc), jnp.asarray(vc),
                           jnp.asarray(lens, jnp.int32))
    out = paged_decode_attention(q, kp, vp, tables, seq_lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pool", ["float", "int8"])
@pytest.mark.parametrize("block_size", [16, 32])
@pytest.mark.parametrize("group,hk", [(7, 4), (4, 8), (1, 8)],
                         ids=["qwen2-g7-hk4", "mistral-g4-hk8", "mha-g1"])
def test_paged_kernel_matches_xla_gather(group, hk, block_size, pool):
    """The kernel (interpret mode) against `_xla_paged_decode_attn` over
    the shapes that decide it: the GQA groupings served, both block
    sizes, lengths at every edge of a block, a chunk and the table, and
    table tails holding block ids far outside the pool (never read)."""
    from paddle_tpu.nlp.paged_attention import _xla_paged_decode_attn
    from paddle_tpu.ops.pallas import paged_attention as kernel_mod

    rng = np.random.RandomState(hk * block_size + group)
    d, w = 128, 12
    h = group * hk
    chunk = min(w, kernel_mod._CHUNK_ROWS // (block_size * hk))
    lens = np.asarray(
        [1, block_size - 1, block_size, block_size + 1,
         chunk * block_size, min(w, chunk + 1) * block_size - 3,
         w * block_size], np.int32)
    b = len(lens)
    nb = b * w + 1
    tables = (rng.permutation(nb - 1)[: b * w].reshape(b, w) + 1).astype(
        np.int32)
    for i, ln in enumerate(lens):
        tables[i, -(-ln // block_size):] = 10 ** 6
    q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
    scales = {}
    if pool == "int8":
        kp = jnp.asarray(rng.randint(-127, 128, (nb, block_size, hk, d)),
                         jnp.int8)
        vp = jnp.asarray(rng.randint(-127, 128, (nb, block_size, hk, d)),
                         jnp.int8)
        ksc = jnp.asarray(rng.uniform(0.002, 0.02, hk), jnp.float32)
        vsc = jnp.asarray(rng.uniform(0.002, 0.02, hk), jnp.float32)
        scales = {"k_scale": ksc, "v_scale": vsc}
        k_ref = kp.astype(jnp.float32) * ksc[:, None]
        v_ref = vp.astype(jnp.float32) * vsc[:, None]
    else:
        kp = k_ref = jnp.asarray(rng.randn(nb, block_size, hk, d),
                                 jnp.float32)
        vp = v_ref = jnp.asarray(rng.randn(nb, block_size, hk, d),
                                 jnp.float32)
    out = paged_decode_attention(q, kp, vp, jnp.asarray(tables),
                                 jnp.asarray(lens), **scales)
    ref = _xla_paged_decode_attn(
        q, k_ref, v_ref, jnp.asarray(np.where(tables >= nb, 0, tables)),
        jnp.asarray(lens))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_gather_decode_matches_dense_reference(kind):
    """`_xla_paged_decode_attn` (the decode attention wherever the Pallas
    kernel is not) against a plain softmax in numpy over the same rows
    laid out contiguously: ragged lengths (one token, mid-block, a block's
    edge, the whole table), grouped heads, and dead table entries that
    point at a block of huge values which a missing mask would let in.
    For an int8 pool with per-row scale pools the reference runs over the
    dequantised rows."""
    from paddle_tpu.nlp.paged_attention import _xla_paged_decode_attn

    rng = np.random.RandomState(9)
    s, w, bs, h, hk, d, nb = 4, 5, 4, 4, 2, 16, 24
    lens = np.asarray([7, 20, 1, 12], np.int32)
    tables = (rng.permutation(nb - 1)[: s * w].reshape(s, w) + 1).astype(
        np.int32)
    for i, ln in enumerate(lens):
        tables[i, -(-ln // bs):] = 0          # dead entries: block 0
    q = rng.randn(s, h, d).astype(np.float32)
    scales = {}
    if kind == "int8":
        kp = rng.randint(-127, 128, (nb, bs, hk, d)).astype(np.int8)
        vp = rng.randint(-127, 128, (nb, bs, hk, d)).astype(np.int8)
        kp[0], vp[0] = 127, 127
        ksc = rng.uniform(0.001, 0.021, (nb, bs, hk)).astype(np.float32)
        vsc = rng.uniform(0.001, 0.021, (nb, bs, hk)).astype(np.float32)
        scales = {"ks": jnp.asarray(ksc), "vs": jnp.asarray(vsc)}
        k_rows = kp.astype(np.float64) * ksc[..., None]
        v_rows = vp.astype(np.float64) * vsc[..., None]
    else:
        kp = rng.randn(nb, bs, hk, d).astype(np.float32)
        vp = rng.randn(nb, bs, hk, d).astype(np.float32)
        kp[0], vp[0] = 1e4, 1e4
        if kind == "bf16":
            q, kp, vp = (np.asarray(jnp.asarray(a, jnp.bfloat16)
                                    .astype(jnp.float32))
                         for a in (q, kp, vp))
        k_rows, v_rows = kp.astype(np.float64), vp.astype(np.float64)
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    pool_dtype = jnp.int8 if kind == "int8" else dtype
    out = _xla_paged_decode_attn(
        jnp.asarray(q, dtype), jnp.asarray(kp, pool_dtype),
        jnp.asarray(vp, pool_dtype), jnp.asarray(tables),
        jnp.asarray(lens), **scales)
    assert out.dtype == dtype and out.shape == (s, h, d)

    want = np.zeros((s, h, d))
    for i, ln in enumerate(lens):
        # the row's live tokens, contiguous: (ln, HK, D)
        k = k_rows[tables[i]].reshape(w * bs, hk, d)[:ln]
        v = v_rows[tables[i]].reshape(w * bs, hk, d)[:ln]
        for head in range(h):
            kv = head // (h // hk)
            sc = k[:, kv] @ q[i, head].astype(np.float64) / np.sqrt(d)
            p = np.exp(sc - sc.max())
            want[i, head] = (p / p.sum()) @ v[:, kv]
    tol = 2e-2 if kind == "bf16" else 2e-5      # bf16: the output's cast
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)), want,
                               rtol=tol, atol=tol)


def test_paged_cache_write_then_attend():
    rng = np.random.RandomState(1)
    lens = [15, 40]
    h, hk, d, bs = 4, 2, 64, 32
    kc, vc, kp, vp, tables, seq_lens = _ragged_setup(
        rng, lens, h, hk, d, bs)
    pool = PagedKVCachePool(num_blocks=8, block_size=bs, num_kv_heads=hk,
                            head_dim=d, dtype=jnp.float32)
    # decode one more token per sequence
    k_new = jnp.asarray(rng.randn(2, hk, d), jnp.float32)
    v_new = jnp.asarray(rng.randn(2, hk, d), jnp.float32)
    positions = jnp.asarray(lens, jnp.int32)
    kp2, vp2 = paged_cache_write(kp, vp, k_new, v_new, tables, positions)
    q = jnp.asarray(rng.randn(2, h, d), jnp.float32)
    out = paged_decode_attention(q, kp2, vp2, tables,
                                 positions + 1)
    # contiguous reference with the token appended
    kc2 = np.zeros((2, max(lens) + 1, hk, d), "f4")
    vc2 = np.zeros_like(kc2)
    kc2[:, : max(lens)] = kc
    vc2[:, : max(lens)] = vc
    for i, ln in enumerate(lens):
        kc2[i, ln] = np.asarray(k_new[i])
        vc2[i, ln] = np.asarray(v_new[i])
    ref = decode_attention(q, jnp.asarray(kc2), jnp.asarray(vc2),
                           jnp.asarray([l + 1 for l in lens], jnp.int32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pool_allocator_reuse_and_memory_claim():
    pool = PagedKVCachePool(num_blocks=16, block_size=32, num_kv_heads=2,
                            head_dim=64, num_layers=2)
    pool.ensure("a", 100)   # 4 blocks
    pool.ensure("b", 10)    # 1 block
    assert pool.blocks_in_use == 5
    per_block = 32 * 2 * 64 * 2  # tokens*heads*dim*bf16
    assert pool.bytes_in_use() == 2 * 2 * 5 * per_block
    pool.free("a")
    assert pool.blocks_in_use == 1
    pool.ensure("c", 128)   # reuses a's blocks
    assert pool.blocks_in_use == 5
    # exhaustion raises
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.ensure("d", 16 * 32)


def test_grow_decode_table_row_is_written_on_the_host(monkeypatch):
    """The quantum's table row: grown to cover the dispatch, padded, equal
    to the pool's own table, and a host array that was never on the
    device (one call a slot and quantum: a device round trip each was
    most of the decode step's host time)."""
    pool = PagedKVCachePool(num_blocks=16, block_size=32, num_kv_heads=2,
                            head_dim=64, num_layers=1)
    pool.ensure("a", 40)    # 2 blocks
    monkeypatch.setattr(pool, "block_table_array", None)
    monkeypatch.setattr(jnp, "asarray", None)
    row = pool.grow_decode_table("a", 70, 40, pad_to=6)
    assert isinstance(row, np.ndarray) and row.dtype == np.int32
    assert row.tolist() == pool._tables["a"] + [0, 0, 0]
    assert len(pool._tables["a"]) == 3 and pool.seq_len("a") == 70
    # no growth needed, no padding asked for: the table as it stands
    assert pool.grow_decode_table("a", 64, 40).tolist() == pool._tables["a"]


def test_block_multihead_attention_prefill_then_decode():
    """The incubate functional: prefill writes the pool + varlen flash;
    decode steps match a full-context reference."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention
    from paddle_tpu.nn.functional.attention import _xla_varlen_attention

    rng = np.random.RandomState(2)
    h, hk, d, bs = 4, 2, 64, 32
    lens = [9, 21]
    b = len(lens)
    total = sum(lens)
    pool = PagedKVCachePool(num_blocks=16, block_size=bs, num_kv_heads=hk,
                            head_dim=d, dtype=jnp.float32)
    for i, ln in enumerate(lens):
        pool.ensure(i, ln)
    kcache = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))
    vcache = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))

    qkv_np = rng.randn(total, (h + 2 * hk) * d).astype("f4")
    cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    out = block_multihead_attention(
        paddle.to_tensor(qkv_np), kcache, vcache,
        seq_lens_encoder=paddle.to_tensor(np.asarray(lens, "i4")),
        seq_lens_decoder=paddle.to_tensor(np.zeros(b, "i4")),
        seq_lens_this_time=paddle.to_tensor(np.asarray(lens, "i4")),
        cu_seqlens_q=paddle.to_tensor(cu), cu_seqlens_k=paddle.to_tensor(cu),
        block_tables=paddle.to_tensor(
            np.asarray(pool.block_table_array(range(b)))),
        num_heads=h, kv_num_heads=hk,
    )
    # reference prefill: causal varlen attention over the same packed qkv
    q = qkv_np[:, : h * d].reshape(total, h, d)
    k = qkv_np[:, h * d : (h + hk) * d].reshape(total, hk, d)
    v = qkv_np[:, (h + hk) * d :].reshape(total, hk, d)
    ref = _xla_varlen_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(cu), jnp.asarray(cu), d ** -0.5, True)
    np.testing.assert_allclose(
        np.asarray(out._value).reshape(total, h, d), np.asarray(ref),
        rtol=2e-5, atol=2e-5)

    # decode one token per sequence; reference = full-context attention
    for i in range(b):
        pool.ensure(i, lens[i] + 1)
    qkv_dec = rng.randn(b, (h + 2 * hk) * d).astype("f4")
    out_dec = block_multihead_attention(
        paddle.to_tensor(qkv_dec), kcache, vcache,
        seq_lens_encoder=paddle.to_tensor(np.zeros(b, "i4")),
        seq_lens_decoder=paddle.to_tensor(np.asarray(lens, "i4")),
        seq_lens_this_time=paddle.to_tensor(np.ones(b, "i4")),
        block_tables=paddle.to_tensor(
            np.asarray(pool.block_table_array(range(b)))),
        num_heads=h, kv_num_heads=hk,
    )
    qd = qkv_dec[:, : h * d].reshape(b, h, d)
    kd = qkv_dec[:, h * d : (h + hk) * d].reshape(b, hk, d)
    vd = qkv_dec[:, (h + hk) * d :].reshape(b, hk, d)
    kc_full = np.zeros((b, max(lens) + 1, hk, d), "f4")
    vc_full = np.zeros_like(kc_full)
    for i, ln in enumerate(lens):
        kc_full[i, :ln] = k[cu[i]:cu[i + 1]]
        vc_full[i, :ln] = v[cu[i]:cu[i + 1]]
        kc_full[i, ln] = kd[i]
        vc_full[i, ln] = vd[i]
    ref_dec = decode_attention(
        jnp.asarray(qd), jnp.asarray(kc_full), jnp.asarray(vc_full),
        jnp.asarray([l + 1 for l in lens], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(out_dec._value).reshape(b, h, d), np.asarray(ref_dec),
        rtol=2e-5, atol=2e-5)


def test_block_mha_mixed_prefill_decode_batch():
    """Round-3 review finding: mixed batches must route per row — the
    decode row attends over its cached context, the prefill row over its
    own new tokens."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention
    from paddle_tpu.nn.functional.attention import _xla_varlen_attention

    rng = np.random.RandomState(3)
    h, hk, d, bs = 4, 2, 64, 32
    pool = PagedKVCachePool(num_blocks=16, block_size=bs, num_kv_heads=hk,
                            head_dim=d, dtype=jnp.float32)
    # row 1 already holds 16 cached tokens
    cached = rng.randn(16, hk, d).astype("f4") * 0.5
    cached_v = rng.randn(16, hk, d).astype("f4") * 0.5
    pool.ensure(1, 16)
    kcache_np = np.zeros((16, bs, hk, d), "f4")
    vcache_np = np.zeros_like(kcache_np)
    t1 = pool._tables[1]
    for pos in range(16):
        kcache_np[t1[pos // bs], pos % bs] = cached[pos]
        vcache_np[t1[pos // bs], pos % bs] = cached_v[pos]
    pool.ensure(0, 8)    # row 0: fresh prefill of 8 tokens
    pool.ensure(1, 17)   # row 1: decode 1 token
    kcache = paddle.to_tensor(kcache_np)
    vcache = paddle.to_tensor(vcache_np)

    qkv_np = rng.randn(9, (h + 2 * hk) * d).astype("f4")  # 8 + 1 tokens
    out = block_multihead_attention(
        paddle.to_tensor(qkv_np), kcache, vcache,
        seq_lens_encoder=paddle.to_tensor(np.asarray([8, 0], "i4")),
        seq_lens_decoder=paddle.to_tensor(np.asarray([0, 16], "i4")),
        seq_lens_this_time=paddle.to_tensor(np.asarray([8, 1], "i4")),
        block_tables=paddle.to_tensor(
            np.asarray(pool.block_table_array(range(2)))),
        num_heads=h, kv_num_heads=hk,
    ).numpy().reshape(9, h, d)

    q = qkv_np[:, : h * d].reshape(9, h, d)
    k = qkv_np[:, h * d : (h + hk) * d].reshape(9, hk, d)
    v = qkv_np[:, (h + hk) * d :].reshape(9, hk, d)
    # row 0 reference: causal self-attention over its 8 tokens
    ref0 = _xla_varlen_attention(
        jnp.asarray(q[:8]), jnp.asarray(k[:8]), jnp.asarray(v[:8]),
        jnp.asarray([0, 8], jnp.int32), jnp.asarray([0, 8], jnp.int32),
        d ** -0.5, True)
    np.testing.assert_allclose(out[:8], np.asarray(ref0), rtol=2e-5, atol=2e-5)
    # row 1 reference: decode over cached 16 + the new token
    kc_full = np.concatenate([cached, k[8:9]], 0)[None]
    vc_full = np.concatenate([cached_v, v[8:9]], 0)[None]
    ref1 = decode_attention(jnp.asarray(q[8:9]), jnp.asarray(kc_full),
                            jnp.asarray(vc_full),
                            jnp.asarray([17], jnp.int32))
    np.testing.assert_allclose(out[8:9], np.asarray(ref1), rtol=2e-5,
                               atol=2e-5)


def test_block_mha_chunked_prefill_attends_cache():
    """A prefill row with dec_lens>0 (chunked prefill) must attend over
    the cached context too, bottom-right aligned."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention
    from paddle_tpu.nn.functional.attention import _xla_varlen_attention

    rng = np.random.RandomState(4)
    h, hk, d, bs = 4, 2, 64, 32
    pool = PagedKVCachePool(num_blocks=8, block_size=bs, num_kv_heads=hk,
                            head_dim=d, dtype=jnp.float32)
    cached_k = rng.randn(10, hk, d).astype("f4") * 0.5
    cached_v = rng.randn(10, hk, d).astype("f4") * 0.5
    pool.ensure(0, 10)
    kcache_np = np.zeros((8, bs, hk, d), "f4")
    vcache_np = np.zeros_like(kcache_np)
    t0 = pool._tables[0]
    for pos in range(10):
        kcache_np[t0[pos // bs], pos % bs] = cached_k[pos]
        vcache_np[t0[pos // bs], pos % bs] = cached_v[pos]
    pool.ensure(0, 16)  # 6 more tokens arriving now
    kcache, vcache = paddle.to_tensor(kcache_np), paddle.to_tensor(vcache_np)

    qkv_np = rng.randn(6, (h + 2 * hk) * d).astype("f4")
    out = block_multihead_attention(
        paddle.to_tensor(qkv_np), kcache, vcache,
        seq_lens_encoder=paddle.to_tensor(np.asarray([6], "i4")),
        seq_lens_decoder=paddle.to_tensor(np.asarray([10], "i4")),
        seq_lens_this_time=paddle.to_tensor(np.asarray([6], "i4")),
        block_tables=paddle.to_tensor(
            np.asarray(pool.block_table_array([0]))),
        num_heads=h, kv_num_heads=hk,
    ).numpy().reshape(6, h, d)

    q = qkv_np[:, : h * d].reshape(6, h, d)
    k = qkv_np[:, h * d : (h + hk) * d].reshape(6, hk, d)
    v = qkv_np[:, (h + hk) * d :].reshape(6, hk, d)
    k_full = np.concatenate([cached_k, k], 0)
    v_full = np.concatenate([cached_v, v], 0)
    ref = _xla_varlen_attention(
        jnp.asarray(q), jnp.asarray(k_full), jnp.asarray(v_full),
        jnp.asarray([0, 6], jnp.int32), jnp.asarray([0, 16], jnp.int32),
        d ** -0.5, True)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_block_mha_inactive_rows_skipped():
    """this_time==0 slots (finished sequences) must contribute nothing
    and not corrupt other rows (round-3 review finding)."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention

    rng = np.random.RandomState(5)
    h, hk, d, bs = 4, 2, 64, 32
    pool = PagedKVCachePool(num_blocks=8, block_size=bs, num_kv_heads=hk,
                            head_dim=d, dtype=jnp.float32)
    cached_k = rng.randn(12, hk, d).astype("f4")
    cached_v = rng.randn(12, hk, d).astype("f4")
    kcache_np = np.zeros((8, bs, hk, d), "f4")
    vcache_np = np.zeros_like(kcache_np)
    pool.ensure(1, 12)
    t1 = pool._tables[1]
    for pos in range(12):
        kcache_np[t1[pos // bs], pos % bs] = cached_k[pos]
        vcache_np[t1[pos // bs], pos % bs] = cached_v[pos]
    pool.ensure(1, 13)
    kcache, vcache = paddle.to_tensor(kcache_np), paddle.to_tensor(vcache_np)

    # row0 finished (this_time 0), row1 decoding — one token total
    qkv_np = rng.randn(1, (h + 2 * hk) * d).astype("f4")
    out = block_multihead_attention(
        paddle.to_tensor(qkv_np), kcache, vcache,
        seq_lens_encoder=paddle.to_tensor(np.asarray([0, 0], "i4")),
        seq_lens_decoder=paddle.to_tensor(np.asarray([0, 12], "i4")),
        seq_lens_this_time=paddle.to_tensor(np.asarray([0, 1], "i4")),
        block_tables=paddle.to_tensor(
            np.asarray(pool.block_table_array(range(2)))),
        num_heads=h, kv_num_heads=hk,
    ).numpy().reshape(1, h, d)

    q = qkv_np[:, : h * d].reshape(1, h, d)
    k = qkv_np[:, h * d : (h + hk) * d].reshape(1, hk, d)
    v = qkv_np[:, (h + hk) * d :].reshape(1, hk, d)
    kc_full = np.concatenate([cached_k, k], 0)[None]
    vc_full = np.concatenate([cached_v, v], 0)[None]
    ref = decode_attention(jnp.asarray(q), jnp.asarray(kc_full),
                           jnp.asarray(vc_full),
                           jnp.asarray([13], jnp.int32))
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_block_mha_quant_arg_validation():
    """Round-5: the quant fusion args are accepted, but inconsistent
    combinations must refuse loudly — int8 pools without scales, scales
    with float pools, or only one of the k/v scale pair."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention

    def call(kc_dtype="f4", **kw):
        return block_multihead_attention(
            paddle.to_tensor(np.zeros((1, 8 * 64), "f4")),
            paddle.to_tensor(np.zeros((2, 32, 2, 64), kc_dtype)),
            paddle.to_tensor(np.zeros((2, 32, 2, 64), kc_dtype)),
            seq_lens_encoder=paddle.to_tensor(np.zeros(1, "i4")),
            seq_lens_decoder=paddle.to_tensor(np.zeros(1, "i4")),
            seq_lens_this_time=paddle.to_tensor(np.ones(1, "i4")),
            block_tables=paddle.to_tensor(np.zeros((1, 1), "i4")),
            num_heads=4, kv_num_heads=2, **kw)

    ones2 = paddle.to_tensor(np.ones(2, "f4"))
    with pytest.raises(ValueError, match="BOTH"):
        call(cache_k_quant_scales=ones2)
    with pytest.raises(ValueError, match="int8"):
        call(kc_dtype="i1")  # int8 pools, no scales
    with pytest.raises(ValueError, match="not int8"):
        call(cache_k_quant_scales=ones2, cache_v_quant_scales=ones2)


def _quant_setup(rng, lens, h=4, hk=2, d=64, bs=32):
    """qkv whose k/v lanes sit exactly on the int8 grid for scale 2.0 —
    quantization is lossless, so int8-cache output must EQUAL float."""
    b, total = len(lens), sum(lens)
    qkv = rng.randn(total, (h + 2 * hk) * d).astype("f4")
    # k/v sections: multiples of 0.5 in [-60, 60] → exact at qs=2.0
    kv = rng.randint(-120, 121, (total, 2 * hk * d)).astype("f4") / 2.0
    qkv[:, h * d:] = kv
    cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    return qkv, cu


def test_block_mha_int8_kv_cache_matches_float():
    """Prefill + decode with int8 pools and per-head quant scales must
    match the float-pool path exactly when values sit on the quant grid
    (proves the wiring: quantize-on-write, dequant-in-kernel/gather)."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention

    rng = np.random.RandomState(6)
    h, hk, d, bs = 4, 2, 64, 32
    lens = [9, 21]
    b = len(lens)
    qkv_np, cu = _quant_setup(rng, lens, h, hk, d, bs)
    qs = paddle.to_tensor(np.full(hk, 2.0, "f4"))

    def run(int8):
        pool = PagedKVCachePool(num_blocks=16, block_size=bs,
                                num_kv_heads=hk, head_dim=d,
                                dtype=jnp.int8 if int8 else jnp.float32)
        for i, ln in enumerate(lens):
            pool.ensure(i, ln)
        kc = paddle.to_tensor(np.zeros((16, bs, hk, d),
                                       "i1" if int8 else "f4"))
        vc = paddle.to_tensor(np.zeros((16, bs, hk, d),
                                       "i1" if int8 else "f4"))
        quant = dict(cache_k_quant_scales=qs, cache_v_quant_scales=qs) \
            if int8 else {}
        out = block_multihead_attention(
            paddle.to_tensor(qkv_np), kc, vc,
            seq_lens_encoder=paddle.to_tensor(np.asarray(lens, "i4")),
            seq_lens_decoder=paddle.to_tensor(np.zeros(b, "i4")),
            seq_lens_this_time=paddle.to_tensor(np.asarray(lens, "i4")),
            block_tables=paddle.to_tensor(
                np.asarray(pool.block_table_array(range(b)))),
            num_heads=h, kv_num_heads=hk, **quant)
        # decode one token per sequence from the (int8) cache
        for i in range(b):
            pool.ensure(i, lens[i] + 1)
        qkv_dec, _ = _quant_setup(rng2, [1] * b, h, hk, d, bs)
        out_dec = block_multihead_attention(
            paddle.to_tensor(qkv_dec), kc, vc,
            seq_lens_encoder=paddle.to_tensor(np.zeros(b, "i4")),
            seq_lens_decoder=paddle.to_tensor(np.asarray(lens, "i4")),
            seq_lens_this_time=paddle.to_tensor(np.ones(b, "i4")),
            block_tables=paddle.to_tensor(
                np.asarray(pool.block_table_array(range(b)))),
            num_heads=h, kv_num_heads=hk, **quant)
        return (out.numpy(), out_dec.numpy(),
                np.asarray(kc._value), np.asarray(vc._value))

    rng2 = np.random.RandomState(7)
    o_i8, od_i8, kc_i8, _ = run(True)
    rng2 = np.random.RandomState(7)
    o_f, od_f, kc_f, _ = run(False)
    assert kc_i8.dtype == np.int8  # the pool genuinely holds int8
    np.testing.assert_allclose(o_i8, o_f, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(od_i8, od_f, rtol=2e-5, atol=2e-5)
    # the int8 cache dequantizes to exactly the float cache
    np.testing.assert_allclose(kc_i8.astype("f4") / 2.0, kc_f,
                               rtol=0, atol=0)


def test_block_mha_qkv_out_scale_dequant():
    """qkv_out_scale applied inside == pre-scaling the qkv outside."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention

    rng = np.random.RandomState(8)
    h, hk, d, bs = 4, 2, 64, 32
    lens = [7, 12]
    b, total = len(lens), sum(lens)
    nchan = (h + 2 * hk) * d
    qkv_int = rng.randint(-1000, 1000, (total, nchan)).astype("f4")
    scale = (0.001 * (1 + np.arange(nchan) % 5)).astype("f4")

    def run(fused):
        pool = PagedKVCachePool(num_blocks=16, block_size=bs,
                                num_kv_heads=hk, head_dim=d,
                                dtype=jnp.float32)
        for i, ln in enumerate(lens):
            pool.ensure(i, ln)
        kc = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))
        vc = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))
        qkv_in = qkv_int if fused else qkv_int * scale[None, :]
        kw = dict(qkv_out_scale=paddle.to_tensor(scale)) if fused else {}
        out = block_multihead_attention(
            paddle.to_tensor(qkv_in), kc, vc,
            seq_lens_encoder=paddle.to_tensor(np.asarray(lens, "i4")),
            seq_lens_decoder=paddle.to_tensor(np.zeros(b, "i4")),
            seq_lens_this_time=paddle.to_tensor(np.asarray(lens, "i4")),
            block_tables=paddle.to_tensor(
                np.asarray(pool.block_table_array(range(b)))),
            num_heads=h, kv_num_heads=hk, **kw)
        return out.numpy()

    np.testing.assert_allclose(run(True), run(False), rtol=2e-5, atol=2e-5)


def test_block_mha_out_quant_epilogue():
    """out_shift + out_smooth + out_scale: int8 output must equal the
    quantize-outside-the-op reference applied to the float output."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention

    rng = np.random.RandomState(9)
    h, hk, d, bs = 4, 2, 64, 32
    lens = [9, 14]
    b, total = len(lens), sum(lens)
    qkv_np = rng.randn(total, (h + 2 * hk) * d).astype("f4")
    shift = (rng.randn(h * d) * 0.1).astype("f4")
    smooth = (1.0 + rng.rand(h * d)).astype("f4")
    out_scale = 0.02

    def run(**kw):
        pool = PagedKVCachePool(num_blocks=16, block_size=bs,
                                num_kv_heads=hk, head_dim=d,
                                dtype=jnp.float32)
        for i, ln in enumerate(lens):
            pool.ensure(i, ln)
        kc = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))
        vc = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))
        return block_multihead_attention(
            paddle.to_tensor(qkv_np), kc, vc,
            seq_lens_encoder=paddle.to_tensor(np.asarray(lens, "i4")),
            seq_lens_decoder=paddle.to_tensor(np.zeros(b, "i4")),
            seq_lens_this_time=paddle.to_tensor(np.asarray(lens, "i4")),
            block_tables=paddle.to_tensor(
                np.asarray(pool.block_table_array(range(b)))),
            num_heads=h, kv_num_heads=hk, **kw).numpy()

    plain = run()
    fused = run(out_shift=paddle.to_tensor(shift),
                out_smooth=paddle.to_tensor(smooth), out_scale=out_scale)
    assert fused.dtype == np.int8
    expect = np.clip(
        np.round((plain + shift[None]) * smooth[None] / out_scale),
        -128, 127).astype(np.int8)
    # rounding at the .5 boundary may differ by 1 lsb between XLA and
    # numpy round-half-to-even on float noise; require exact match on
    # 99.9% and |diff| <= 1 everywhere
    diff = np.abs(fused.astype(np.int32) - expect.astype(np.int32))
    assert diff.max() <= 1
    assert (diff == 0).mean() > 0.999


def test_masked_mha_out_scale_quant():
    from paddle_tpu.incubate.nn.functional import masked_multihead_attention

    rng = np.random.RandomState(10)
    b, h, hk, d, smax = 2, 4, 2, 64, 32
    lens = np.asarray([9, 17], "i4")
    cache = rng.randn(2, b, smax, hk, d).astype("f4")
    x = rng.randn(b, h, d).astype("f4")
    plain = masked_multihead_attention(
        paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
        sequence_lengths=paddle.to_tensor(lens)).numpy()
    scale = 0.015
    q8 = masked_multihead_attention(
        paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
        sequence_lengths=paddle.to_tensor(lens), out_scale=scale).numpy()
    assert q8.dtype == np.int8
    expect = np.clip(np.round(plain / scale), -128, 127).astype(np.int8)
    diff = np.abs(q8.astype(np.int32) - expect.astype(np.int32))
    assert diff.max() <= 1
    assert (diff == 0).mean() > 0.999


def test_block_multihead_attention_fused_rope_bias_parity():
    """Round-4 verdict #6: rotary_embs + qkv_bias accepted INSIDE the op
    (reference contract) — parity vs apply-bias-then-rope-then-attend.
    Covers prefill (fresh cache) and a decode step whose rope positions
    must be the ABSOLUTE cache positions, both rope styles."""
    from paddle_tpu.incubate.nn.functional import block_multihead_attention
    from paddle_tpu.nn.functional.rope import apply_rotary_emb

    rng = np.random.RandomState(5)
    h, hk, d, bs = 4, 2, 64, 32
    lens = [7, 13]
    b, total = len(lens), sum(lens)
    max_seq = 64
    inv = 1.0 / (10000.0 ** (np.arange(0, d, 2) / d))
    ang = np.outer(np.arange(max_seq), inv)
    rot_np = np.stack([np.cos(ang), np.sin(ang)]).astype("f4")  # (2,S,D/2)
    bias_np = rng.randn((h + 2 * hk) * d).astype("f4") * 0.1

    for neox in (True, False):
        qkv_np = rng.randn(total, (h + 2 * hk) * d).astype("f4")
        cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)

        def pools():
            pool = PagedKVCachePool(num_blocks=16, block_size=bs,
                                    num_kv_heads=hk, head_dim=d,
                                    dtype=jnp.float32)
            for i, ln in enumerate(lens):
                pool.ensure(i, ln)
            kc = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))
            vc = paddle.to_tensor(np.zeros((16, bs, hk, d), "f4"))
            return pool, kc, vc

        common = dict(
            seq_lens_encoder=paddle.to_tensor(np.asarray(lens, "i4")),
            seq_lens_decoder=paddle.to_tensor(np.zeros(b, "i4")),
            seq_lens_this_time=paddle.to_tensor(np.asarray(lens, "i4")),
            num_heads=h, kv_num_heads=hk,
        )
        # fused path
        pool, kc_f, vc_f = pools()
        out_f = block_multihead_attention(
            paddle.to_tensor(qkv_np), kc_f, vc_f,
            block_tables=paddle.to_tensor(
                np.asarray(pool.block_table_array(range(b)))),
            rotary_embs=paddle.to_tensor(rot_np),
            qkv_bias=paddle.to_tensor(bias_np),
            use_neox_rotary_style=neox, **common)

        # reference: bias + per-token rope applied BEFORE the plain op
        biased = qkv_np + bias_np[None, :]
        q = biased[:, : h * d].reshape(total, h, d)
        k = biased[:, h * d: (h + hk) * d].reshape(total, hk, d)
        pos = np.concatenate([np.arange(ln) for ln in lens]).astype("i4")
        q_r = np.asarray(apply_rotary_emb(
            jnp.asarray(q)[None], jnp.asarray(rot_np[0]),
            jnp.asarray(rot_np[1]), neox=neox,
            position_ids=jnp.asarray(pos)[None])[0])
        k_r = np.asarray(apply_rotary_emb(
            jnp.asarray(k)[None], jnp.asarray(rot_np[0]),
            jnp.asarray(rot_np[1]), neox=neox,
            position_ids=jnp.asarray(pos)[None])[0])
        ref_qkv = np.concatenate(
            [q_r.reshape(total, -1), k_r.reshape(total, -1),
             biased[:, (h + hk) * d:]], axis=1).astype("f4")
        pool2, kc_r, vc_r = pools()
        out_r = block_multihead_attention(
            paddle.to_tensor(ref_qkv), kc_r, vc_r,
            block_tables=paddle.to_tensor(
                np.asarray(pool2.block_table_array(range(b)))),
            **common)
        np.testing.assert_allclose(
            np.asarray(out_f._value), np.asarray(out_r._value),
            rtol=2e-5, atol=2e-5)
        # caches must hold the ROTATED keys
        np.testing.assert_allclose(
            np.asarray(kc_f._value), np.asarray(kc_r._value),
            rtol=2e-5, atol=2e-5)

        # one decode step: fused rope must use ABSOLUTE position len_i
        for i in range(b):
            pool.ensure(i, lens[i] + 1)
            pool2.ensure(i, lens[i] + 1)
        qkv_dec = rng.randn(b, (h + 2 * hk) * d).astype("f4")
        dec_common = dict(
            seq_lens_encoder=paddle.to_tensor(np.zeros(b, "i4")),
            seq_lens_decoder=paddle.to_tensor(np.asarray(lens, "i4")),
            seq_lens_this_time=paddle.to_tensor(np.ones(b, "i4")),
            num_heads=h, kv_num_heads=hk,
        )
        out_fd = block_multihead_attention(
            paddle.to_tensor(qkv_dec), kc_f, vc_f,
            block_tables=paddle.to_tensor(
                np.asarray(pool.block_table_array(range(b)))),
            rotary_embs=paddle.to_tensor(rot_np),
            qkv_bias=paddle.to_tensor(bias_np),
            use_neox_rotary_style=neox, **dec_common)
        biased_d = qkv_dec + bias_np[None, :]
        qd = biased_d[:, : h * d].reshape(b, h, d)
        kd = biased_d[:, h * d: (h + hk) * d].reshape(b, hk, d)
        pos_d = np.asarray(lens, "i4")
        qd_r = np.asarray(apply_rotary_emb(
            jnp.asarray(qd)[None], jnp.asarray(rot_np[0]),
            jnp.asarray(rot_np[1]), neox=neox,
            position_ids=jnp.asarray(pos_d)[None])[0])
        kd_r = np.asarray(apply_rotary_emb(
            jnp.asarray(kd)[None], jnp.asarray(rot_np[0]),
            jnp.asarray(rot_np[1]), neox=neox,
            position_ids=jnp.asarray(pos_d)[None])[0])
        ref_qkv_d = np.concatenate(
            [qd_r.reshape(b, -1), kd_r.reshape(b, -1),
             biased_d[:, (h + hk) * d:]], axis=1).astype("f4")
        out_rd = block_multihead_attention(
            paddle.to_tensor(ref_qkv_d), kc_r, vc_r,
            block_tables=paddle.to_tensor(
                np.asarray(pool2.block_table_array(range(b)))),
            **dec_common)
        np.testing.assert_allclose(
            np.asarray(out_fd._value), np.asarray(out_rd._value),
            rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tp", [None, 2], ids=["one-chip", "tp2"])
def test_engine_decodes_through_the_kernel(tp):
    """The serving engine's decode quantum with the kernel routed in
    (interpret mode; per shard under ``tp``): greedy streams are what
    one request generated alone gives — ragged contexts, rows that
    retire early, the donated pools written in place between steps."""
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nlp.generation import generate_on_device
    from paddle_tpu.serving import ServingEngine

    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=tp is not None)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 5, 3)]
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        engine = ServingEngine(model, tp=tp, num_slots=3, block_size=4,
                               prefill_chunk=4, decode_quantum=3)
        reqs = [engine.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, (7, 4, 9))]
        engine.run()
        got = [engine.output_tokens(r) for r in reqs]
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    for req, tokens in zip(reqs, got):
        alone = generate_on_device(
            model, paddle.to_tensor(req.prompt[None, :]),
            max_new_tokens=req.max_new_tokens)
        np.testing.assert_array_equal(tokens, np.asarray(alone._value)[0])
