"""Context parallelism: attention over sequences sharded on the ``sep``
mesh axis (SURVEY.md §5 long-context — the exceed-the-reference axis;
reference analog: PaddleNLP RingFlashAttention /
``paddle.distributed.fleet`` sep-parallel utilities — unverified,
SURVEY.md §0).

Two TPU-native schedules, both pure ``shard_map`` programs over the
global mesh so XLA schedules the ICI traffic:

- **Ring attention** (``ring_flash_attention``): every device keeps its
  query shard resident and rotates the K/V shards one hop around the
  ``sep`` ring with ``lax.ppermute`` per step, folding each visiting
  block into a numerically-stable online-softmax accumulator — the
  flash-attention recurrence lifted to the device level. Memory per chip
  is O(S/n); the permute rides ICI and overlaps with the block matmul
  under XLA's async collectives.
- **Ulysses** (``ulysses_attention``): two ``lax.all_to_all`` reshards —
  sequence-sharded → head-sharded, run the full-sequence attention
  locally, and reshard back. Cheaper comm volume than ring for moderate
  sequence lengths, but caps the sep degree at the head count.

Both are reverse-differentiable (scan + ppermute/all_to_all have
transpose rules), so the eager tape and the fully-jitted train step both
get gradients for free.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ....parallel import mesh as mesh_state
from ....tensor._helpers import apply, ensure_tensor

__all__ = [
    "ring_flash_attention",
    "ulysses_attention",
    "sep_attention",
    "split_inputs_sequence_dim",
]


def _repeat_kv(q, k, v):
    """GQA/MQA: repeat kv heads up to the query head count."""
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _block_attn(q, k, v, scale, mask):
    """One unnormalized attention block in f32.

    q (B,Sq,H,D), k/v (B,Sk,H,D), mask (Sq,Sk) bool or None.
    Returns (o, m, l): o (B,Sq,H,D) unnormalized, m/l (B,H,Sq) row
    max / row sum of exp(s - m). Fully-masked rows yield m=-inf, l=0,
    o=0 — the combine step treats them as absent.
    """
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])  # exp(-inf)=0 handles masked rows
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o, m, l


def _combine(acc, blk):
    """Fold one block's (o, m, l) into the running accumulator."""
    o_a, m_a, l_a = acc
    o_b, m_b, l_b = blk
    m_new = jnp.maximum(m_a, m_b)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.exp(m_a - m_safe)  # -inf accumulator → weight 0
    beta = jnp.exp(m_b - m_safe)
    l_new = alpha * l_a + beta * l_b
    # o is (B,S,H,D); weights are (B,H,S) → (B,S,H,1)
    wa = jnp.transpose(alpha, (0, 2, 1))[..., None]
    wb = jnp.transpose(beta, (0, 2, 1))[..., None]
    o_new = wa * o_a + wb * o_b
    return o_new, m_new, l_new


def _finalize(o, m, l, dtype):
    l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows stay 0
    return (o / jnp.transpose(l_safe, (0, 2, 1))[..., None]).astype(dtype)


def _ring_local(q, k, v, *, axis, n, causal, scale):
    """Body run per-device under shard_map: q,k,v are the local shards
    (B, S/n, H, D); returns the local output shard.

    K/V rotate at their native (GQA) head count — the repeat to the query
    head count happens per block, locally, so ring ICI traffic stays at
    HK-sized volume."""
    idx = lax.axis_index(axis)
    sq = q.shape[1]
    perm = [(j, (j + 1) % n) for j in range(n)]
    q_pos = idx * sq + jnp.arange(sq)

    def _mask(src):
        if not causal:
            return None
        k_pos = src * sq + jnp.arange(sq)
        return q_pos[:, None] >= k_pos[None, :]

    def _block(kb, vb, src):
        kr, vr = _repeat_kv(q, kb, vb)
        return _block_attn(q, kr, vr, scale, _mask(src))

    # step 0: the resident block — folded outside the scan so the ring
    # does exactly n-1 permutes (the n-th rotation's result is dead)
    acc = _block(k, v, idx)

    def step(carry, t):
        kb, vb, o, m, l = carry
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        src = (idx - t) % n  # which device's block we now hold
        o, m, l = _combine((o, m, l), _block(kb, vb, src))
        return (kb, vb, o, m, l), None

    if n > 1:
        (kb, vb, *acc), _ = lax.scan(step, (k, v, *acc), jnp.arange(1, n))
    return _finalize(*acc, q.dtype)


def _ulysses_local(q, k, v, *, axis, n, causal, scale):
    """All-to-all reshard seq→heads, local full attention, reshard back."""
    from ....nn.functional.attention import _xla_attention

    if k.shape[2] % n != 0:  # GQA heads not splittable: expand first
        k, v = _repeat_kv(q, k, v)
    # (B, S/n, H, D) → (B, S, H/n, D)
    q = lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
    k = lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
    v = lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
    # _xla_attention expands any remaining GQA gap after the reshard, so
    # the all_to_all moved K/V at their native HK-sized volume
    o = _xla_attention(q, k, v, causal=causal, scale=scale)
    # (B, S, H/n, D) → (B, S/n, H, D)
    return lax.all_to_all(o, axis, split_axis=1, concat_axis=2, tiled=True)


def _sep_call(local_fn, query, key, value, is_causal, scale, axis):
    mesh = mesh_state.get_mesh()
    n = mesh_state.mesh_axis_size(axis)
    query = ensure_tensor(query)
    key = ensure_tensor(key)
    value = ensure_tensor(value)
    if scale is None:
        scale = 1.0 / math.sqrt(query._value.shape[-1])
    if mesh is None or n <= 1:
        from ....nn.functional.attention import scaled_dot_product_attention

        # sdpa always scales by 1/sqrt(d); fold a custom scale into q so
        # sharded and unsharded runs agree
        d = query._value.shape[-1]
        default = 1.0 / math.sqrt(d)
        if abs(scale - default) > 1e-12 * default:
            query = query * (scale * math.sqrt(d))
        return scaled_dot_product_attention(
            query, key, value, is_causal=is_causal
        )
    b, s, h, _ = query._value.shape
    hk = key._value.shape[2]
    if s % n != 0:
        raise ValueError(
            f"context parallelism requires seq len ({s}) divisible by sep "
            f"degree ({n})"
        )

    # Carry the surrounding hybrid axes into the shard_map so GSPMD does
    # NOT all-gather over dp/mp: batch stays dp-sharded and heads stay
    # mp-sharded (TP attention heads are already split by the column-
    # parallel projections); the ring/all_to_all runs only over ``sep``.
    def _axis_if(name, dim):
        sz = mesh_state.mesh_axis_size(name)
        return name if (sz > 1 and dim % sz == 0) else None

    batch_ax = _axis_if("dp", b)
    head_ax = _axis_if("mp", h) if _axis_if("mp", h) == _axis_if("mp", hk) \
        else None
    mp = mesh_state.mesh_axis_size("mp") if head_ax else 1

    if local_fn is _ulysses_local and (h // mp) % n != 0:
        raise ValueError(
            f"ulysses requires local num_heads ({h // mp}) divisible by "
            f"sep degree ({n}); use ring_flash_attention instead"
        )

    q_spec = P(batch_ax, axis, head_ax, None)
    kv_spec = P(batch_ax, axis, head_ax, None)
    fn = jax.shard_map(
        functools.partial(
            local_fn, axis=axis, n=n, causal=is_causal, scale=scale
        ),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return apply(fn, query, key, value, op_name="sep_attention")


def ring_flash_attention(query, key, value, is_causal=False, scale=None,
                         axis="sep", name=None):
    """Ring attention over the ``sep`` axis. Layout (B, S, H, D) with the
    global sequence logically sharded over ``sep``; q/k/v are the global
    arrays (GSPMD keeps them sharded)."""
    return _sep_call(_ring_local, query, key, value, is_causal, scale, axis)


def ulysses_attention(query, key, value, is_causal=False, scale=None,
                      axis="sep", name=None):
    """DeepSpeed-Ulysses-style all_to_all attention over ``sep``."""
    return _sep_call(_ulysses_local, query, key, value, is_causal, scale, axis)


def sep_attention(query, key, value, is_causal=False, scale=None,
                  schedule="ring", axis="sep", name=None):
    """Dispatch by schedule name: ``ring`` | ``ulysses``."""
    if schedule == "ring":
        return ring_flash_attention(query, key, value, is_causal, scale, axis)
    if schedule == "ulysses":
        return ulysses_attention(query, key, value, is_causal, scale, axis)
    raise ValueError(f"unknown context-parallel schedule: {schedule!r}")


def split_inputs_sequence_dim(inputs, axis="sep", seq_dim=1):
    """Constrain batch tensors' sequence dim onto the ``sep`` axis (the
    reference splits+scatters per rank; under GSPMD one constraint does
    the same job). Leaves without a ``seq_dim`` dim (None, scalars,
    per-example vectors) pass through untouched."""
    def _one(t):
        if t is None:
            return t
        t = ensure_tensor(t)
        if t.ndim <= seq_dim:
            return t
        spec = [None] * t.ndim
        spec[seq_dim] = axis
        return apply(
            lambda v: mesh_state.constraint(v, *spec), t,
            op_name="split_sequence_dim",
        )

    return jax.tree_util.tree_map(
        _one, inputs,
        is_leaf=lambda x: x is None or not isinstance(x, (list, tuple, dict)),
    )
