"""Weights from ``--seed``, made on the device. The benchmark owns them: the
program's model is given these arrays and the reference draws the same
leaves again from the same seed, so neither takes anything the other made.

Every leaf is a pure function of (seed, its index in the family's leaf
table), and every value is an integer over a power of two with at most
eight significant bits, so it is exact in bfloat16 and in float32 and no
compiler's choice of fusion can round it differently when it is drawn
again: matrices and biases are k / 8192 with k uniform in -255..255
(standard deviation 0.018), norm scales 1 + k / 128 with k in -12..12. The
seed enters as traced data, so every seed runs the same compiled program.
JAX's default threefry generator is used: its bits do not depend on how an
array is sharded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

def seed_words(seed):
    """``--seed`` (any whole number up to a little over 2**31) as three
    16-bit words for ``fold_in``."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return jnp.asarray([seed & 0xFFFF, (seed >> 16) & 0xFFFF, seed >> 32],
                       jnp.uint32)


def _key(words):
    k = jax.random.key(20250927)
    for i in range(3):
        k = jax.random.fold_in(k, words[i])
    return k


def _leaf(words, index, shape, kind, dtype):
    key = jax.random.fold_in(_key(words), index)
    if kind == "norm":
        k = jax.random.randint(key, shape, -12, 13)
        w = (128 + k).astype(jnp.float32) * 2.0 ** -7
    elif kind in ("matrix", "bias"):
        k = jax.random.randint(key, shape, -255, 256)
        w = k.astype(jnp.float32) * 2.0 ** -13
    else:
        raise ValueError(f"unknown leaf kind {kind!r}")
    return w.astype(dtype)


def fill(table, seed, dtype, old_values):
    """All leaves of ``table`` ([(name, shape, kind)]) in ONE jitted call.
    ``old_values`` (arrays of the same shapes: the program's freshly built
    parameters) give the placements and are deleted first, so the new
    weights take their memory and their shardings."""
    shardings = [v.sharding for v in old_values]
    meshes = [s.mesh for s in shardings
              if isinstance(s, jax.sharding.NamedSharding)]
    if meshes:  # leaves the program left off its mesh go on it, replicated
        whole = jax.sharding.NamedSharding(meshes[0],
                                           jax.sharding.PartitionSpec())
        shardings = [s if isinstance(s, jax.sharding.NamedSharding)
                     else whole for s in shardings]
    for v in old_values:
        v.delete()

    def gen(words):
        return [_leaf(words, i, shape, kind, dtype)
                for i, (_, shape, kind) in enumerate(table)]

    return jax.jit(gen, out_shardings=shardings)(seed_words(seed))


def leaf_reader(table, seed, dtype):
    """``get_leaf(name)`` for the reference: draws that one leaf again."""
    index = {name: (i, shape, kind)
             for i, (name, shape, kind) in enumerate(table)}
    words = seed_words(seed)
    one = jax.jit(_leaf, static_argnums=(2, 3, 4))

    def get_leaf(name):
        i, shape, kind = index[name]
        return one(words, jnp.uint32(i), tuple(shape), kind, dtype)

    return get_leaf


def delta_norms(table, seed, dtype, values):
    """Per leaf, the norm of ``values[i]`` minus the seed's leaf i (drawn
    again in ``dtype``), in one jitted call: how far training moved it."""
    def norms(words, vals):
        return [jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32)
            - _leaf(words, i, shape, kind, dtype).astype(jnp.float32))))
            for i, ((_, shape, kind), v) in enumerate(zip(table, vals))]

    return [float(x) for x in jax.device_get(
        jax.jit(norms)(seed_words(seed), list(values)))]


def norms(values):
    """Per array, its norm in float32, in one jitted call."""
    return [float(x) for x in jax.device_get(jax.jit(
        lambda vals: [jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                      for v in vals])(list(values)))]
