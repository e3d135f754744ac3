"""One scope vocabulary over every step program (ISSUE 37): each
equation of the traced ``_mixed`` and ``_quantum`` programs of the
serving families, and of the trainer's step, sits in a scope of
``paddle_tpu.profiler.scopes.SCOPES`` unless the short allow-list below
takes it. Name stacks of the jaxpr: nothing is compiled.

What may stay outside a scope: an equation with no floating-point output
(slot bookkeeping, masks, counters, keys), and the residual stream between
a layer's blocks (its adds, a residual multiplier, layout-only
reshapes and broadcasts), which XLA fuses into the neighbours it feeds.
Never a product, a scatter, a gather, a reduction, a sort, a loop or a
kernel.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.profiler.reader import scope_of
from paddle_tpu.profiler.scopes import SCOPES
from paddle_tpu.serving import ServingEngine

from family_harness import tiny_model as _model

RESIDUAL_STREAM = {"add", "mul", "reshape", "broadcast_in_dim",
                   "convert_element_type"}


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in (value if isinstance(value, (list, tuple)) else [value]):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def leaves(jaxpr, prefix=""):
    """(primitive, full name stack, equation) of every equation that
    holds no other: a scan's, a pjit's or a kernel call's body stands for
    it, under its name stack."""
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        inner = list(_sub_jaxprs(eqn))
        if inner and eqn.primitive.name != "pallas_call":
            for sub in inner:
                yield from leaves(sub, stack)
        else:
            yield eqn.primitive.name, stack, eqn


def allowed_outside(primitive, eqn):
    floats = [v for v in eqn.outvars
              if jnp.issubdtype(v.aval.dtype, jnp.floating)]
    return not floats or primitive in RESIDUAL_STREAM


def unscoped(fn, args):
    rows = list(leaves(jax.make_jaxpr(fn)(*args).jaxpr))
    assert len(rows) > 100
    seen = {scope_of(stack)[0] for _, stack, _ in rows}
    bad = [(p, stack) for p, stack, eqn in rows
           if scope_of(stack)[0] == "unscoped"
           and not allowed_outside(p, eqn)]
    return bad, seen


def _train_step():
    import chip_smoke
    from paddle_tpu.nlp import LlamaConfig

    cfg = LlamaConfig.tiny(tensor_parallel=False, sliding_window=8)
    step, ids = chip_smoke.build_step(cfg, 1, 32)
    stacked = paddle.to_tensor(
        np.repeat(np.asarray(ids._value)[None], 2, axis=0))
    return step._jitted_multi, step._steps_args(stacked, stacked)


EXPECTED = {
    "llama": {"embed", "norm", "attn.proj", "attn.full", "cache.write",
              "mlp", "head", "sample"},
    "deepseek_v3": {"embed", "norm", "attn.proj", "mla", "cache.write",
                    "mlp", "moe.router", "moe.dispatch", "moe.products",
                    "moe.combine", "moe.shared", "head", "sample"},
    "granitemoehybrid": {"embed", "norm", "attn.proj", "attn.full",
                         "cache.write", "ssm.in_proj", "ssm.conv",
                         "ssm.scan", "ssm.out", "moe.router",
                         "moe.dispatch", "moe.products", "moe.combine",
                         "moe.shared", "head", "sample"},
    "afmoe": {"embed", "norm", "attn.proj", "attn.full", "attn.window",
              "attn.gate", "cache.write", "mlp", "moe.router",
              "moe.dispatch", "moe.products", "moe.combine", "moe.shared",
              "head", "sample"},
    "nemotron_h": {"embed", "norm", "attn.proj", "attn.full", "cache.write",
                   "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out",
                   "moe.router", "moe.dispatch", "moe.products",
                   "moe.combine", "moe.shared", "head", "sample"},
    # every layer both branches: attention, the mixer, their scaled sum
    # under a scope of its own, a dense MLP, and both writes of the cache
    "falcon_h1": {"embed", "norm", "attn.proj", "attn.full", "cache.write",
                  "ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out",
                  "mix.sum", "mlp", "head", "sample"},
    # one gated GQA layer and three KDA mixers, experts in every layer
    "solar_open2": {"embed", "norm", "attn.proj", "attn.full", "attn.gate",
                    "cache.write", "kda.proj", "kda.conv", "kda.gates",
                    "kda.scan", "kda.out", "moe.router", "moe.dispatch",
                    "moe.products", "moe.combine", "moe.shared", "head",
                    "sample"},
    "train": {"embed", "norm", "attn.proj", "attn.window", "mlp", "head",
              "loss", "optimizer"},
}


@pytest.mark.parametrize("family,program", [
    (f, p) for f in ("llama", "deepseek_v3", "granitemoehybrid", "afmoe",
                     "nemotron_h", "falcon_h1", "solar_open2")
    for p in ("mixed", "quantum")] + [("train", "step")])
def test_every_equation_sits_in_a_scope(family, program):
    if family == "train":
        fn, args = _train_step()
    else:
        eng = ServingEngine(_model(family), num_slots=2, block_size=8,
                            max_context=64, prefill_chunk=16,
                            decode_quantum=4)
        eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
        eng._admit()
        fn, args = (eng.mixed_step_target() if program == "mixed"
                    else eng.decode_step_target())
    bad, seen = unscoped(fn, args)
    assert not bad, bad[:10]
    assert seen - {"unscoped"} <= set(SCOPES)
    assert EXPECTED[family] <= seen, EXPECTED[family] - seen


@pytest.mark.parametrize("path", ["kernel", "ragged_dot"])
def test_both_forms_of_the_products_sit_under_moe_products(path,
                                                           monkeypatch):
    """The routed experts' two products under either form the rule picks
    (ISSUE 38): the ``grouped_matmul`` kernel's calls (on a chip trace
    their rows read ``<program>/grouped_matmul``, as the chunk kernel's
    do) and the ``ragged_dot``s (``ragged-dot-none`` there, mapped by
    ``COMPILER_NAMES``) are equations of ``moe.products``."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import (
        grouped_expert_ffn)
    from paddle_tpu.ops.pallas import grouped_matmul as kernel

    monkeypatch.setattr(kernel, "_BLOCK_M", 32)
    paddle.set_flags({"FLAGS_pallas_force": path == "kernel"})
    try:
        jaxpr = jax.make_jaxpr(lambda x, i, g, a, b: grouped_expert_ffn(
            x, i, g, a, b, jax.nn.gelu))(
                jnp.zeros((64, 128)), jnp.zeros((64, 2), jnp.int32),
                jnp.ones((64, 2)), jnp.zeros((4, 128, 128)),
                jnp.zeros((4, 128, 128)))
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    rows = list(leaves(jaxpr.jaxpr))
    products = [stack for p, stack, eqn in rows
                if p == ("pallas_call" if path == "kernel"
                         else "ragged_dot_general")]
    assert len(products) == 2
    assert {scope_of(stack)[0] for stack in products} == {"moe.products"}
    if path == "kernel":
        assert not [p for p, _, _ in rows if p == "ragged_dot_general"]
    assert not [(p, stack) for p, stack, eqn in rows
                if scope_of(stack)[0] == "unscoped"
                and not allowed_outside(p, eqn)]


def test_every_scope_has_a_site():
    """No dead vocabulary: every scope is written by some program above,
    ``moe.experts`` (the block outside its three parts: a tiled call's
    loop) and ``grad.clip`` (an optimizer with a clip) apart."""
    everywhere = set().union(*EXPECTED.values())
    assert set(SCOPES) - everywhere == {"moe.experts", "grad.clip"}
    import paddle_tpu.optimizer as optim

    paddle.seed(0)
    layer = paddle.nn.Linear(4, 4)
    opt = optim.AdamW(1e-3, parameters=layer.parameters(),
                      grad_clip=optim.ClipGradByGlobalNorm(1.0)
                      if hasattr(optim, "ClipGradByGlobalNorm")
                      else paddle.nn.ClipGradByGlobalNorm(1.0))
    p = [q._value for q in layer.parameters()]
    state = opt.functional_state_init(p)
    jaxpr = jax.make_jaxpr(lambda p, g, s: opt.functional_apply(
        p, g, s, jnp.float32(1e-3), jnp.int32(1)))(p, p, state)
    assert "grad.clip" in {scope_of(stack)[0]
                           for _, stack, _ in leaves(jaxpr.jaxpr)}
