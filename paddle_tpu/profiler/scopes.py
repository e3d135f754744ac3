"""The program's two vocabularies of names, in one place.

``SCOPES``: the ``jax.named_scope``s inside the jitted step programs (the
engine's ``jit_mixed`` / ``jit_quantum`` and the trainer's step). A scope
is HLO metadata only: XLA writes the name stack into every instruction's
``op_name`` and the profiler's trace carries it, so the reader
(:mod:`paddle_tpu.profiler.reader`) can say which scope a device
operation's seconds belong to; the compiled instructions are the same
with and without it. The backward of a scope has no site of its own: JAX
writes ``transpose(jvp(<scope>))`` and the reader turns that into a phase.

``SPANS``: the host regions the program spans with
:class:`~paddle_tpu.profiler.RecordEvent` (``PERF.md`` section 3 has what
reads each); the reader finds them on a trace's host planes by these
names and charges the device's idle time to the innermost one open.

Nothing here imports jax: the model files use the names as literals, and
``tests/test_profiler_scopes.py`` holds every traced equation of every
family's step programs to this list.
"""
from __future__ import annotations

SCOPES = {
    "embed": "the token embedding's lookup (and its multiplier)",
    "norm": "a layer's RMS norms (input, post-attention, the MoE "
            "families' extra ones)",
    "attn.proj": "q / k / v / o products, their biases, rotary positions, "
                 "per-head norms and a gate's product",
    "attn.full": "attention over a paged K/V table (decode kernel, chunk "
                 "kernel or the XLA folds), causal flash attention in "
                 "training",
    "attn.window": "attention over a window layer's ring",
    "attn.gate": "the sigmoid gate on the attention output",
    "mla": "latent attention: absorbed decode and the chunk kernel over "
           "the latent pool (its projections are attn.proj)",
    "cache.write": "the scatter of new keys / values / latents into the "
                   "pool, a ring or the slot state",
    "mlp": "a dense SwiGLU MLP",
    "moe.router": "router logits, top-k, the routing weights",
    "moe.experts": "the routed expert block outside its three parts",
    "moe.dispatch": "sort by expert, gather of the rows, group sizes",
    "moe.products": "the routed experts' two grouped products (the "
                    "grouped_matmul kernel on a TPU wherever its widths "
                    "allow, jax.lax.ragged_dot elsewhere) and the "
                    "activation "
                    "between them",
    "moe.combine": "un-sort, the routing weights, the sum over choices",
    "moe.shared": "the shared expert(s)",
    "ssm.in_proj": "a Mamba-2 mixer's input product and split",
    "ssm.conv": "the causal convolution and its tail",
    "ssm.scan": "the chunked state-space sum / the one-step state update",
    "ssm.out": "the gated norm and the output product",
    "kda.proj": "a KDA mixer's q / k / v products",
    "kda.conv": "its three causal convolutions and their tails",
    "kda.gates": "the two low-rank pairs' decay, beta and the l2 norms of "
                 "q and k",
    "kda.scan": "the delta rule: the chunked solve and sums / the one-step "
                "state update (the kda_decode_update kernel on a TPU)",
    "kda.out": "the per-head norm, the low-rank output gate and the "
               "output product",
    "mix.sum": "a parallel layer's two branches (attention, state-space "
               "mixer), each times its multiplier, added to the stream",
    "head": "final norm and the vocabulary product",
    "sample": "argmax / the sampler inside the engine's jitted bodies",
    "loss": "the trainer's loss (cross entropy over the logits)",
    "optimizer": "AdamW's update and the master-weight cast",
    "grad.clip": "the global-norm clip of the gradients",
}

SPANS = (
    "door.pump", "engine.step", "engine.admit", "request.queued",
    "engine.mixed", "engine.mixed.prepare", "engine.mixed.forward",
    "engine.mixed.select", "engine.mixed.emit",
    "engine.decode", "engine.decode.prepare", "engine.decode.enqueue",
    "engine.decode.args", "engine.decode.sync", "engine.decode.emit",
    "engine.spec_round",
    "train.run_steps", "train.args", "train.enqueue",
)

# names the TPU compiler writes OVER an operation's op_name where it expands
# the operation into calls of its own (``ragged-dot-none``,
# ``ragged-dot-metadata``): a prefix -> the scope the operation sits in.
# ``jax.lax.ragged_dot`` has two sites, both the routed experts' two
# products: ``grouped_expert_ffn`` (under ``moe.products``, off the TPU,
# under a mesh and at a width the ``grouped_matmul`` kernel cannot take;
# the kernel's own rows read ``<program>/grouped_matmul``) and
# ``MoELayer._grouped_ep_fn`` (the expert-parallel schedule inside
# ``shard_map``).
COMPILER_NAMES = {"ragged-dot": "moe.products"}
