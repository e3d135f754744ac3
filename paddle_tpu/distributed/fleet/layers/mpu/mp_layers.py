"""Tensor-parallel layers (reference:
python/paddle/distributed/fleet/layers/mpu/mp_layers.py — unverified,
SURVEY.md §0).

Same classes, TPU-native mechanics: each layer holds the FULL logical
weight, placed with a NamedSharding over the ``mp`` mesh axis
(column-parallel: output dim sharded; row-parallel: input dim sharded) and
constrains its activations; XLA GSPMD inserts the all-reduce the
reference does with ``mp_allreduce_sum``/``c_identity`` ops.

Training's forward on a mesh moves the hidden stream another way
(Megatron's sequence-parallel schedule, ``hidden_stream_axis``): between
the products the stream is split over ``mp`` along the SEQUENCE, and a
half-layer moves it once each way, written out in a ``shard_map``:
``column_parallel_group`` gathers it and ``row_parallel_scatter``
reduce-scatters it. The model decides once a forward and calls either
these or the classes' own ``forward``: the replicated stream, which
serving over ``tp`` and every other caller keep.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .....nn.layer.layers import Layer
from .....nn import functional as F
from .....nn import initializer as I
from .....nn.functional.common import linear_values
from .....ops.pallas._utils import count_traced_program
from .....parallel import mesh as mesh_state
from .....tensor._helpers import apply, ensure_tensor

__all__ = [
    "VocabParallelEmbedding", "ColumnParallelLinear", "RowParallelLinear",
    "ParallelCrossEntropy", "hidden_stream_axis", "column_parallel_group",
    "row_parallel_scatter", "mp_hidden_stream_programs",
]


def _mark_last(v, axis):
    """Constrain the one dim a tensor-parallel layer owns, the last, to
    ``axis`` (``"mp"``, or None: whole on every ``mp`` member). The
    leading (batch/seq) dims stay UNCONSTRAINED: in a PartitionSpec None
    is "replicated over every axis", so naming them would gather a batch
    that is split over dp / sharding / sep, and compute it once a member."""
    return mesh_state.constraint(
        v, *([mesh_state.UNCONSTRAINED] * (v.ndim - 1)), axis)


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim sharded over mp."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim), attr=weight_attr,
            default_initializer=I.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight._value = mesh_state.shard_value(self.weight._value, "mp", None)

    def forward(self, x):
        out = F.embedding(x, self.weight)
        # The vocab-sharded gather's partial sums all-reduce to a hidden
        # state whose LAST dim must be replicated (Megatron semantics),
        # NOT E-over-mp: an E-sharded hidden colliding with a downstream
        # (dp, sep)-sharded constraint makes GSPMD fall back to
        # replicate-then-repartition (full remat).
        return apply(lambda v: _mark_last(v, None), out,
                     op_name="vocab_parallel_gather")


class ColumnParallelLinear(Layer):
    """Weight (in, out) sharded along out; output stays mp-sharded unless
    gather_output."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=None, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self._gather_output = gather_output
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight._value = mesh_state.shard_value(
            self.weight._value, None, "mp"
        )
        if has_bias:
            self.bias = self.create_parameter(
                (out_features,), is_bias=True
            )
            self.bias.is_distributed = True
            self.bias._value = mesh_state.shard_value(self.bias._value, "mp")
        else:
            self.bias = None

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)

        return apply(
            lambda v: _mark_last(v, None if self._gather_output else "mp"),
            out, op_name="column_parallel_out")


class RowParallelLinear(Layer):
    """Weight (in, out) sharded along in; GSPMD inserts the forward
    all-reduce (the reference's mp_allreduce_sum)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self._input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight._value = mesh_state.shard_value(
            self.weight._value, "mp", None
        )
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        x = ensure_tensor(x)
        if self._input_is_parallel:
            x = apply(lambda v: _mark_last(v, "mp"), x,
                      op_name="row_parallel_in")
        out = F.linear(x, self.weight, self.bias)
        return apply(lambda v: _mark_last(v, None), out,
                     op_name="row_parallel_out")


def mp_hidden_stream_programs():
    """``mp_hidden_stream_programs_total{path}`` (``sequence`` |
    ``replicated``) on the process's registry: forwards traced on a mesh
    with ``mp`` > 1, by where their hidden stream sits between the
    tensor-parallel products."""
    from .....obs.registry import MetricsRegistry

    return MetricsRegistry.process().counter(
        "mp_hidden_stream_programs_total",
        "forwards traced on a mesh with mp > 1, by where the hidden "
        "stream sits between tensor-parallel products (sequence | "
        "replicated)")


def hidden_stream_axis(seq_len, layers, cached=False, seq_taken=False):
    """``"mp"`` where a forward over ``seq_len`` positions can keep its
    hidden stream split over ``mp`` along the sequence (the ``axis`` of
    the two functions below, and of the norms between them), else None:
    the replicated stream. Read from what the call brings, no option: a
    mesh with ``mp`` > 1, no cache (``cached``: decode's one row and the engine's
    flattened steps need the stream whole), a length ``mp`` divides, the
    sequence not already split over another axis (``seq_taken``: ``sep``)
    and every one of the model's tensor-parallel ``layers`` the plain
    class (a LoRA-wrapped or quantised projection brings its own
    ``forward``). Counted once a traced program; a mesh without ``mp``
    counts nothing."""
    mp = mesh_state.mesh_axis_size("mp")
    if mp == 1:
        return None
    split = (not cached and not seq_taken and seq_len % mp == 0 and all(
        type(m) in (ColumnParallelLinear, RowParallelLinear) for m in layers))
    count_traced_program(mp_hidden_stream_programs(),
                         "sequence" if split else "replicated")
    return "mp" if split else None


# Both ways in the UNTILED form, whose transpose is the other one: at one
# batch row a member the TPU compiler turns a tiled reduce-scatter along
# dim 1 of (1, S, E) into an all-reduce of the whole and a slice.

def _gather_sequence(x, axis):
    """A member's rows (B, S / n, E) -> every member's, (B, S, E)."""
    b, _, e = x.shape
    return jax.lax.all_gather(x, axis, axis=1).reshape(b, -1, e)


def _scatter_sequence(x, axis):
    """Partial sums (B, S, E) -> the member's rows of the sum."""
    b, s, e = x.shape
    n = mesh_state.mesh_axis_size(axis)
    return jax.lax.psum_scatter(x.reshape(b, n, s // n, e), axis,
                                scatter_dimension=1)


def _member_linear(data):
    """``linear_values(x, w)`` for a manual region that splits ``x``'s
    batch over the mesh axes ``data`` (a PartitionSpec entry, None: none)
    and holds ``w`` whole on them. The same products forward and for
    ``dx``; ``dw`` is summed over ``data`` from the product's float32
    partial sums and rounded once, as the partitioner reduces
    ``F.linear``'s. (The region's own transpose would round each member's
    ``dw`` to the weight's dtype first and sum in that dtype: one more
    rounding a step, and an error that grows with the data-parallel
    degree.)"""

    @jax.custom_vjp
    def linear(x, w):
        return linear_values(x, w)

    def fwd(x, w):
        return linear_values(x, w), (x, w)

    def bwd(res, g):
        x, w = res
        dw = jnp.einsum("...i,...o->io", x, g,
                        preferred_element_type=jnp.promote_types(
                            w.dtype, jnp.float32))
        if data:
            dw = jax.lax.psum(dw, data)
        return linear_values(g, w.T), dw.astype(w.dtype)

    linear.defvjp(fwd, bwd)
    return linear


def column_parallel_group(x, layers, axis):
    """The products of a column-parallel GROUP (``q`` / ``k`` / ``v``;
    ``gate`` / ``up``; the head alone) on one input ``x`` (B, S, E) whose
    sequence is split over the mesh axis ``axis`` (``hidden_stream_axis``);
    one output a layer, its last dim over ``axis`` (whole where the layer
    gathers its output). ``x`` is gathered over ``axis`` along S ONCE and
    every product runs on it against its own column shard inside one
    manual region, so the backward sums the group's partial input
    gradients on the chip and they leave through ONE reduce-scatter.
    Weights enter whole on the data axes (ZeRO's gathers stay outside);
    ``_member_linear`` sums their gradients there."""
    n = len(layers)
    biases = [layer.bias for layer in layers if layer.bias is not None]

    def fn(v, *wb):
        batch = mesh_state.data_axes(v.shape[0])
        linear = _member_linear(batch)

        def body(xs, *ws):
            xg = _gather_sequence(xs, axis)
            return tuple(linear(xg, w) for w in ws)

        outs = jax.shard_map(
            body, mesh=mesh_state.get_mesh(),
            in_specs=(P(batch, axis, None), *[P(None, axis)] * n),
            out_specs=(P(batch, None, axis),) * n)(v, *wb[:n])
        bs = iter(wb[n:])
        return tuple(out if layer.bias is None
                     else out + next(bs).astype(out.dtype)
                     for layer, out in zip(layers, outs))

    outs = apply(fn, ensure_tensor(x), *[layer.weight for layer in layers],
                 *biases, op_name="column_parallel_group")
    return [apply(lambda v: _mark_last(v, None), out,
                  op_name="column_parallel_out")
            if layer._gather_output else out
            for layer, out in zip(layers, outs)]


def row_parallel_scatter(x, layer, axis):
    """A row-parallel product (``o_proj``, ``down_proj``) of ``x`` (B, S,
    F), F split over the mesh axis ``axis``, into a hidden stream whose
    sequence is split over it: the local product's partial sums leave
    through one reduce-scatter over ``axis`` along S (backward: one
    all-gather of the cotangent); the bias is added to the member's own
    rows."""

    def fn(v, w, *b):
        batch = mesh_state.data_axes(v.shape[0])
        linear = _member_linear(batch)
        out = jax.shard_map(
            lambda xs, w: _scatter_sequence(linear(xs, w), axis),
            mesh=mesh_state.get_mesh(),
            in_specs=(P(batch, None, axis), P(axis, None)),
            out_specs=P(batch, axis, None))(v, w)
        return out + b[0].astype(out.dtype) if b else out

    bias = [] if layer.bias is None else [layer.bias]
    return apply(fn, ensure_tensor(x), layer.weight, *bias,
                 op_name="row_parallel_scatter")


class ParallelCrossEntropy(Layer):
    """Cross entropy over mp-sharded logits (reference:
    ParallelCrossEntropy / c_softmax_with_cross_entropy). GSPMD computes
    the sharded logsumexp with the same collective schedule."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self._ignore_index = ignore_index

    def forward(self, input, label):
        loss = F.cross_entropy(
            input, label, reduction="none", ignore_index=self._ignore_index
        )
        from .....tensor.manipulation import unsqueeze

        return unsqueeze(loss, -1)
