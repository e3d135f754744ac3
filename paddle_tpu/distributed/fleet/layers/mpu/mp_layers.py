"""Tensor-parallel layers (reference:
python/paddle/distributed/fleet/layers/mpu/mp_layers.py — unverified,
SURVEY.md §0).

Same classes, TPU-native mechanics: each layer holds the FULL logical
weight, placed with a NamedSharding over the ``mp`` mesh axis
(column-parallel: output dim sharded; row-parallel: input dim sharded) and
constrains its activations; XLA GSPMD inserts the all-reduce the
reference does with ``mp_allreduce_sum``/``c_identity`` ops.
"""
from __future__ import annotations

import jax.numpy as jnp

from .....nn.layer.layers import Layer
from .....nn import functional as F
from .....nn import initializer as I
from .....parallel import mesh as mesh_state
from .....tensor._helpers import apply, ensure_tensor

__all__ = [
    "VocabParallelEmbedding", "ColumnParallelLinear", "RowParallelLinear",
    "ParallelCrossEntropy",
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
