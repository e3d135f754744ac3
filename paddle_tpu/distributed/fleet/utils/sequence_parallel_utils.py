"""Megatron-style sequence parallelism (reference:
python/paddle/distributed/fleet/utils/sequence_parallel_utils.py —
unverified, SURVEY.md §0).

The reference all-gathers activations entering a parallel linear and
reduce-scatters on exit so LayerNorm/dropout run sequence-sharded; under
GSPMD the same schedule is asked for by constraining the sequence dim to
the ``mp`` axis around the matmuls; each op names the one dim it owns and
leaves the others to the propagation pass, as ``mp_layers._mark_last``
does (None would replicate a batch that is split over the data axes).
Layout convention matches the reference: (seq, batch, hidden) with the
sequence dim sharded. The training path of ``nlp/llama.py`` does not come
through here: it writes the schedule out
(``mp_layers.column_parallel_group`` / ``row_parallel_scatter``), because
constraints alone keep every all-reduce and add a gather.
"""
from __future__ import annotations

from ....nn.layer.layers import Layer
from ....nn import functional as F
from ....nn import initializer as I
from ....parallel import mesh as mesh_state
from ....tensor._helpers import apply, ensure_tensor
from ..layers.mpu.mp_layers import _mark_last

__all__ = [
    "ScatterOp", "GatherOp", "AllGatherOp", "ReduceScatterOp",
    "ColumnSequenceParallelLinear", "RowSequenceParallelLinear",
    "mark_as_sequence_parallel_parameter",
    "register_sequence_parallel_allreduce_hooks",
]


def _mark_sequence(v, axis):
    """Constrain dim 0, the sequence, to ``axis`` (``"mp"``, or None:
    whole on every ``mp`` member) and nothing else."""
    return mesh_state.constraint(
        v, axis, *([mesh_state.UNCONSTRAINED] * (v.ndim - 1)))


def _seq_shard(v):
    return _mark_sequence(v, "mp")


def _seq_full(v):
    return _mark_sequence(v, None)


class ScatterOp:
    """Split along the sequence dim across mp (forward scatter)."""

    @staticmethod
    def apply(input):
        return apply(_seq_shard, ensure_tensor(input), op_name="sp_scatter")


class GatherOp:
    @staticmethod
    def apply(input):
        return apply(_seq_full, ensure_tensor(input), op_name="sp_gather")


class AllGatherOp:
    @staticmethod
    def apply(input):
        return apply(_seq_full, ensure_tensor(input), op_name="sp_all_gather")


class ReduceScatterOp:
    @staticmethod
    def apply(input):
        return apply(_seq_shard, ensure_tensor(input), op_name="sp_reduce_scatter")


def mark_as_sequence_parallel_parameter(parameter):
    parameter.sequence_parallel = True


def register_sequence_parallel_allreduce_hooks(model, accumulation_steps=1,
                                               fuse_sequence_parallel_allreduce=False):
    """Grad sync of sequence-parallel params is automatic under GSPMD
    (grads of replicated params are reduced by the partitioner)."""
    return


class ColumnSequenceParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=None, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight._value = mesh_state.shard_value(self.weight._value, None, "mp")
        self.bias = (
            self.create_parameter((out_features,), is_bias=True)
            if has_bias
            else None
        )

    def forward(self, x):
        # entry: gather sequence (mp) → full activations for the matmul
        x = AllGatherOp.apply(x)
        out = F.linear(x, self.weight, self.bias)
        return apply(lambda v: _mark_last(v, "mp"), out,
                     op_name="col_sp_out")


class RowSequenceParallelLinear(Layer):
    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            (in_features, out_features), attr=weight_attr,
            default_initializer=I.XavierNormal(),
        )
        self.weight.is_distributed = True
        self.weight._value = mesh_state.shard_value(self.weight._value, "mp", None)
        self.bias = (
            self.create_parameter((out_features,), is_bias=True)
            if has_bias
            else None
        )

    def forward(self, x):
        out = F.linear(x, self.weight, self.bias)
        # exit: reduce-scatter along sequence
        return ReduceScatterOp.apply(out)
