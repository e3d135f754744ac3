"""DataParallel (reference: python/paddle/parallel.py / EagerReducer —
unverified, SURVEY.md §0). Under GSPMD there is no bucketed grad
all-reduce to run: the wrapper shards the input batch over the ``dp``
mesh axis (and ``sharding`` when present — fsdp-style batch split) and
XLA reduces grads of replicated params automatically.
"""
from __future__ import annotations

from ..core.tensor import Tensor
from ..parallel import mesh as mesh_state
from ..tensor._helpers import apply

__all__ = ["DataParallel"]


class DataParallel:
    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        self._layers = layers

    def _shard_batch(self, x):
        if not isinstance(x, Tensor):
            return x

        def fn(v):
            if not v.ndim:
                return v
            spec = [mesh_state.data_axes(v.shape[0])] + [None] * (v.ndim - 1)
            return mesh_state.constraint(v, *spec)

        return apply(fn, x, op_name="dp_shard_batch")

    def __call__(self, *args, **kwargs):
        args = [self._shard_batch(a) for a in args]
        return self._layers(*args, **kwargs)

    def forward(self, *args, **kwargs):
        return self(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.__dict__["_layers"], name)

    def parameters(self, *a, **k):
        return self._layers.parameters(*a, **k)

    def state_dict(self, *a, **k):
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, *a, **k):
        return self._layers.set_state_dict(*a, **k)

    def train(self):
        self._layers.train()
        return self

    def eval(self):
        self._layers.eval()
        return self

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        pass

    def no_sync(self):
        import contextlib

        return contextlib.nullcontext()
