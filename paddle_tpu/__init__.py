"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
API surface, built from scratch on JAX/XLA/Pallas.

``import paddle_tpu as paddle`` is the intended usage: the public names
mirror ``paddle.*`` (see SURVEY.md for the reference component map).
"""
from __future__ import annotations

import os as _os

# Multi-controller bootstrap MUST precede any backend use (jax.devices,
# device_put, ...). Importing the framework does not touch the backend
# (tests/test_chip_smoke.py pins that: the launcher parent lives in this
# package and must not take the chip), but the first op after it does —
# so a launched worker rendezvouses here, at import. The PJRT
# coordination service replaces the reference's TCPStore (SURVEY.md
# §2.3 TCPStore row — unverified). Gated on the launcher-private marker:
# subprocesses that merely INHERIT the public PADDLE_* vars must not try
# to join the rendezvous as a duplicate process.
if _os.environ.get("PADDLE_TPU_LAUNCHED") == "1":
    from ._bootstrap import rendezvous_from_env as _rdv

    _rdv()

from .version import __version__

# core
from .core.tensor import Tensor, Parameter, to_tensor
from .core.autograd import (
    no_grad,
    enable_grad,
    set_grad_enabled,
    is_grad_enabled,
    grad,
)
from .core.dtype import (
    DType, dtype, bfloat16, float16, float32, float64, int8, int16, int32,
    int64, uint8, bool_ as bool8, complex64, complex128, float8_e4m3fn,
    float8_e5m2, get_default_dtype, set_default_dtype, finfo, iinfo,
)
from .core.place import (
    CPUPlace, TPUPlace, CUDAPlace, XPUPlace, CustomPlace,
    set_device, get_device, is_compiled_with_cuda, is_compiled_with_rocm,
    is_compiled_with_xpu, is_compiled_with_custom_device,
)
from .core.random import seed, get_rng_state, set_rng_state
from .core.flags import set_flags, get_flags

# the op corpus (also patches Tensor methods)
from .tensor import *  # noqa: F401,F403
from . import tensor as tensor  # noqa: PLC0414

# `paddle.bool` is the dtype; paddle shadows the builtin here and so do we.
bool = bool8

_static_mode = False


def disable_static():
    global _static_mode
    _static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def in_dynamic_mode():
    return not _static_mode


def device_count():
    import jax

    return len(jax.devices())


def get_cudnn_version():
    return None


class batch:
    """paddle.batch generator wrapper (legacy reader API)."""

    def __init__(self, reader, batch_size, drop_last=False):
        self.reader, self.batch_size, self.drop_last = reader, batch_size, drop_last

    def __call__(self):
        buf = []
        for item in self.reader():
            buf.append(item)
            if len(buf) == self.batch_size:
                yield buf
                buf = []
        if buf and not self.drop_last:
            yield buf


# Subsystem namespaces (populated progressively; each mirrors paddle.<ns>).
from . import autograd  # noqa: E402
from . import nn  # noqa: E402
from .nn.layer.layers import ParamAttr  # noqa: E402
from . import optimizer  # noqa: E402
from .optimizer.optimizer import L1Decay, L2Decay  # noqa: E402
from . import regularizer  # noqa: E402
from . import amp  # noqa: E402
from . import io  # noqa: E402
from . import jit  # noqa: E402
from . import static  # noqa: E402
from .framework.io import save, load  # noqa: E402
from . import framework  # noqa: E402
from . import metric  # noqa: E402
from . import vision  # noqa: E402
from .hapi.model import Model  # noqa: E402
from . import hapi  # noqa: E402
from . import callbacks  # noqa: E402
from .hapi.summary import summary, flops  # noqa: E402
from . import incubate  # noqa: E402
from . import inference  # noqa: E402
from . import nlp  # noqa: E402
from . import serving  # noqa: E402
from . import profiler  # noqa: E402
from . import fft  # noqa: E402
from . import quantization  # noqa: E402
from . import peft  # noqa: E402
from . import sparse  # noqa: E402
from . import device  # noqa: E402
from . import visualdl  # noqa: E402
from . import distribution  # noqa: E402
from . import signal  # noqa: E402
from . import geometric  # noqa: E402
from . import audio  # noqa: E402
from . import text  # noqa: E402
from . import utils  # noqa: E402
from . import sysconfig  # noqa: E402

# populate the kernel-registry analog once the whole surface exists
from .core.dispatch import (  # noqa: E402
    OP_REGISTRY, register_op, populate_op_registry as _pop_reg,
)

_pop_reg()


def __getattr__(name):
    # lazy: paddle.distributed / paddle.DataParallel must not import the
    # distributed stack (and touch the backend bootstrap) at package
    # import time
    if name == "distributed":
        from . import distributed

        return distributed
    if name == "DataParallel":
        from .distributed.parallel import DataParallel

        return DataParallel
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def __dir__():
    # lazy __getattr__ names must be discoverable (dir() feeds the API
    # manifest generator and user introspection)
    return sorted(set(globals()) | {"distributed", "DataParallel"})
