"""Mixture-of-Experts with expert parallelism (reference:
python/paddle/incubate/distributed/models/moe/moe_layer.py, GlobalScatter/
GlobalGather collective ops — unverified, SURVEY.md §0/§2.3 EP row).

TPU-native design: the GShard einsum formulation. Expert weights are
STACKED (num_experts leading dim) and sharded over an ``expert`` mesh
axis; token dispatch/combine are einsums against one-hot capacity
masks, so GSPMD lowers the dispatch to the same all-to-all the reference
issues explicitly via GlobalScatter — no hand-written collectives.
"""
from .gate import (  # noqa: F401
    TopKGate, GShardGate, SwitchGate, SigmoidTopKGate, SoftmaxTopKGate)
from .moe_layer import MoELayer  # noqa: F401

__all__ = ["MoELayer", "TopKGate", "GShardGate", "SwitchGate",
           "SigmoidTopKGate", "SoftmaxTopKGate"]
