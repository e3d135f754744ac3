"""Trainer: model FLOP/s utilization. Forward plus backward operations per
token with no recomputation (``counts.train_flops_per_token``) times the
traced window's tokens per second, over chips times the bf16 peak."""
from benchmark.harness import counts


def read(obs):
    if not obs.get("dispatches"):
        return None
    tok_s = obs["tokens_per_dispatch"] * obs["dispatches"] / obs["window_s"]
    flops = counts.train_flops_per_token(obs["config"], obs["seq"])
    return 100.0 * flops * tok_s / (obs["chips"] * obs["peaks"]["bf16_flops"])
