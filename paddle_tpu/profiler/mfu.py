"""MFU accounting — the honest meter SURVEY.md §7 hard-part #6 demands.

Model FLOPs (not hardware FLOPs): standard 6*N*T matmul accounting for a
train step (fwd 2NT + bwd 4NT) plus causal attention score/value terms
(12 * L * S * E * T * 0.5). MFU = achieved model FLOP/s ÷ chip peak.
"""
from __future__ import annotations

import time

import jax

__all__ = ["peak_flops_per_chip", "transformer_train_flops", "MFUMeter"]

# bf16 peak FLOP/s per chip (public spec sheets)
_PEAKS = {
    "v5 lite": 197e12,   # v5e
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12,   # Trillium
    "v6e": 918e12,
    "v3": 123e12,
    "v2": 45e12,
}


def peak_flops_per_chip(device=None):
    """Peak bf16 FLOP/s of the attached chip. The CPU backend has no
    such peak and reads 0.0 (callers then report raw throughput, not
    MFU); an accelerator whose ``device_kind`` is not in the table is an
    error, never a guess."""
    device = device or jax.devices()[0]
    if device.platform == "cpu":
        return 0.0
    kind = device.device_kind.lower()
    for key in sorted(_PEAKS, key=len, reverse=True):
        if key in kind:
            return _PEAKS[key]
    raise ValueError(
        f"no peak FLOP/s known for device_kind {device.device_kind!r}; "
        "add it to profiler.mfu._PEAKS with its source")


def transformer_train_flops(n_params, tokens, num_layers=0, seq_len=0,
                            hidden=0, causal=True):
    """Model FLOPs for ONE train step over ``tokens`` tokens.

    6*N*T covers all parameter matmuls (fwd+bwd); the attention
    score+value matmuls add 12 * L * S * E per token (fwd 4*S*E per layer,
    ×3 for fwd+bwd), halved when causal.
    """
    flops = 6.0 * n_params * tokens
    if num_layers and seq_len and hidden:
        attn = 12.0 * num_layers * seq_len * hidden * tokens
        if causal:
            attn *= 0.5
        flops += attn
    return flops


class MFUMeter:
    """Times step callables and reports tokens/sec + MFU."""

    def __init__(self, flops_per_step, tokens_per_step, n_chips=1):
        self.flops_per_step = flops_per_step
        self.tokens_per_step = tokens_per_step
        self.n_chips = n_chips
        self.peak = peak_flops_per_chip() * n_chips
        self._times = []

    def measure(self, step_fn, warmup=2, iters=10, sync=None):
        """Run ``step_fn()`` warmup+iters times; blocks on the result each
        iteration (pass ``sync`` to override how)."""
        for _ in range(warmup):
            r = step_fn()
            _block(r, sync)
        for _ in range(iters):
            t0 = time.perf_counter()
            r = step_fn()
            _block(r, sync)
            self._times.append(time.perf_counter() - t0)
        return self.report()

    def report(self):
        if not self._times:
            return {}
        # median step time is robust to stragglers/retraces
        ts = sorted(self._times)
        step_time = ts[len(ts) // 2]
        achieved = self.flops_per_step / step_time
        return {
            "step_time_s": step_time,
            "tokens_per_sec": self.tokens_per_step / step_time,
            "tokens_per_sec_per_chip": self.tokens_per_step / step_time / self.n_chips,
            "model_tflops_per_sec": achieved / 1e12,
            "mfu": (achieved / self.peak) if self.peak else None,
            "n_steps_timed": len(ts),
        }


def _block(result, sync):
    if sync is not None:
        sync(result)
        return
    # NOTE: jax.block_until_ready can return early on experimental PJRT
    # plugins; a device→host copy of (a leaf of) the result is the only
    # reliable completion barrier.
    leaves = jax.tree_util.tree_leaves(
        result._value if hasattr(result, "_value") else result)
    if leaves:
        jax.device_get(leaves[0])
