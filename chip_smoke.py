"""chip_smoke.py — the quickest proof that paddle-tpu still starts on the
chip. One process, one TPU, the two main paths through the entry points a
user calls, at the published widths of Llama-2-7B (hidden 4096, 32 heads
x d128, FFN 11008, vocab 32000) with DEPTH cut to the L=4 one 16 GB v5e
holds, bf16, weights random from a seed:

  train   build_step -> JittedTrainStep.run_steps, B1 x S4096
  serve   paddle.inference.serve(model, policy=no_shed_policy()) with the
          engine's defaults, a handful of ragged requests submitted together

``--chips 4`` runs instead ONLY what exists across chips: a fleet-mesh
(mp=2 x sharding=2) train step against the same step on one device, and a
``tp=4`` serving door against a ``tp=1`` door on the same trace.

Each phase prints one JSON line as it ends. The LAST line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase, or no TPU, is ``"ok": false`` and a non-zero exit. It
measures nothing: no rate or utilization is claimed from this script.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

TRAIN = dict(batch=1, seq=4096, steps=4)
SERVE = dict(prompt_lens=(200, 137, 310, 96, 251), max_new=32,
             max_context=2048)
# the serial comparison step must fit ONE chip beside nothing else:
# B2 x S2048 is the one-chip phase's token count per step
MESH_TRAIN = dict(batch=2, seq=2048, steps=2, mp=2, sharding=2)
TP_SERVE = dict(tp=4, prompt_lens=(130, 70, 40), max_new=32,
                max_context=2048)

# A greedy token is RIGHT when the plain full-sequence forward scores it
# within this many steps of the bf16 grid of its own best token. The
# logits are bf16: two kernels (chunked-prefill vs whole-prompt flash
# attention) or two all-reduce orders round the same logit a step apart,
# so a near-tie may break either way and the streams part there. Streams
# are therefore NOT pinned bit-equal on the chip as they are in f32 on
# the CPU; instead every token must be a near-best one, and where two
# paths part, BOTH picks must be. Found on the v5e (PR 22): engine vs
# sequential oracle part at token 9 of 32, tp=4 vs tp=1 in 1 stream of 3
# (at token 8), every token within 1.0 step.
TIE_STEPS = 2
TRAIN_KERNELS = {"flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv", "rms_norm_fwd", "rms_norm_bwd"}
# mesh loss against the serial step's, relative. CPU f32 holds 1e-4
# (__graft_entry__.dryrun_multichip); bf16 matmuls whose contraction is
# split over mp round differently. Found on four v5e chips (PR 22):
# 4.3e-6 at step 1, 1.4e-5 at step 2.
MESH_LOSS_RTOL = 2e-4


def device_report():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def result_line(ok, device, **extra):
    """The contract's last line (extra keys only on failure)."""
    return json.dumps({"ok": bool(ok), "device": device, **extra})


def compile_counts():
    """What the program's own compile counters hold for this process so
    far, over every step label (``profiler.count_compile_events``:
    ``jax.monitoring`` events, so eager ops and jitted programs count
    alike): seconds in XLA backend compiles (a persistent-cache hit counts
    its load instead), the cache's hits and misses, and per step label the
    executables JAX asked for."""
    from paddle_tpu.obs import MetricsRegistry
    from paddle_tpu.profiler import count_compile_events

    count_compile_events()  # listening from the first phase on
    series = {m["name"]: m["series"]
              for m in MetricsRegistry.process().snapshot()["metrics"]}

    def total(name, **want):
        return sum(s["value"] for s in series[name]
                   if want.items() <= s["labels"].items())

    by_step = {s["labels"]["step"]: int(s["value"])
               for s in series["jax_compile_requests_total"]}
    return (total("jax_compile_seconds_total", stage="backend")
            + total("jax_compile_seconds_total", stage="cache_load"),
            int(total("jax_compile_cache_hits_total")),
            int(total("jax_compile_cache_misses_total")), by_step)


def run_phase(name, fn, **kwargs):
    """Run one phase and print its JSON line. A phase that raises or
    whose pass condition fails propagates: nothing is let through."""
    import jax

    t0 = time.perf_counter()
    s0, h0, m0, r0 = compile_counts()
    detail = fn(**kwargs)
    gc.collect()  # the phase's params/state leave the device with it
    s1, h1, m1, r1 = compile_counts()
    stats = [d.memory_stats() or {} for d in jax.devices()]
    print(json.dumps({
        "phase": name, "ok": True,
        "seconds": round(time.perf_counter() - t0, 2),
        "compile_seconds": round(s1 - s0, 2),
        "compile_cache": {"hits": h1 - h0, "misses": m1 - m0},
        # executables JAX asked for, by the step that caused them
        "compile_requests": {k: v - r0.get(k, 0) for k, v in r1.items()
                             if v - r0.get(k, 0)},
        "device": device_report(),
        # process-wide high-water mark so far, and what is still held
        "peak_bytes_in_use": [s.get("peak_bytes_in_use") for s in stats],
        "bytes_in_use": [s.get("bytes_in_use") for s in stats],
        **detail,
    }), flush=True)
    return detail


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def _config_report(cfg):
    return {"hidden": cfg.hidden_size, "heads": cfg.num_attention_heads,
            "head_dim": cfg.head_dim, "ffn": cfg.intermediate_size,
            "vocab": cfg.vocab_size, "layers": cfg.num_hidden_layers,
            "dtype": "bfloat16"}


# ------------------------------------------------------------------ set-up
def enable_compile_cache():
    """Turn on JAX's persistent compilation cache. The directory comes
    from ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it
    itself; no other directory is set in code), else it is the fixed
    ``.jax_cache/`` of this checkout: the path is part of the cache key,
    so it is never built from a temp name, a pid or the time. Every
    program is kept, small ones included."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def headline_config(**overrides):
    """Llama-2-7B at its published widths (h4096, 32 heads x d128, FFN
    11008, vocab 32000) with DEPTH cut to the L=4 one v5e-16G holds
    (~1.07B params; bf16 params + f32 master + bf16 Adam moments)."""
    from paddle_tpu.nlp import LlamaConfig

    kw = dict(num_hidden_layers=4, tensor_parallel=False,
              use_recompute=False)
    kw.update(overrides)
    return LlamaConfig.llama2_7b(**kw)


def build_step(cfg, batch, seq, **step_kw):
    """The trainer's defaults around ``cfg``: bf16 weights, AdamW 1e-4
    with decay 0.01, f32 master weights, bf16 moments, the criterion on
    f32 logits; one seeded batch of ids."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.train import JittedTrainStep
    from paddle_tpu.nlp import LlamaForCausalLM, LlamaPretrainingCriterion

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.astype("bfloat16")
    crit = LlamaPretrainingCriterion(cfg)

    def criterion(out, labels):
        return crit(out.astype("float32"), labels)

    opt = paddle.optimizer.AdamW(
        1e-4, parameters=model.parameters(), weight_decay=0.01,
        multi_precision=True, moment_dtype="bfloat16")
    step = JittedTrainStep(model, criterion, opt, **step_kw)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (batch, seq)))
    return step, ids


# ------------------------------------------------------------------ train
def _train_losses(cfg, batch, seq, steps, **step_kw):
    """``steps`` steps on ONE repeated batch through run_steps; returns
    (losses, kernel names in the compiled K-step program)."""
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas._utils import compiled_kernel_names

    step, ids = build_step(cfg, batch, seq, **step_kw)
    stacked = paddle.to_tensor(
        np.repeat(np.asarray(ids._value)[None], steps, axis=0))
    # compiled ahead of the dispatch so its text can be read; run_steps
    # then finds the same program in the compile cache
    compiled = step.lower_steps(stacked, stacked).compile()
    kernels = compiled_kernel_names(compiled.as_text())
    losses = np.asarray(step.run_steps(stacked, stacked)._value, np.float32)
    return losses, kernels


def _check_kernels(kernels):
    """On a TPU the compiled step must hold the flash-attention and
    rms-norm Mosaic calls: the kernels ran, not a reference path."""
    import jax

    if jax.devices()[0].platform == "tpu":
        check(TRAIN_KERNELS <= kernels,
              f"compiled train step lacks {sorted(TRAIN_KERNELS - kernels)}"
              f" as tpu_custom_call (found {sorted(kernels)})")


def train_phase(cfg, batch, seq, steps):
    losses, kernels = _train_losses(cfg, batch, seq, steps)
    check(np.isfinite(losses).all(), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    _check_kernels(kernels)
    return {"config": _config_report(cfg), "batch": batch, "seq": seq,
            "losses": [round(float(x), 4) for x in losses],
            "tpu_custom_calls": sorted(kernels)}


# ------------------------------------------------------------------ serve
def _build_model(cfg):
    import paddle_tpu as paddle
    from paddle_tpu.nlp import LlamaForCausalLM

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.astype("bfloat16")
    model.eval()
    return model


def _prompts(cfg, prompt_lens):
    rng = np.random.RandomState(0)
    return [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
            for n in prompt_lens]


def _serve(model, prompts, max_new, max_context, **serve_kw):
    """All prompts submitted together through the front door; returns
    (generated token rows, engine stats)."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import no_shed_policy

    door = paddle.inference.serve(
        model, policy=no_shed_policy(), max_context=max_context, **serve_kw)
    streams = [door.submit(p, max_new_tokens=max_new) for p in prompts]
    door.run_until_idle()
    for i, s in enumerate(streams):
        check(s.finish_reason not in (None, "error", "shed"),
              f"request {i} finished with {s.finish_reason!r}")
        check(len(s.request.tokens) == max_new,
              f"request {i} made {len(s.request.tokens)} of {max_new} tokens")
    stats = door.engine.engine_stats()
    check(stats["mixed_steps"] > 0 and stats["decode_quanta"] > 0,
          f"chunked prefill and the decode quantum must both run: {stats}")
    return ([np.asarray(s.request.tokens, np.int32) for s in streams],
            {k: int(stats[k]) for k in
             ("mixed_steps", "decode_quanta", "prefill_tokens")},
            [s.finish_reason for s in streams])


def reference_gaps(model, prompt, tokens):
    """(len(tokens), vocab): how far each vocabulary entry sits below
    the best one at each generated position, in steps of the bf16 grid
    at the best logit — under the plain full-sequence forward of
    ``model``, teacher-forced on ``tokens``. 0 marks the argmax."""
    import paddle_tpu as paddle

    full = np.concatenate([prompt, tokens])[None, :-1]
    with paddle.no_grad():
        logits = np.asarray(
            model(paddle.to_tensor(full))._value[0, len(prompt) - 1:],
            np.float32)
    best = logits.max(axis=-1, keepdims=True)
    step = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(best), 1e-30))) - 7)
    return (best - logits) / step


def check_greedy(model, prompt, got, want, what):
    """``got`` (the path under test) must be near-best tokens of the
    reference forward throughout; where it parts from ``want`` (the
    path it is compared with) that path's pick must be one too."""
    gaps = reference_gaps(model, prompt, got)
    mine = gaps[np.arange(len(got)), got]
    check(mine.max() <= TIE_STEPS,
          f"{what}: a token sits {mine.max():.1f} bf16 steps below the "
          f"reference argmax (allowed {TIE_STEPS}): {mine.tolist()}")
    n = min(len(got), len(want))
    diff = np.nonzero(np.asarray(got[:n]) != np.asarray(want[:n]))[0]
    fork = int(diff[0]) if diff.size else n
    found = {"argmax_tokens": int((mine == 0).sum()), "of": len(got),
             "widest_gap_bf16_steps": float(mine.max()),
             "same_stream": fork == len(got), "agree_prefix": fork}
    if fork < n:
        theirs = float(gaps[fork, want[fork]])
        check(theirs <= TIE_STEPS,
              f"{what}: streams part at token {fork}, where the other "
              f"path's pick is {theirs:.1f} bf16 steps below the best — "
              f"no tie explains it")
        found["fork_gaps_bf16_steps"] = [float(mine[fork]), theirs]
    return found


def serve_phase(cfg, prompt_lens, max_new, max_context):
    from paddle_tpu.nlp.generation import generate_on_device
    import paddle_tpu as paddle

    model = _build_model(cfg)
    prompts = _prompts(cfg, prompt_lens)
    outs, stats, reasons = _serve(model, prompts, max_new, max_context)
    # request 0 against the sequential single-request oracle of
    # tests/test_serving.py, and against the plain forward
    want = np.asarray(generate_on_device(
        model, paddle.to_tensor(prompts[0][None, :]),
        max_new_tokens=max_new)._value)[0, len(prompts[0]):]
    return {"config": _config_report(cfg), "requests": len(prompts),
            "prompt_lens": list(prompt_lens), "new_tokens": max_new,
            "finish_reasons": reasons, "engine": stats,
            "vs_sequential_oracle": check_greedy(
                model, prompts[0], outs[0], want, "serve request 0")}


# ------------------------------------------------- four chips (--chips 4)
def mesh_train_phase(cfg, batch, seq, steps, mp, sharding):
    """One JittedTrainStep under a fleet mesh (mp x sharding) against
    the same step run serially on one device."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.parallel import mesh as mesh_state

    serial, _ = _train_losses(cfg, batch, seq, steps)
    gc.collect()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 1, "mp_degree": mp, "pp_degree": 1,
        "sharding_degree": sharding,
    }
    fleet.init(is_collective=True, strategy=strategy)
    try:
        # ZeRO's sharding group is a data-parallel group: the batch
        # splits over the same axis as the optimizer state
        parallel, kernels = _train_losses(
            cfg, batch, seq, steps, state_sharding_axis="sharding",
            input_batch_axes=("sharding",))
    finally:
        mesh_state.set_mesh(None)
    check(np.isfinite(parallel).all() and np.isfinite(serial).all(),
          f"non-finite loss: mesh {parallel} serial {serial}")
    _check_kernels(kernels)
    rel = np.abs(parallel - serial) / np.abs(serial)
    check(rel.max() <= MESH_LOSS_RTOL,
          f"mesh loss {parallel} vs serial {serial}: rel {rel} > "
          f"{MESH_LOSS_RTOL}")
    return {"config": _config_report(cfg), "mesh": {"mp": mp,
                                                    "sharding": sharding},
            "batch": batch, "seq": seq,
            "losses_mesh": [float(x) for x in parallel],
            "losses_serial": [float(x) for x in serial],
            "rel_diff": [float(x) for x in rel], "rtol": MESH_LOSS_RTOL,
            "tpu_custom_calls": sorted(kernels)}


def tp_serve_phase(cfg, tp, prompt_lens, max_new, max_context):
    """serve(model, tp=N) against a tp=1 door on the same trace. Each
    door gets its own freshly seeded model (identical weights)."""
    prompts = _prompts(cfg, prompt_lens)
    model1 = _build_model(cfg)
    outs1, _, _ = _serve(model1, prompts, max_new, max_context)
    outs_n, stats, reasons = _serve(
        _build_model(cfg), prompts, max_new, max_context, tp=tp)
    # the serial model is the reference both doors answer to
    found = [check_greedy(model1, p, got, want, f"tp={tp} request {i}")
             for i, (p, got, want)
             in enumerate(zip(prompts, outs_n, outs1))]
    return {"config": _config_report(cfg), "tp": tp,
            "requests": len(prompts), "prompt_lens": list(prompt_lens),
            "new_tokens": max_new, "finish_reasons": reasons,
            "engine": stats,
            "bit_equal_streams": sum(f["same_stream"] for f in found),
            "vs_tp1": found}


def fft_phase():
    """Does this chip do complex dtypes? (paddle.fft runs on-device;
    there is no host path to hide a refusal.)"""
    import paddle_tpu as paddle

    x = np.arange(16, dtype=np.float32)
    y = paddle.fft.fft(paddle.to_tensor(x))
    err = float(np.abs(np.asarray(y._value) - np.fft.fft(x)).max())
    check(err < 1e-3, f"paddle.fft.fft off by {err}")
    return {"dtype": str(y._value.dtype), "max_abs_err": err,
            "on": sorted(d.platform for d in y._value.devices())}


# ------------------------------------------------------------------- main
def run(chips, device):
    import jax

    if device["platform"] != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; jax found {device['platform']!r} "
            f"({device['kind']})")
    if device["count"] != chips:
        raise RuntimeError(
            f"--chips {chips} but jax sees {device['count']} device(s)")
    enable_compile_cache()
    print(json.dumps({"phase": "start", "device": device,
                      "model": "Llama-2-7B at its published widths, depth "
                               "cut from 32 layers to the 4 one 16 GB chip "
                               "holds with its optimizer state",
                      "jax": jax.__version__,
                      "compile_cache_dir":
                          jax.config.jax_compilation_cache_dir}), flush=True)
    if chips == 1:
        run_phase("fft", fft_phase)
        run_phase("train", train_phase, cfg=headline_config(),
                  **TRAIN)
        run_phase("serve", serve_phase, cfg=headline_config(),
                  **SERVE)
    else:
        tp_cfg = headline_config(tensor_parallel=True)
        run_phase("mesh_train", mesh_train_phase, cfg=tp_cfg,
                  **MESH_TRAIN)
        run_phase("tp_serve", tp_serve_phase, cfg=tp_cfg,
                  **TP_SERVE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh train step and the tp=4 "
                         "door, each against its one-chip comparison")
    args = ap.parse_args(argv)
    device = None
    try:
        device = device_report()
        run(args.chips, device)
    except Exception as e:  # the boundary: report, then fail the run
        traceback.print_exc()
        print(result_line(False, device, error=f"{type(e).__name__}: {e}"),
              flush=True)
        return 1
    print(result_line(True, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
