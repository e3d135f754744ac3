"""Traffic kind ``closed_batches``: offline batch generation through the
program's front door (``paddle.inference.serve``). ``batch`` requests are
submitted together, each a seeded prompt of ``prompt_len`` tokens asking
for ``new_tokens`` greedy tokens; the door is pumped until the last stream
closes; then the next batch. A closed loop with one client that holds
``batch`` requests.

The window holds WHOLE batches only: a batch starts only if the time used
plus ``margin`` times the slowest batch so far still fits in ``--seconds``
(the first always starts). The rate is all tokens of the whole batches over
all the time from the first submit to the last close, the time between
batches included, and the tails are over all their requests, so a parent
and a change that fit different counts still compare. Prompts are a pure
function of (seed, batch index).

Once the window has closed and the program is freed, the finished requests
(all of them up to ``check_requests``, else that many drawn from the seed,
the longest among them) are run through the plain reference, prompt and
served tokens in one pass each, and the gap by which each served token's
logit lies below the reference's best is what `correct` is decided on
(greedy tokens only).

Traffic parameters: ``batch``, ``prompt_len``, ``new_tokens``, ``margin``,
``check_requests``, ``traced_batches``.
"""
from __future__ import annotations

import statistics
import time

import numpy as np


def make_prompts(seed, batch_index, batch, prompt_len, vocab):
    """One batch's prompts: token ids in [1, vocab)."""
    rng = np.random.default_rng([int(seed), 11, int(batch_index) + 1])
    return rng.integers(1, vocab, (batch, prompt_len), dtype=np.int32)


def fits(used, slowest, margin, seconds):
    """May another batch start? (The first always may.)"""
    return slowest is None or used + margin * slowest <= seconds


def drive_batch(door, prompts, new_tokens, spans):
    """Submit one batch and pump until every stream has closed. Returns
    the batch's record: submit time, per request the time of each token as
    the door delivered it, close time, and the streams."""
    t_submit = time.perf_counter()
    with spans.span("submit"):
        streams = [door.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
    seen = [0] * len(streams)
    arrivals = [[] for _ in streams]
    engine = door.engine
    while engine.has_work:
        stats = engine.stats
        mixed, quanta = stats["mixed_steps"], stats["decode_quanta"]
        with spans.span("pump") as row:
            door.pump()
        mixed = stats["mixed_steps"] - mixed
        quanta = stats["decode_quanta"] - quanta
        row[0] = ("pump:mixed" if mixed else
                  "pump:quantum" if quanta else "pump:other")
        now = row[2]
        for i, s in enumerate(streams):
            n = len(s.request.tokens)
            arrivals[i].extend([now] * (n - seen[i]))
            seen[i] = n
    return {"submitted": t_submit, "closed": time.perf_counter(),
            "arrivals": arrivals, "streams": streams}


def summarise(batches, new_tokens):
    """End-to-end numbers over the window's whole batches: all of their
    tokens over all the time from the first submit to the last close."""
    rates, ttft, gaps, failed, total = [], [], [], 0, 0
    for b in batches:
        tokens = sum(len(a) for a in b["arrivals"])
        total += tokens
        rates.append(tokens / (b["closed"] - b["submitted"]))
        for a, s in zip(b["arrivals"], b["streams"]):
            if s.finish_reason != "length" or len(a) != new_tokens:
                failed += 1
                continue
            ttft.append((a[0] - b["submitted"]) * 1e3)
            gaps.extend(np.diff(a) * 1e3)
    span = batches[-1]["closed"] - batches[0]["submitted"]
    return {"out_tok_s": total / span,
            "first_token_ms": statistics.median(ttft) if ttft else None,
            "gap_p95_ms": float(np.percentile(gaps, 95)) if gaps else None,
            "ttft_samples": len(ttft), "gap_samples": len(gaps),
            "batch_tok_s": rates}, failed


def build(ctx):
    import paddle_tpu as paddle
    from paddle_tpu.serving import no_shed_policy

    cfg, fam = ctx.config, ctx.family
    model = fam.build_model(cfg)
    fam.install_weights(model, cfg, ctx.seed)
    model.eval()
    return paddle.inference.serve(model, policy=no_shed_policy(),
                                  **cfg["engine"])


def serve_window(ctx):
    """Build the door, warm it up, run the window. Everything that holds
    the program's memory lives in this frame and dies with it; what comes
    back is plain data."""
    from benchmark.harness import probe

    cfg, tf = ctx.config, ctx.traffic
    n, p_len, new = int(tf["batch"]), int(tf["prompt_len"]), int(tf["new_tokens"])
    vocab = int(cfg["vocab_size"])
    door = build(ctx)
    engine = door.engine
    ctx.log({"built": "door", "bytes_in_use": probe.memory(ctx.devices)[1],
             "pool_blocks": engine.engine_stats()["pool"]["num_blocks"]})

    # ---- set-up: one batch of the cell's own shape warms every program
    t0 = time.perf_counter()
    warm = drive_batch(door, make_prompts(ctx.seed, -1, n, p_len, vocab),
                       new, ctx.spans)
    ctx.log({"warmup": {"seconds": time.perf_counter() - t0,
                        "finish": sorted({s.finish_reason
                                          for s in warm["streams"]}),
                        "compile": ctx.meter.snapshot()}})
    del warm
    ctx.spans.rows.clear()

    # ---- the window: whole batches only
    keys = ("steps", "mixed_steps", "decode_quanta", "prefill_tokens",
            "occupancy_sum")
    stats0 = {k: engine.stats[k] for k in keys}
    before = ctx.meter.snapshot()
    if ctx.trace:
        ctx.start_trace()
    batches, slowest = [], None
    t_begin = time.perf_counter()
    ctx.setup_done(t_begin)
    with ctx.spans.span("window"):
        while fits(time.perf_counter() - t_begin, slowest,
                   float(tf["margin"]), ctx.seconds):
            if ctx.trace and len(batches) >= int(tf["traced_batches"]):
                break
            prompts = make_prompts(ctx.seed, len(batches), n, p_len, vocab)
            with ctx.spans.span("batch"):
                rec = drive_batch(door, prompts, new, ctx.spans)
            rec["prompts"] = prompts
            batches.append(rec)
            took = rec["closed"] - rec["submitted"]
            slowest = took if slowest is None else max(slowest, took)
    t_end = time.perf_counter()
    traced = ctx.stop_trace() if ctx.trace else None
    after = ctx.meter.snapshot()
    peak, _ = probe.memory(ctx.devices)
    pool = engine.engine_stats()["pool"]
    e2e, failed = summarise(batches, new)
    steps = {k: engine.stats[k] - stats0[k] for k in keys}
    done = [(b["prompts"][i], np.asarray(s.request.tokens, np.int32))
            for b in batches for i, s in enumerate(b["streams"])
            if s.finish_reason == "length"]
    return {
        "window_s": t_end - t_begin, "e2e": e2e, "failed": failed,
        "attempted": sum(len(b["streams"]) for b in batches),
        "batch_seconds": [b["closed"] - b["submitted"] for b in batches],
        "first_token_s": [min(a[0] for a in b["arrivals"]) - b["submitted"]
                          for b in batches],
        "steps": steps, "done": done, "peak": peak, "traced": traced,
        "compile_requests": after["requests"] - before["requests"],
        "pool": {"num_blocks": pool["num_blocks"],
                 "peak_blocks_in_use": pool["peak_blocks_in_use"]}}


def run(ctx):
    from benchmark.harness import probe

    cfg, tf, fam = ctx.config, ctx.traffic, ctx.family
    w = serve_window(ctx)
    e2e, steps, done = w["e2e"], w["steps"], w["done"]
    batch_tok_s = e2e.pop("batch_tok_s")
    ctx.log({"window": {
        "seconds": w["window_s"], "batches": len(w["batch_seconds"]),
        "batch_seconds": w["batch_seconds"],
        "batch_tok_s": batch_tok_s,
        "first_token_s": w["first_token_s"],
        "ttft_samples": e2e.pop("ttft_samples"),
        "gap_samples": e2e.pop("gap_samples"), "engine_steps": steps,
        "compile_requests": w["compile_requests"]}})

    # ---- the finished requests: all of them up to ``check_requests``,
    # else a sample drawn from the seed with the longest in it
    rng = np.random.default_rng([int(ctx.seed), 13])
    n_check = min(len(done), int(tf["check_requests"]))
    longest = max(range(len(done)), key=lambda i: len(done[i][1]))
    pick = [longest] + [i for i in rng.permutation(len(done)).tolist()
                        if i != longest]
    rows = [done[i] for i in sorted(pick[:n_check])]

    # ---- the program is freed; the reference reads the sample
    probe.release()
    t0 = time.perf_counter()
    ctx.log({"freed": {"bytes_in_use": probe.memory(ctx.devices)[1]}})
    get_leaf = fam.leaf_reader(cfg, ctx.seed)
    gaps, cgaps = fam.reference.gap_below_best(cfg, get_leaf, rows,
                                               control=ctx.control)
    gaps = np.asarray(gaps)
    compared = [{"name": "gap_max", "value": float(gaps.max())},
                {"name": "gap_mean", "value": float(gaps.mean())}]
    control = None
    if cgaps is not None:
        cgaps = np.asarray(cgaps)
        control = [{"name": "gap_max", "value": float(cgaps.max())},
                   {"name": "gap_mean", "value": float(cgaps.mean())}]
    ctx.log({"check_seconds": time.perf_counter() - t0,
             "requests": len(rows), "positions": int(gaps.size),
             "not_best": int((gaps > 0).sum())})

    return {
        "attempted": w["attempted"], "failed": w["failed"],
        "end_to_end": e2e, "compared": compared, "control": control,
        "memory_peak_bytes": w["peak"],
        "observations": {
            "window_s": w["window_s"], "batches": len(w["batch_seconds"]),
            "engine_steps": steps, "pool": w["pool"],
            "batch": int(tf["batch"]), "prompt_len": int(tf["prompt_len"]),
            "new_tokens": int(tf["new_tokens"]),
            "mixed_pump_seconds": ctx.spans.durations("pump:mixed"),
            "quantum_pump_seconds": ctx.spans.durations("pump:quantum"),
            "first_token_ms": e2e["first_token_ms"],
            "batch_tok_s": batch_tok_s,
            "compile_requests": w["compile_requests"],
            "memory_peak_bytes": w["peak"], "trace": w["traced"]},
    }
