"""Traffic kind ``train_job``: a pretraining job through the program's
``JittedTrainStep.run_steps``: seeded batches of ``batch`` sequences of
``seq`` tokens, one dispatch of ``steps_per_dispatch`` steps after another,
each waited for once the next has been issued (one dispatch in flight, as in
a training loop that reads its loss a step late).

Set-up builds ONE step object from the seed's weights and drives it through
its first ``check_steps`` steps with the window's own call and feed; those
steps compile and warm every program, and the losses, the first gradient's
norms (from the optimizer's first moments) and the parameters' change they
leave are what the plain reference is compared with once the window has
closed and the program's state is freed. The same object then runs the
window.

Traffic parameters: ``batch``, ``seq``, ``steps_per_dispatch`` (1: the
check reads the state after one step), ``batches`` (how many different
seeded batches the window cycles through), ``check_steps``,
``traced_dispatches``.
"""
from __future__ import annotations

import statistics
import time

import numpy as np


def make_batches(seed, n, batch, seq, vocab):
    """``n`` batches (steps_per_dispatch=1 each) of token ids in [0, vocab),
    a pure function of the seed. Rows all differ."""
    return [np.random.default_rng([int(seed), 7, i]).integers(
        0, vocab, (1, batch, seq), dtype=np.int32) for i in range(n)]


def _worst_gap(got, want):
    """Widest gap between the program's norm of a leaf and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    floor = statistics.median(want.values())
    gaps = {n: abs(got[n] - want[n]) / max(want[n], floor) for n in want}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def compare(seen, ref):
    """The numbers `correct` is decided on, program against reference."""
    out = []
    for i, (a, b) in enumerate(zip(seen["losses"], ref["losses"]), start=1):
        out.append({"name": f"loss{i}_rel", "value": abs(a - b) / abs(b)})
    g, g_leaf = _worst_gap(seen["grad_norm"], ref["grad_norm"])
    d, d_leaf = _worst_gap(seen["delta_norm"], ref["delta_norm"])
    out.append({"name": "grad_norm_gap", "value": g, "leaf": g_leaf})
    out.append({"name": "delta_norm_gap", "value": d, "leaf": d_leaf})
    return out


def _install_mesh(mesh):
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": int(mesh.get("dp", 1)), "mp_degree": int(mesh.get("mp", 1)),
        "pp_degree": 1, "sharding_degree": int(mesh.get("sharding", 1))}
    fleet.init(is_collective=True, strategy=strategy)


def build(ctx):
    """The program's step object on the seed's weights."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.train import JittedTrainStep

    cfg, fam = ctx.config, ctx.family
    tr = cfg["train"]
    mesh = tr.get("mesh")
    step_kw = {}
    if mesh:
        _install_mesh(mesh)
        if int(mesh.get("sharding", 1)) > 1:
            # ZeRO's sharding group is a data-parallel group: the batch
            # splits over the axis the optimizer state is sharded over
            step_kw = {"state_sharding_axis": "sharding",
                       "input_batch_axes": ("sharding",)}
    model = fam.build_model(
        cfg, tensor_parallel=bool(mesh and int(mesh.get("mp", 1)) > 1))
    table, params = fam.install_weights(model, cfg, ctx.seed)
    opt = paddle.optimizer.AdamW(
        tr["lr"], beta1=tr["beta1"], beta2=tr["beta2"], epsilon=tr["eps"],
        parameters=model.parameters(), weight_decay=tr["weight_decay"],
        multi_precision=True, moment_dtype=tr["moment_dtype"])
    step = JittedTrainStep(model, fam.criterion(cfg, model), opt, **step_kw)
    return step, opt, table, params


def _state(step, opt, params, key):
    """The optimizer's ``key`` state of every parameter, through the
    program's public save path (sync_to_model + state_dict). A float32
    parameter has no master copy: it is its own."""
    step.sync_to_model()
    sd = opt.state_dict()
    return [sd[f"{p.name}_{key}"]._value
            if key != "master" or f"{p.name}_master" in sd else p._value
            for p in params]


def train_window(ctx, host):
    """Build the step, drive its first steps, run the window. Everything
    that holds the program's memory lives in this frame and dies with it;
    what comes back is plain data."""
    import paddle_tpu as paddle

    from benchmark.harness import probe, weights

    cfg, tf, fam = ctx.config, ctx.traffic, ctx.family
    tr = cfg["train"]
    n_check = int(tf["check_steps"])
    step, opt, table, params = build(ctx)
    names = [n for n, _, _ in table]
    feed = [paddle.to_tensor(b) for b in host]

    def dispatch(i):
        ids = feed[i % len(feed)]
        with ctx.spans.span("dispatch"):
            return step.run_steps(ids, ids)._value

    def wait(losses):
        with ctx.spans.span("wait") as row:
            losses.block_until_ready()
        return row[2]

    # ---- set-up: the first steps, through the window's own call and feed
    seen = {"losses": []}
    for i in range(n_check):
        first = dispatch(i)
        wait(first)
        seen["losses"].append(float(np.asarray(first)[0]))
        if i == 0:
            m1 = weights.norms(_state(step, opt, params, "moment1"))
            seen["grad_norm"] = {n: v / (1.0 - tr["beta1"])
                                 for n, v in zip(names, m1)}
    seen["delta_norm"] = dict(zip(names, weights.delta_norms(
        table, ctx.seed, fam.DTYPES[cfg["torch_dtype"]],
        _state(step, opt, params, "master"))))
    ctx.log({"first_steps": seen["losses"], "compile": ctx.meter.snapshot()})
    ctx.spans.rows.clear()

    # ---- the window
    before = ctx.meter.snapshot()
    if ctx.trace:
        ctx.start_trace()
    # as a training loop that reads its loss a step late does, the next
    # dispatch is issued before the last one is waited for: one in flight
    losses, done_at = [], []
    t_begin = time.perf_counter()
    ctx.setup_done(t_begin)
    with ctx.spans.span("window"):
        losses.append(dispatch(n_check))
        while True:
            if ctx.trace:
                more = len(losses) < int(tf["traced_dispatches"])
            else:
                more = time.perf_counter() - t_begin < ctx.seconds
            if more:
                losses.append(dispatch(n_check + len(losses)))
            done_at.append(wait(losses[len(done_at)]))
            if len(done_at) == len(losses):
                break
    t_end = time.perf_counter()
    traced = ctx.stop_trace() if ctx.trace else None
    after = ctx.meter.snapshot()
    peak, _ = probe.memory(ctx.devices)
    values = np.concatenate([np.asarray(x).reshape(-1) for x in losses])
    if tr.get("mesh"):
        from paddle_tpu.parallel import mesh as mesh_state

        mesh_state.set_mesh(None)
    return {"seen": seen, "window_s": t_end - t_begin, "losses": values,
            "dispatch_seconds": np.diff([t_begin] + done_at).tolist(),
            "peak": peak, "traced": traced,
            "compile_requests": after["requests"] - before["requests"]}


def run(ctx):
    from benchmark.harness import probe

    cfg, tf, fam = ctx.config, ctx.traffic, ctx.family
    tr = cfg["train"]
    batch, seq, k = int(tf["batch"]), int(tf["seq"]), int(tf["steps_per_dispatch"])
    if k != 1:
        raise NotImplementedError(
            "steps_per_dispatch > 1: the check reads the optimizer's state "
            "after ONE step, which a fused dispatch does not expose")
    n_check = int(tf["check_steps"])
    host = make_batches(ctx.seed, max(int(tf["batches"]), n_check), batch,
                        seq, int(cfg["vocab_size"]))
    w = train_window(ctx, host)
    seen, window_s = w["seen"], w["window_s"]
    failed = int((~np.isfinite(w["losses"])).sum())
    per_dispatch = w["dispatch_seconds"]  # one completion to the next
    ctx.log({"window": {
        "seconds": window_s, "dispatches": len(per_dispatch),
        "dispatch_min_med_max": [min(per_dispatch),
                                 statistics.median(per_dispatch),
                                 max(per_dispatch)],
        "last_loss": float(w["losses"][-1]),
        "compile_requests": w["compile_requests"]}})

    # ---- the program is freed; the reference follows the first steps
    probe.release()
    t0 = time.perf_counter()
    ctx.log({"freed": {"bytes_in_use": probe.memory(ctx.devices)[1]}})
    hp = {x: tr[x] for x in ("lr", "beta1", "beta2", "eps", "weight_decay")}
    get_leaf = fam.leaf_reader(cfg, ctx.seed)
    first = [b[0] for b in host[:n_check]]
    ref = fam.reference.train_steps(cfg, get_leaf, first, hp, ctx.devices)
    compared = compare(seen, ref)
    control = None
    if ctx.control:
        # the reference in the program's place, one precision lower
        control = compare(fam.reference.train_steps(
            cfg, get_leaf, first, hp, ctx.devices, control=True), ref)
    ctx.log({"check_seconds": time.perf_counter() - t0})

    n_chips = len(ctx.devices)
    tokens_per_dispatch = batch * seq * k
    return {
        "attempted": len(per_dispatch), "failed": failed,
        "end_to_end": {
            "train_tok_s_chip": tokens_per_dispatch * len(per_dispatch)
            / window_s / n_chips},
        "compared": compared, "control": control,
        "memory_peak_bytes": w["peak"],
        "observations": {
            "window_s": window_s, "dispatches": len(per_dispatch),
            "dispatch_seconds": per_dispatch, "steps_per_dispatch": k,
            "tokens_per_dispatch": tokens_per_dispatch, "chips": n_chips,
            "batch": batch, "seq": seq,
            "compile_requests": w["compile_requests"],
            "memory_peak_bytes": w["peak"], "trace": w["traced"]},
    }
