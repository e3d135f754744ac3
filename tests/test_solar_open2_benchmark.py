"""The benchmark's seventh family (``solar_open2``: three layers in four a
Kimi Delta Attention mixer with a matrix state a head, the fourth a gated GQA
without positions, routed experts of which a chip holds its share) rehearsed
on the CPU, and its hand counts.

The rehearsal is the whole of a run but the look for a chip and the
profiler's trace: ``benchmark/run.py::run_cell(..., tracing=False)`` on the
toy files ``benchmark/configs/toy-kda-moe.json``, ``benchmark/cells/
toy.kda-moe.json`` and the toy traffic, with an index built here that gives
the toy cell every per-layer metric of the real cell (``rehearsal.json`` and
``selfcheck.py`` are not edited). The hand counts are those of PERF.md
section 3 and of ISSUE 46's table, to the parameter.
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
import selfcheck  # noqa: E402

from benchmark.harness import counts_solar_open2 as counts  # noqa: E402

SEED = 2147483777
CELL = "solar-open2-250b.chat1k-o256"
CONFIG = "solar-open2-250b-l4-ep8"
TOY = "toy.kda-moe"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"solaropen2_state_bytes_per_slot",
               "solaropen2_held_load_max_over_mean"}
TRACE_READERS = {"solaropen2_decode_hbm_bw_pct", "solaropen2_mixed_mfu_pct",
                 "solaropen2_paged_decode_attention_roofline",
                 "kda_decode_update_roofline"}
SHARED_READERS = {
    "slot_occupancy_pct", "batch_tok_s", "first_token_ms", "mixed_step_ms",
    "compiles_per_mixed_step", "decode_quantum_ms", "kv_blocks_peak_pct",
    "cache_bytes_per_token", "serve_device_idle_pct", "serve_hbm_peak_gib",
    "queue_wait_ms", "mixed_forward_ms", "mixed_trace_lower_ms",
    "quantum_host_ms", "quantum_args_ms", "compiles_in_decode",
    "quanta_ahead_pct",
    "mixed_host_ms"}
KDA, GQA, EXPERT = 137_732_288, 109_051_904, 15_728_640      # ISSUE 46's


@pytest.fixture(scope="module")
def run():
    return selfcheck.load_run()


@pytest.fixture(scope="module")
def real(run):
    return run.load_json("BENCHMARK.json")


@pytest.fixture(scope="module")
def cfg(run):
    return run.load_json("benchmark", "configs", CONFIG + ".json")


@pytest.fixture(scope="module")
def index(real):
    """One toy cell of the new family with every per-layer metric the real
    cell reports."""
    return {
        "configs": [{"name": "toy-kda-moe",
                     "file": "benchmark/configs/toy-kda-moe.json"}],
        "workloads": [{"name": TOY, "config": "toy-kda-moe",
                       "traffic": "toy-batches", "chips": 1}],
        "end_to_end": [],
        "per_layer": [dict(m, workloads=[TOY]) for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_family(run, index, trace, monkeypatch):
    """Trace 0 and 1: `correct` true, the int8 control not correct, and in
    the traced run every new reader called (those that read the device
    trace find none on the CPU and say nothing; the others give a
    number)."""
    called = []
    real_load = run.load_by_name

    def load(folder, name):
        mod = real_load(folder, name)
        if folder == "metrics":
            called.append(name)
        return mod

    monkeypatch.setattr(run, "load_by_name", load)
    out = selfcheck.rehearse_cell(run, index, TOY, SEED, trace=trace,
                                  control=1)
    assert out["correct"] is True and out["control_correct"] is False
    assert out["failed"] == 0 and out["attempted"] >= 4
    if trace:
        assert NEW_READERS | TRACE_READERS | SHARED_READERS <= set(called)
        assert NEW_READERS | {"cache_bytes_per_token"} \
            <= set(out["metrics_read"])
        assert not TRACE_READERS & set(out["metrics_read"])


def _span(step_id, name, **args):
    return {"name": name, "ph": "X", "ts": 0, "dur": 1,
            "args": dict(args, id=step_id)}


def test_new_readers_on_a_hand_made_observation(run, cfg, monkeypatch):
    """The device readers on numbers a hand can check, and every new reader
    silent (None, no raise) on a configuration of another family and on a
    program without the spans or the kernel."""
    from benchmark.harness import program_spans

    obs = {"config": cfg, "batches": 1, "batch": 96, "prompt_len": 1024,
           "new_tokens": 128, "pool": {},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "engine_steps": {"prefill_tokens": 98304, "mixed_steps": 8,
                            "decode_quanta": 16},
           "trace": {"module_seconds": {"jit_mixed": 3.0,
                                        "jit_quantum": 2.0},
                     "op_seconds": {
                         "jit_quantum/paged_decode_attention": 0.1,
                         "jit_quantum/kda_decode_update": 0.3,
                         "jit_quantum/fusion": 1.0}}}
    # 8 mixed steps of 96 x 128 valid positions whose held experts got
    # exactly one of the 8 choices a token and layer; 16 quanta of 8 steps
    # in which 36 of each of 4 layers' 40 held experts got a row, the
    # fullest 6 of a mean of 96 x 8 / 320 = 2.4
    events = [_span(i, "engine.mixed", rows=96, prefill_tokens=12288,
                    bucket=128, padded_tokens=0, moe_rows=12288 * 1 * 4)
              for i in range(8)]
    for i in range(16):
        events += [_span(100 + 2 * i, "engine.decode", half="dispatch"),
                   _span(101 + 2 * i, "engine.decode", half="collect",
                         moe_rows=8 * 4 * 96, moe_experts_touched=8 * 144,
                         moe_rows_max=8 * 4 * 6, moe_layer_steps=32,
                         moe_offshare_rows=8 * 4 * 672)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(events))
    fixed = 3 * 137_625_600 + 3 * 4096 * 8192 + 2 * 4096 * 1024 \
        + 4 * (4096 * 320 + 3 * 4096 * 1280)
    assert fixed == counts.fixed_matmul_params_per_token(cfg) == 590_086_144
    mfu = run.load_by_name("metrics", "solaropen2_mixed_mfu_pct").read(obs)
    flops = (2 * fixed * 98304 + 2 * EXPERT * 98304 * 4
             + 7 * 3 * 64 * 128 * 128 * 98304
             + 4 * 64 * 128 * 524_800 * 96)
    assert mfu == pytest.approx(100 * flops / 3.0 / 197e12)
    assert 20 < mfu < 30
    bw = run.load_by_name("metrics", "solaropen2_decode_hbm_bw_pct").read(obs)
    nbytes = (127 * counts.fixed_weight_bytes_per_step(cfg)
              + 16 * 8 * 144 * 2 * EXPERT
              + 127 * 96 * 2 * 13_025_280
              + sum(range(1025, 1152)) * 96 * 4096)
    assert bw == pytest.approx(100 * nbytes / 2.0 / 819e9)
    assert 60 < bw < 75
    # the GQA layer's keys and values of contexts 1025..1151, read once a
    # step and stream: memory-bound
    roof = run.load_by_name(
        "metrics", "solaropen2_paged_decode_attention_roofline").read(obs)
    assert roof == pytest.approx(
        100 * sum(range(1025, 1152)) * 96 * 4096 / 819e9 / 0.1)
    assert 60 < roof < 72
    # the kernel: 127 steps x 96 slots x 3 layers, the 4 MB state once
    # each way and the head's five vectors; memory-bound
    kda = run.load_by_name("metrics", "kda_decode_update_roofline").read(obs)
    each = 127 * 96 * 3
    assert kda == pytest.approx(100 * each * (
        2 * 4_194_304 + 64 * (5 * 128 + 1) * 4) / 819e9 / 0.3)
    assert 120 < kda < 135   # the hand-made 0.3 s is under the least time
    for name in ("solaropen2_paged_decode_attention_roofline",
                 "kda_decode_update_roofline"):
        assert run.load_by_name("metrics", name).read(
            dict(obs, trace=dict(obs["trace"], op_seconds={}))) is None
    load = run.load_by_name(
        "metrics", "solaropen2_held_load_max_over_mean").read(obs)
    assert load == pytest.approx(6 * 40 / 96) == 2.5
    # another family, and a program without the spans: silent
    monkeypatch.setattr(program_spans, "rows", lambda: [])
    granite = run.load_json("benchmark", "configs",
                            "granite-4.0-h-small-l10-ep2.json")
    for name in NEW_READERS | TRACE_READERS:
        reader = run.load_by_name("metrics", name)
        assert reader.read(dict(obs, config=granite)) is None
        if name in ("solaropen2_mixed_mfu_pct",
                    "solaropen2_decode_hbm_bw_pct",
                    "solaropen2_held_load_max_over_mean"):
            assert reader.read(obs) is None    # these read the spans
    # and the siblings' readers are silent on this family's configuration
    for name in ("state_bytes_per_slot", "moe_held_load_max_over_mean",
                 "hybrid_decode_hbm_bw_pct", "nemotronh_mixed_mfu_pct",
                 "falconh1_state_bytes_per_slot"):
        assert run.load_by_name("metrics", name).read(obs) is None


def test_the_state_gauge_has_to_match_the_shapes(run, cfg):
    """``solaropen2_state_bytes_per_slot`` reads the program's gauge and
    raises when it differs from what the configuration's shapes give."""
    from paddle_tpu.obs.registry import MetricsRegistry

    gauge = MetricsRegistry.process().gauge(
        "serving_state_bytes_per_slot", "")
    reader = run.load_by_name("metrics", "solaropen2_state_bytes_per_slot")
    gauge.set(13_025_280.0, pool="target")
    assert reader.read({"config": cfg, "pool": {}}) == 13_025_280
    gauge.set(13_025_280.0 + 4, pool="target")
    with pytest.raises(RuntimeError, match="bytes of state"):
        reader.read({"config": cfg, "pool": {}})
    assert reader.read({"config": cfg}) is None


def test_the_cell_and_its_files(run, real, cfg):
    """The cell's files against ISSUE 46's parameters."""
    cell, entry, config, traffic, limits = run.resolve(real, CELL)
    assert config == cfg and cell["chips"] == 1
    assert cell["config"] == CONFIG
    assert entry["reduced"] == ["num_hidden_layers", "gqa_layers",
                                "n_routed_experts", "vocab_size"]
    assert set(cfg["reduced_from"]) == set(entry["reduced"])
    assert traffic == dict(traffic, kind="closed_batches", prompt_len=1024,
                           new_tokens=256, margin=1.25, check_requests=16,
                           traced_batches=1)
    # 128 rows, or the issue's one fallback (96: PERF.md section 6)
    assert traffic["batch"] in (128, 96)
    assert cfg["engine"] == {
        "num_slots": traffic["batch"], "block_size": 32,
        "num_blocks": traffic["batch"] * 40 + 1, "max_context": 1280,
        "prefill_chunk": 128, "decode_quantum": 8,
        "decode_strategy": "greedy"}
    assert cfg["family"] == "solar_open2"
    assert cfg["torch_dtype"] == "bfloat16"
    assert (cfg["n_routed_experts"], cfg["published_experts"],
            cfg["held_experts"]) == (40, 320, [0, 40])
    assert cfg["reduced_from"] == {
        "num_hidden_layers": 48, "gqa_layers": list(range(0, 48, 4)),
        "n_routed_experts": 320, "vocab_size": 196608}
    assert cfg["deployment"]["chips"] == 8
    assert (cfg["num_hidden_layers"], cfg["gqa_layers"],
            cfg["vocab_size"]) == (4, [0], 24576)
    # every published width unchanged
    assert (cfg["hidden_size"], cfg["linear_attn_config"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"]) == (
        4096, {"short_conv_kernel_size": 4, "head_dim": 128,
               "num_heads": 64, "num_kv_heads": None}, 64, 8, 128, 1280, 8,
        1)
    # the state REMEMBERS: as drawn (A_log and dt_bias near 0) it forgets
    # within ~10 tokens and ``correct`` sees no carry between chunks; the
    # selection bias is small, so the experts a step touches do not follow
    # the seed
    assert cfg["seeded_leaf_scale_log2"] == {"A_log": 7, "dt_bias": 5,
                                             "router_b": -3}
    assert cfg["seeded_leaf_offset"] == {"dt_bias": -3}
    assert any(a.startswith("seeded_leaf_scale_log2") for a in cfg["assumed"])
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"Solar-Open2-250B"' in line)
        assert entry["source"] == row["source_url"] == cfg["source"]
        changed = {k for k, v in row["config"].items() if cfg[k] != v}
        assert changed == set(entry["reduced"])
        assert cfg["gqa_layers"] == row["config"]["gqa_layers"][:1]
    assert set(limits["limits"]) == {"gap_max", "gap_mean"}
    names = {m["name"] for m in run.metrics_of(real, cell, "per_layer")}
    assert names == NEW_READERS | TRACE_READERS | SHARED_READERS
    assert {m["name"] for m in run.metrics_of(real, cell, "end_to_end")} \
        == {"out_tok_s", "gap_p95_ms", "setup_s"}
    # the new entries were appended, and the cell joined its lists at the
    # end (what later PRs append follows them)
    assert [c["name"] for c in real["configs"]].index(CONFIG) == 8
    assert [w["name"] for w in real["workloads"]].index(CELL) == 8
    later = {w["name"] for w in real["workloads"][9:]}
    joined = [m for m in real["per_layer"] + real["end_to_end"]
              if CELL in m.get("workloads", ())]
    assert len(joined) == 2 + 17 + 6 + 1    # PR 48: quanta_ahead_pct
    assert all(set(m["workloads"][m["workloads"].index(CELL) + 1:]) <= later
               for m in joined)
    # the names say what the files hold
    assert CONFIG.endswith(f"-l{cfg['num_hidden_layers']}-ep8")
    assert cell["traffic"] == (f"batch{traffic['batch']}-p"
                               f"{traffic['prompt_len']}-o"
                               f"{traffic['new_tokens']}")
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_hand_counts(cfg):
    """ISSUE 46's table, to the parameter, and PERF.md section 3's hand
    counts for the cut."""
    # q, k, v, o 4 x 4096 x 8192; two rank-128 pairs; beta 4096 x 64
    assert counts.kda_matmul_params(cfg) == (
        134_217_728 + 2 * (524_288 + 1_048_576) + 262_144) == 137_625_600
    # + three convolutions 8192 x 4, A_log 64, dt_bias 8192, o_norm 128
    assert counts.kda_params(cfg) == 137_625_600 + 98_304 + 64 + 8192 + 128 \
        == KDA
    # q, o, gate 4096 x 8192; k, v 4096 x 1024
    assert counts.attention_params(cfg) == GQA
    assert counts.expert_params(cfg) == 3 * 4096 * 1280 == EXPERT
    # router 4096 x 320 (+ bias 320), the shared expert one expert wide
    assert counts.expert_layer_matmul_params(cfg) == 1_310_720 + EXPERT
    assert counts.expert_layer_fixed_params(cfg) == 1_310_720 + 320 + EXPERT
    # the table: 3 KDA mixers, 1 gated GQA, 4 x (40 held + shared +
    # router), embedding + head of 24,576 rows; the table leaves out the
    # norms (4 x 2 x 4096 + 4096) and the selection biases (4 x 320)
    table = (3 * KDA + GQA + 4 * (40 * EXPERT + EXPERT + 1_310_720)
             + 2 * 24576 * 4096)
    assert table == 413_196_864 + 109_051_904 + 2_584_739_840 + 201_326_592
    assert counts.total_params(cfg) == table + 9 * 4096 + 4 * 320 \
        == 3_308_353_344                                    # 6.62 GB
    whole = dict(cfg, num_hidden_layers=48, n_routed_experts=320,
                 gqa_layers=list(range(0, 48, 4)), vocab_size=196608)
    assert counts.total_params(whole) == (
        36 * KDA + 12 * GQA + 48 * (320 * EXPERT + EXPERT + 1_310_720 + 320
                                    + 8192)
        + 2 * 196608 * 4096 + 4096) == 250_287_810_304
    assert counts.fixed_matmul_params_per_token(cfg) == 590_086_144
    # with one of a token's 8 experts held, a layer: 1.30 G operations a
    # token beside the recurrence's 22 M
    assert 2 * (590_086_144 + 4 * EXPERT) == 1_306_001_408
    assert counts.recurrence_flops_per_token(cfg) == 7 * 3 * 64 * 128 * 128 \
        == 22_020_096
    # the slot: 3 x (64 x 128 x 128 x 4 + 3 tails x 3 x 8192 x 2)
    assert counts.matrix_state_bytes(cfg) == 4_194_304
    assert counts.state_bytes_per_slot(cfg) == 3 * (4_194_304 + 147_456) \
        == 13_025_280
    # the one GQA layer: K and V of 8 x 128 in bf16
    assert counts.cache_bytes_per_token(cfg) == 4_096
    # a decode step's weights outside the routed experts: 1.30 GB
    assert counts.fixed_weight_bytes_per_step(cfg) == 2 * (
        3 * KDA + GQA + 4 * (1_310_720 + 320 + EXPERT + 8192) + 4096
        + 24576 * 4096) == 1_382_215_296
    # a closed batch's decode at 128 rows: 255 steps, 38.4 of 40 held
    # experts touched a layer and step, 128 slots' state read and written,
    # the keys of 1025..1279: the issue's ~10.1 GB a step
    touched = round(255 * 4 * 38.4)
    step = counts.decode_bytes_needed(cfg, touched, 1, 128, 1024, 256) / 255
    assert counts.decode_bytes_needed(cfg, touched, 1, 128, 1024, 256) == (
        255 * 1_382_215_296 + touched * 2 * EXPERT
        + 255 * 128 * 2 * 13_025_280 + sum(range(1025, 1280)) * 128 * 4096)
    assert 10.0e9 < step < 10.3e9
    # the decode kernel: the GQA layer's keys of 1025..1279, 64 query heads
    assert counts.paged_attention_needs(cfg, 128, 1024, 256) == (
        4 * 64 * 128 * sum(range(1025, 1280)) * 128,
        sum(range(1025, 1280)) * 128 * 4096)
    # the one-step delta rule: 255 steps x 128 slots x 3 layers
    flops, nbytes = counts.kda_update_needs(cfg, 128, 256)
    assert flops == 255 * 128 * 3 * 7 * 64 * 128 * 128
    assert nbytes == 255 * 128 * 3 * (2 * 4_194_304 + 64 * 641 * 4)
    # the pool: 128 requests x ceil(1279 / 32) blocks + the scratch
    assert 128 * 40 + 1 == 5121


def test_the_leaf_table_is_the_programs_parameters(run, cfg):
    """Every leaf of the reference's table has the program's path and
    shape, and no other (from shapes: nothing of the cut is allocated)."""
    import jax

    import paddle_tpu as paddle

    fam = run.load_by_name("families", "solar_open2")
    table = fam.reference.leaf_table(cfg)
    assert sum(1 for n, _, _ in table if n.endswith(".e_gate_up")) == 4
    assert [n for n, _, _ in table if n.endswith(".A_log")] == [
        "L1.A_log", "L2.A_log", "L3.A_log"]
    assert [n for n, _, _ in table if n.endswith(".g_w")] == ["L0.g_w"]
    dtype_was = paddle.get_default_dtype()  # build_model sets the cell's
    try:
        shapes = jax.eval_shape(lambda: {
            k: p._value for k, p in fam.build_model(cfg).named_parameters()})
    finally:    # ... and a later test of this worker would inherit bfloat16
        paddle.set_default_dtype(dtype_was)
    assert {fam.program_path(n): tuple(s) for n, s, _ in table} \
        == {k: tuple(v.shape) for k, v in shapes.items()}
    assert sum(int(np.prod(s)) for _, s, _ in table) \
        == counts.total_params(cfg)


def test_per_leaf_install_equals_fill(run):
    """``build_model`` draws nothing (every leaf zeros) and
    ``install_weights`` draws leaf by leaf through ``weights.leaf_reader``
    (the two vocabulary leaves first): every parameter is bit for bit what
    ``weights.fill`` gives for the same seed, times its scale plus its
    offset, and the decay they give is the slow one the cell's
    configuration is read at (g spread from about -4 to -0.0005 a token over
    the heads)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from benchmark.harness import weights

    fam = run.load_by_name("families", "solar_open2")
    toy = run.load_json("benchmark", "configs", "toy-kda-moe.json")
    dtype_was = paddle.get_default_dtype()
    try:
        model = fam.build_model(toy)
    finally:
        paddle.set_default_dtype(dtype_was)
    assert not any(float(abs(p._value).max())
                   for _, p in model.named_parameters())
    table, params = fam.install_weights(model, toy, SEED)
    assert [n for n, _, _ in table] == [
        n for n, _, _ in fam.reference.leaf_table(toy)]
    want = weights.fill(table, SEED, jnp.float32,
                        [jnp.zeros(s, jnp.float32) for _, s, _ in table])
    scale, offset = fam.leaf_scale(toy), fam.leaf_offset(toy)
    assert (scale("L1.A_log"), scale("L2.dt_bias"), offset("L3.dt_bias"),
            offset("L1.A_log"), scale("L1.k_w"), scale("L0.router_b")) == (
        128.0, 32.0, -3.0, 0.0, 1.0, 0.125)
    for (name, _, _), p, w in zip(table, params, want):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(p._value)),
            np.asarray(jax.device_get(w)) * scale(name) + offset(name),
            err_msg=name)
    leaf = dict(zip((n for n, _, _ in table),
                    (np.asarray(jax.device_get(p._value)) for p in params)))
    # softplus(dt_bias) in 0.018..0.127 times exp(A_log) in 0.02..50 a
    # head: fast heads and slow ones
    assert -4.0 <= leaf["L1.dt_bias"].min() < leaf["L1.dt_bias"].max() <= -2
    assert -4.0 <= leaf["L1.A_log"].min() < leaf["L1.A_log"].max() <= 4.0
    get_leaf = fam.leaf_reader(toy, SEED)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(get_leaf("L1.k_w"))),
        np.asarray(jax.device_get(params[[n for n, _, _ in table].index(
            "L1.k_w")]._value)))
