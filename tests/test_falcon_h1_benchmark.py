"""The benchmark's sixth family (``falcon_h1``: every layer a Mamba-2 mixer
AND rotary grouped-query attention on one normed input, muP multipliers, a
dense SwiGLU MLP) rehearsed on the CPU, and its hand counts.

The rehearsal is the whole of a run but the look for a chip and the
profiler's trace: ``benchmark/run.py::run_cell(..., tracing=False)`` on the
toy files ``benchmark/configs/toy-parallel-ssm.json``, ``benchmark/cells/
toy.parallel-ssm.json`` and the toy traffic, with an index built here that
gives the toy cell every per-layer metric of the real cell (``rehearsal.json``
and ``selfcheck.py`` are not edited). The hand counts are ISSUE 41's.
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

from benchmark.harness import counts_falcon_h1 as counts  # noqa: E402

SEED = 2147483777
CELL = "falcon-h1-34b.chat1k-o256"
CONFIG = "falcon-h1-34b-l6"
TOY = "toy.parallel-ssm"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"falconh1_state_bytes_per_slot"}
TRACE_READERS = {"falconh1_decode_hbm_bw_pct", "falconh1_mixed_mfu_pct",
                 "falconh1_paged_decode_attention_roofline"}
SHARED_READERS = {
    "slot_occupancy_pct", "batch_tok_s", "first_token_ms", "mixed_step_ms",
    "compiles_per_mixed_step", "decode_quantum_ms", "kv_blocks_peak_pct",
    "cache_bytes_per_token", "serve_device_idle_pct", "serve_hbm_peak_gib",
    "queue_wait_ms", "mixed_forward_ms", "mixed_trace_lower_ms",
    "quantum_host_ms", "quantum_args_ms", "compiles_in_decode",
    "quanta_ahead_pct",
    "mixed_host_ms"}


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
        "configs": [{"name": "toy-parallel-ssm",
                     "file": "benchmark/configs/toy-parallel-ssm.json"}],
        "workloads": [{"name": TOY, "config": "toy-parallel-ssm",
                       "traffic": "toy-batches", "chips": 1}],
        "end_to_end": [],
        "per_layer": [dict(m, workloads=[TOY]) for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_family(run, index, trace, monkeypatch):
    """Trace 0 and 1: `correct` true, the int8 control not correct, and in
    the traced run every new reader called (those that read the device
    trace find none on the CPU and say nothing; the others give a number).
    Three layers, each on both sides of the pool."""
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
    """The three device readers on numbers a hand can check, and every new
    reader silent (None, no raise) on a configuration of another family
    and on a program without the spans."""
    from benchmark.harness import program_spans

    obs = {"config": cfg, "batches": 1, "batch": 64, "prompt_len": 1024,
           "new_tokens": 128, "pool": {},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "engine_steps": {"prefill_tokens": 65536, "mixed_steps": 8,
                            "decode_quanta": 16},
           "trace": {"module_seconds": {"jit_mixed": 4.0,
                                        "jit_quantum": 2.5},
                     "op_seconds": {
                         "jit_quantum/paged_decode_attention": 0.2,
                         "jit_quantum/fusion": 1.0}}}
    # 8 mixed steps of 64 x 128 valid positions
    events = [_span(i, "engine.mixed", rows=64, prefill_tokens=8192,
                    bucket=128, padded_tokens=0) for i in range(8)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(events))
    mfu = run.load_by_name("metrics", "falconh1_mixed_mfu_pct").read(obs)
    flops = ((2 * 2_580_480_000 + 31_457_280) * 65536
             + 4 * 20 * 128 * 524_800 * 64 * 6)
    assert mfu == pytest.approx(100 * flops / 4.0 / 197e12)
    assert 40 < mfu < 46
    bw = run.load_by_name("metrics", "falconh1_decode_hbm_bw_pct").read(obs)
    nbytes = (127 * 7_835_319_424 + 127 * 64 * 2 * 25_350_144
              + sum(range(1025, 1152)) * 64 * 12_288)
    assert bw == pytest.approx(100 * nbytes / 2.5 / 819e9)
    assert 70 < bw < 75
    # all six layers' keys and values of contexts 1025..1151, read once a
    # step and stream: memory-bound (its operations take 0.6 ms)
    roof = run.load_by_name(
        "metrics", "falconh1_paged_decode_attention_roofline").read(obs)
    assert roof == pytest.approx(
        100 * sum(range(1025, 1152)) * 64 * 12_288 / 819e9 / 0.2)
    assert 65 < roof < 70
    assert run.load_by_name(
        "metrics", "falconh1_paged_decode_attention_roofline").read(
            dict(obs, trace=dict(obs["trace"], op_seconds={}))) is None
    # padded positions bring no token
    half = [_span(i, "engine.mixed", rows=64, prefill_tokens=4096, bucket=128,
                  padded_tokens=4096) for i in range(8)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(half))
    assert run.load_by_name("metrics", "falconh1_mixed_mfu_pct").read(
        obs) == pytest.approx(100 * (
            (2 * 2_580_480_000 + 31_457_280) * 32768
            + 4 * 20 * 128 * 524_800 * 64 * 6) / 4.0 / 197e12)
    # another family, and a program without the spans: silent
    monkeypatch.setattr(program_spans, "rows", lambda: [])
    granite = run.load_json("benchmark", "configs",
                            "granite-4.0-h-small-l10-ep2.json")
    for name in NEW_READERS | TRACE_READERS:
        reader = run.load_by_name("metrics", name)
        assert reader.read(dict(obs, config=granite)) is None
    assert run.load_by_name("metrics", "falconh1_mixed_mfu_pct").read(
        obs) is None                                 # it reads the spans
    # and the siblings' readers are silent on this family's configuration
    for name in ("state_bytes_per_slot", "nemotronh_state_bytes_per_slot",
                 "hybrid_decode_hbm_bw_pct", "hybrid_mixed_mfu_pct",
                 "nemotronh_decode_hbm_bw_pct", "nemotronh_mixed_mfu_pct",
                 "nemotronh_paged_decode_attention_roofline"):
        assert run.load_by_name("metrics", name).read(obs) is None


def test_the_state_gauge_has_to_match_the_shapes(run, cfg):
    """``falconh1_state_bytes_per_slot`` reads the program's gauge and
    raises when it differs from what the configuration's shapes give."""
    from paddle_tpu.obs.registry import MetricsRegistry

    gauge = MetricsRegistry.process().gauge(
        "serving_state_bytes_per_slot", "")
    reader = run.load_by_name("metrics", "falconh1_state_bytes_per_slot")
    gauge.set(25_350_144.0, pool="target")
    assert reader.read({"config": cfg, "pool": {}}) == 25_350_144
    gauge.set(25_350_144.0 + 4, pool="target")
    with pytest.raises(RuntimeError, match="bytes of state"):
        reader.read({"config": cfg, "pool": {}})
    assert reader.read({"config": cfg}) is None


def test_the_cell_and_its_files(run, real, cfg):
    """The cell's files against ISSUE 41's parameters."""
    cell, entry, config, traffic, limits = run.resolve(real, CELL)
    assert config == cfg and cell["chips"] == 1
    assert cell["config"] == CONFIG and cell["traffic"] == "batch64-p1024-o256"
    assert entry["reduced"] == ["num_hidden_layers"]
    assert set(cfg["reduced_from"]) == set(entry["reduced"])
    assert traffic == dict(traffic, kind="closed_batches", batch=64,
                           prompt_len=1024, new_tokens=256, margin=1.25,
                           check_requests=16, traced_batches=1)
    assert cfg["engine"] == {
        "num_slots": 64, "block_size": 32, "num_blocks": 2561,
        "max_context": 1280, "prefill_chunk": 128, "decode_quantum": 8,
        "decode_strategy": "greedy"}
    assert cfg["family"] == "falcon_h1"
    assert cfg["torch_dtype"] == "bfloat16"
    assert cfg["reduced_from"]["num_hidden_layers"] == 72
    assert cfg["num_hidden_layers"] == 6 >= 4
    assert cfg["mamba_chunk_size"] == cfg["engine"]["prefill_chunk"]
    assert cfg["deployment"]["chips"] == 1
    for key in ("why_reduced", "deployment", "assumed",
                "seeded_leaf_scale_log2"):
        assert cfg[key] and "TBD" not in json.dumps(cfg[key])
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"Falcon-H1-34B-Instruct"' in line)
        assert entry["source"] == row["source_url"] == cfg["source"]
        # every published key as given, the multipliers among them, but
        # the depth
        changed = {k for k, v in row["config"].items() if cfg[k] != v}
        assert changed == {"num_hidden_layers"}
    assert set(limits["limits"]) == {"gap_max", "gap_mean"}
    assert "TBD" not in json.dumps(limits)
    names = {m["name"] for m in run.metrics_of(real, cell, "per_layer")}
    assert names == NEW_READERS | TRACE_READERS | SHARED_READERS
    assert {m["name"] for m in run.metrics_of(real, cell, "end_to_end")} \
        == {"out_tok_s", "gap_p95_ms", "setup_s"}
    # the new entries were appended, and the cell joined its lists at the
    # end (what later PRs append follows them)
    assert [c["name"] for c in real["configs"]].index(CONFIG) == 7
    assert [w["name"] for w in real["workloads"]].index(CELL) == 7
    later = {w["name"] for w in real["workloads"][8:]}
    joined = [m for m in real["per_layer"] + real["end_to_end"]
              if CELL in m.get("workloads", ())]
    assert len(joined) == 2 + 17 + 4 + 1    # PR 48: quanta_ahead_pct
    assert all(set(m["workloads"][m["workloads"].index(CELL) + 1:]) <= later
               for m in joined)
    new = [m for m in real["per_layer"] if m["name"].startswith("falconh1_")]
    assert [(m["name"], m["source"], m["layer"], m["moves"]) for m in new] \
        == [("falconh1_mixed_mfu_pct", "device_trace", "engine step",
             "out_tok_s"),
            ("falconh1_decode_hbm_bw_pct", "device_trace", "device",
             "gap_p95_ms"),
            ("falconh1_state_bytes_per_slot", "program_counter", "KV cache",
             "out_tok_s"),
            ("falconh1_paged_decode_attention_roofline", "device_trace",
             "kernels", "gap_p95_ms")]
    assert all(m["workloads"][0] == CELL for m in new)
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200
    # the pool's peak: 64 requests x ceil(1279 / 32) blocks + the scratch
    assert 64 * 40 + 1 <= cfg["engine"]["num_blocks"]
    # the names say what the files hold
    assert CONFIG.endswith(f"-l{cfg['num_hidden_layers']}")
    assert cell["traffic"] == (f"batch{traffic['batch']}-p"
                               f"{traffic['prompt_len']}-o"
                               f"{traffic['new_tokens']}")
    assert CELL.endswith(f"chat1k-o{traffic['new_tokens']}")


def test_hand_counts(cfg):
    """ISSUE 41's arithmetic (per layer and for the whole cut) and PERF.md
    section 3's hand counts."""
    # q, o 5120 x 2560; k, v 5120 x 512
    assert counts.attention_params(cfg) == 31_457_280
    # in_proj 5120 x (4096 z + 4096 x + 512 B + 512 C + 32 dt)
    assert counts.conv_dim(cfg) == 4096 + 2 * 2 * 256 == 5120
    assert counts.mamba_matmul_params(cfg) == 47_349_760 + 20_971_520
    # + conv 5120 x 4 + bias 5120 + dt_bias, A_log, D 3 x 32 + norm 4096
    assert counts.mamba_params(cfg) == 68_351_072
    assert counts.mlp_params(cfg) == 3 * 5120 * 21504 == 330_301_440
    assert counts.layer_params(cfg) == 430_120_032          # 0.86 GB
    top = 2 * 261120 * 5120 + 5120
    assert top == 2_673_873_920
    assert counts.total_params(cfg) == 6 * 430_120_032 + top \
        == 5_254_594_112                                     # 10.51 GB
    assert counts.total_params(dict(cfg, num_hidden_layers=72)) \
        == 33_642_516_224
    # what a token multiplies: 2.58 G parameters, 5.16 G operations
    assert counts.matmul_params_per_token(cfg) == 6 * 430_080_000 \
        == 2_580_480_000
    # 5 operations a state element: 6 layers x 32 x 128 x 256
    assert counts.recurrence_flops_per_token(cfg) == 31_457_280
    assert counts.causal_pairs(1024) == 524_800
    batch = counts.prefill_flops(cfg, 65536, 64, 1024)
    assert batch == ((2 * 2_580_480_000 + 31_457_280) * 65536
                     + 4 * 20 * 128 * 524_800 * 64 * 6)
    # a mixed step of 8,192 positions: the issue's 42.3 TFLOP without the
    # recurrence and the pairs, 42.8 with them
    assert 42.2e12 < 2 * 2_580_480_000 * 8192 < 42.4e12
    assert 42.7e12 < batch / 8 < 42.9e12
    # the slot: 6 x (32 x 128 x 256 x 4 + 3 x 5120 x 2): 1.62 GB at 64
    assert counts.state_bytes_per_slot(cfg) == 6 * 4_225_024 == 25_350_144
    assert 1.62e9 < 64 * counts.state_bytes_per_slot(cfg) < 1.63e9
    # every layer: K and V of 4 x 128 in bf16; 2,561 blocks of 32: 1.01 GB
    assert counts.cache_bytes_per_token(cfg) == 12_288
    assert 1.00e9 < 2561 * 32 * counts.cache_bytes_per_token(cfg) < 1.01e9
    # a decode step's weights: 7.84 GB, the head 2.67 of them
    assert counts.weight_bytes_per_step(cfg) == 2 * (
        6 * 430_120_032 + 5120 + 261120 * 5120) == 7_835_319_424
    # a closed batch's decode: 255 steps, 64 slots' state read and
    # written, the keys of 1025..1279
    assert counts.decode_bytes_needed(cfg, 1, 64, 1024, 256) == (
        255 * 7_835_319_424 + 255 * 64 * 2 * 25_350_144
        + sum(range(1025, 1280)) * 64 * 12_288)
    # a step: 7.84 + 3.24 + ~0.91 = 12.0 GB, 14.6 ms at 819 GB/s
    step = counts.decode_bytes_needed(cfg, 1, 64, 1024, 256) / 255
    assert 11.9e9 < step < 12.1e9
    # the parallel mixer (state, keys, its own 1.2 GB of weights) 45 % of a
    # step's bytes, the MLP 33 %, the head 22 %
    mixer = (64 * 2 * 25_350_144 + 1152 * 64 * 12_288
             + 6 * 2 * (31_457_280 + 68_351_072))
    assert 0.44 < mixer / step < 0.46
    assert 0.32 < 6 * 2 * 330_301_440 / step < 0.34
    assert 0.22 < 2 * 261120 * 5120 / step < 0.23
    # the decode kernel: the keys of 1025..1279 in six layers, 20 query
    # heads x 128 over them twice (QK^T, PV)
    assert counts.paged_attention_needs(cfg, 64, 1024, 256) == (
        4 * 20 * 128 * sum(range(1025, 1280)) * 64 * 6,
        sum(range(1025, 1280)) * 64 * 12_288)


def test_the_leaf_table_is_the_programs_parameters(run, cfg):
    """Every leaf of the reference's table has the program's path and
    shape, and no other (from shapes: nothing of the cut is allocated);
    ``leaf_scale`` scales the leaves the configuration names, each by its
    power of two, and nothing else."""
    import jax

    fam = run.load_by_name("families", "falcon_h1")
    table = fam.reference.leaf_table(cfg)
    assert len(table) == 3 + 6 * 17
    import paddle_tpu as paddle

    dtype_was = paddle.get_default_dtype()  # build_model sets the cell's
    try:
        shapes = jax.eval_shape(lambda: {
            k: p._value for k, p in fam.build_model(cfg).named_parameters()})
    finally:    # ... and a later test of this worker would inherit bfloat16
        paddle.set_default_dtype(dtype_was)
    assert {fam.program_path(n): tuple(s) for n, s, _ in table} \
        == {k: tuple(v.shape) for k, v in shapes.items()}
    assert fam.program_path("L3.k_w") \
        == "model.layers.3.self_attn.k_proj.weight"
    assert fam.program_path("L0.in_w") == "model.layers.0.mamba.in_proj.weight"
    scale = fam.leaf_scale(cfg)
    scaled = cfg["seeded_leaf_scale_log2"]
    shorts = {n.split(".")[-1] for n, _, _ in table}
    assert set(scaled) <= shorts
    for short in shorts:
        assert scale(f"L2.{short}") == 2.0 ** scaled.get(short, 0)
    # the build drew nothing (every matrix zeros) and put the program's
    # global initialiser back
    from paddle_tpu.nn import initializer as init

    assert init._global_weight_init is None
    toy = run.load_json("benchmark", "configs", "toy-parallel-ssm.json")
    try:
        built = fam.build_model(toy)
    finally:
        paddle.set_default_dtype(dtype_was)
    assert not any(float(abs(p._value).max())
                   for _, p in built.named_parameters())


def test_per_leaf_install_equals_fill(run):
    """``install_weights`` draws leaf by leaf through ``weights.leaf_reader``
    (the two vocabulary leaves first): every parameter is bit for bit what
    ``weights.fill`` gives for the same seed, times its scale."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from benchmark.harness import weights

    fam = run.load_by_name("families", "falcon_h1")
    toy = run.load_json("benchmark", "configs", "toy-parallel-ssm.json")
    dtype_was = paddle.get_default_dtype()
    try:
        model = fam.build_model(toy)
    finally:
        paddle.set_default_dtype(dtype_was)
    table, params = fam.install_weights(model, toy, SEED)
    assert [n for n, _, _ in table] == [
        n for n, _, _ in fam.reference.leaf_table(toy)]
    want = weights.fill(table, SEED, jnp.float32,
                        [jnp.zeros(s, jnp.float32) for _, s, _ in table])
    scale = fam.leaf_scale(toy)
    assert any(scale(n) != 1.0 for n, _, _ in table)
    for (name, _, _), p, w in zip(table, params, want):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(p._value)),
            np.asarray(jax.device_get(w)) * scale(name), err_msg=name)
    get_leaf = fam.leaf_reader(toy, SEED)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(get_leaf("L1.k_w"))),
        np.asarray(jax.device_get(params[[n for n, _, _ in table].index(
            "L1.k_w")]._value)))
