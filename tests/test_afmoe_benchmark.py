"""The benchmark's fourth family (``afmoe``: window and full attention layers
mixed, gated attention, routed experts beside a shared one) rehearsed on the
CPU, and its hand counts.

The rehearsal is the whole of a run but the look for a chip and the
profiler's trace: ``benchmark/run.py::run_cell(..., tracing=False)`` on the
toy files ``benchmark/configs/toy-window-moe.json``, ``benchmark/cells/
toy.window-moe.json`` and the toy traffic, with an index built here that
gives the toy cell every per-layer metric of the real cell (``rehearsal.json``
and ``selfcheck.py`` are not edited). The hand counts are those of PERF.md
section 3 and of ISSUE 35's table.
"""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
import selfcheck  # noqa: E402

from benchmark.harness import counts_afmoe as counts  # noqa: E402

SEED = 2147483777
CELL = "trinity-mini.doc16k-o128"
CONFIG = "trinity-mini-l5"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"window_bytes_per_slot", "afmoe_load_max_over_mean"}
TRACE_READERS = {"afmoe_decode_hbm_bw_pct", "afmoe_mixed_mfu_pct"}
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
        "configs": [{"name": "toy-window-moe",
                     "file": "benchmark/configs/toy-window-moe.json"}],
        "workloads": [{"name": "toy.window-moe", "config": "toy-window-moe",
                       "traffic": "toy-batches", "chips": 1}],
        "end_to_end": [],
        "per_layer": [dict(m, workloads=["toy.window-moe"])
                      for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_family(run, index, trace, monkeypatch):
    """Trace 0 and 1: `correct` true, the control not correct, and in the
    traced run every new reader called (those that read the device trace
    find none on the CPU and say nothing; the others give a number).
    Contexts reach 63 over a window of 16 and a ring of 24."""
    called = []
    real_load = run.load_by_name

    def load(folder, name):
        mod = real_load(folder, name)
        if folder == "metrics":
            called.append(name)
        return mod

    monkeypatch.setattr(run, "load_by_name", load)
    out = selfcheck.rehearse_cell(run, index, "toy.window-moe", SEED,
                                  trace=trace, control=1)
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
    """The two device readers on numbers a hand can check, and every new
    reader silent (None, no raise) on a configuration of another family
    and on a program without the spans."""
    from benchmark.harness import program_spans

    obs = {"config": cfg, "batches": 1, "batch": 8, "prompt_len": 16384,
           "new_tokens": 128, "pool": {},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "engine_steps": {"prefill_tokens": 131072, "mixed_steps": 16,
                            "decode_quanta": 16},
           "trace": {"module_seconds": {"jit_mixed": 2.5,
                                        "jit_quantum": 1.5}}}
    # 16 mixed steps of 8 x 1024 valid positions; 16 quanta of 8 steps in
    # which 50 of a layer's 128 experts got a row, the fullest 2 of a mean
    # of 8 x 8 / 128 = 0.5; a quantum's rows at lengths 16385 + 8 q .. + 7
    events = [_span(i, "engine.mixed", rows=8, prefill_tokens=8192,
                    bucket=1024, padded_tokens=0, moe_rows=8192 * 4 * 8,
                    window_keys=1, full_keys=1)
              for i in range(16)]
    full = window = 0
    for i in range(16):
        lens = [16385 + 8 * i + j for j in range(8)]
        events += [_span(100 + 2 * i, "engine.decode", half="dispatch"),
                   _span(101 + 2 * i, "engine.decode", half="collect",
                         moe_rows=8 * 4 * 64, moe_experts_touched=8 * 4 * 50,
                         moe_rows_max=8 * 4 * 2, moe_layer_steps=32,
                         moe_offshare_rows=0, window_keys=8 * 8 * 2048,
                         full_keys=8 * sum(lens))]
        full += 8 * sum(lens)
        window += 8 * 8 * 2048
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(events))
    mfu = run.load_by_name("metrics", "afmoe_mixed_mfu_pct").read(obs)
    flops = (2 * 401_604_608 * 131072
             + 4 * 32 * 128 * (134_225_920 + 4 * 31_458_304) * 8)
    assert mfu == pytest.approx(100 * flops / 2.5 / 197e12)
    assert 2 * flops == 278_729_418_866_688 and 25 < mfu < 30
    bw = run.load_by_name("metrics", "afmoe_decode_hbm_bw_pct").read(obs)
    nbytes = (128 * 1_220_632_064 + 16 * 8 * 4 * 50 * 12_582_912
              + (full + 4 * window) * 2048)
    assert bw == pytest.approx(100 * nbytes / 1.5 / 819e9)
    assert 4.0e9 < nbytes / 128 < 4.3e9 and 40 < bw < 46
    load = run.load_by_name("metrics", "afmoe_load_max_over_mean").read(obs)
    assert load == pytest.approx(4.0)
    # padded positions are no tokens
    half = [_span(i, "engine.mixed", rows=8, prefill_tokens=4096,
                  bucket=1024, padded_tokens=4096, moe_rows=1, window_keys=1,
                  full_keys=1) for i in range(16)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(half))
    assert run.load_by_name("metrics", "afmoe_mixed_mfu_pct").read(
        obs) == pytest.approx(100 * (
            2 * 401_604_608 * 65536
            + 4 * 32 * 128 * (134_225_920 + 4 * 31_458_304) * 8)
        / 2.5 / 197e12)
    # another family, and a program without the spans: silent
    monkeypatch.setattr(program_spans, "rows", lambda: [])
    kanana = run.load_json("benchmark", "configs", "kanana-2-30b-a3b-l8.json")
    for name in NEW_READERS | TRACE_READERS:
        reader = run.load_by_name("metrics", name)
        assert reader.read(dict(obs, config=kanana)) is None
        if name != "window_bytes_per_slot":
            assert reader.read(obs) is None
    # the accepted reader of the same quantity reads a key this source
    # does not have: why this family brings its own
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(events))
    with pytest.raises(KeyError, match="n_routed_experts"):
        run.load_by_name("metrics", "moe_load_max_over_mean").read(obs)


def test_the_window_gauge_has_to_match_the_shapes(run, cfg):
    """``window_bytes_per_slot`` reads the program's gauge and raises when
    it differs from what the configuration's shapes give."""
    from paddle_tpu.obs.registry import MetricsRegistry

    gauge = MetricsRegistry.process().gauge(
        "serving_window_bytes_per_slot", "")
    reader = run.load_by_name("metrics", "window_bytes_per_slot")
    gauge.set(25_165_824.0, pool="target")
    assert reader.read({"config": cfg, "pool": {}}) == 25_165_824
    gauge.set(25_165_824.0 * 2, pool="target")
    with pytest.raises(RuntimeError, match="bytes of window rings"):
        reader.read({"config": cfg, "pool": {}})
    assert reader.read({"config": cfg}) is None


def test_the_cell_and_its_files(run, real, cfg):
    """The cell's files against ISSUE 35's parameters."""
    cell, entry, config, traffic, limits = run.resolve(real, CELL)
    assert config == cfg and cell["chips"] == 1
    # the issue's 16 requests a batch took 12.6 s, over its 12 s rule: 8
    assert cell["config"] == CONFIG and cell["traffic"] \
        == "batch8-p16384-o128"
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers",
                                "layer_types"]
    assert traffic == dict(traffic, kind="closed_batches", batch=8,
                           prompt_len=16384, new_tokens=128, margin=1.25,
                           check_requests=8, traced_batches=1)
    assert cfg["engine"] == {
        "num_slots": 8, "block_size": 32, "num_blocks": 4224,
        "max_context": 16640, "prefill_chunk": 1024, "decode_quantum": 8,
        "decode_strategy": "greedy"}
    assert cfg["family"] == "afmoe" and cfg["torch_dtype"] == "bfloat16"
    assert cfg["reduced_from"]["num_hidden_layers"] == 32
    assert cfg["reduced_from"]["num_dense_layers"] == 2
    assert "one of eight pipeline stages" in cfg["deployment"]["layout"]
    assert len(cfg["assumed"]) >= 7
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"Trinity-Mini"' in line)
        assert entry["source"] == row["source_url"] == cfg["source"]
        # every published key as given, but the depth, the dense layers
        # and layer_types (the first five: the dense layer and one period)
        changed = {k for k, v in row["config"].items() if cfg[k] != v}
        assert changed == set(entry["reduced"])
        assert cfg["layer_types"] == row["config"]["layer_types"][:5] == [
            "sliding_attention"] * 3 + ["full_attention", "sliding_attention"]
    assert set(limits["limits"]) == {"gap_max", "gap_mean"}
    names = {m["name"] for m in run.metrics_of(real, cell, "per_layer")}
    assert names == NEW_READERS | TRACE_READERS | SHARED_READERS
    assert {m["name"] for m in run.metrics_of(real, cell, "end_to_end")} \
        == {"out_tok_s", "gap_p95_ms", "setup_s"}
    # the new entries were appended, and the cell joined its lists at the
    # end (what later PRs appended follows them)
    assert [c["name"] for c in real["configs"]].index(CONFIG) == 5
    assert [w["name"] for w in real["workloads"]].index(CELL) == 5
    # (per-layer metrics that later PRs appended follow them)
    per_layer = [m["name"] for m in real["per_layer"]]
    at = per_layer.index("window_bytes_per_slot")
    assert per_layer[at:at + 4] == [
        "window_bytes_per_slot", "afmoe_decode_hbm_bw_pct",
        "afmoe_mixed_mfu_pct", "afmoe_load_max_over_mean"]
    later = {w["name"] for w in real["workloads"][6:]}
    assert all(set(m["workloads"][m["workloads"].index(CELL) + 1:]) <= later
               for m in real["per_layer"] + real["end_to_end"]
               if CELL in m.get("workloads", ()))
    # the pool's peak: 8 requests x ceil(16511 / 32) blocks + the scratch
    assert 8 * 516 + 1 <= cfg["engine"]["num_blocks"]
    assert cfg["engine"]["max_context"] >= 16384 + 128


def test_hand_counts(cfg):
    """ISSUE 35's table and PERF.md section 3's hand counts for
    trinity-mini-l5."""
    # q, o, gate 3 x 2048 x 4096; k, v 2 x 2048 x 512; q_norm + k_norm
    assert counts.attention_matmul_params(cfg) == 27_262_976
    assert counts.attention_params(cfg) == 27_263_232
    assert counts.expert_params(cfg) == 3 * 2048 * 1024 == 6_291_456
    # router 2048 x 128 + shared 3 x 2048 x 1024
    assert counts.expert_layer_fixed_params(cfg) == 262_144 + 6_291_456
    # attention + four norms + dense MLP 3 x 2048 x 6144
    assert counts.dense_layer_params(cfg) == 27_263_232 + 8_192 \
        + 37_748_736 == 65_020_160
    # attention + four norms + router, bias 128, shared and 128 experts
    assert counts.expert_layer_params(cfg) == 27_263_232 + 8_192 \
        + 811_860_096 == 839_131_520
    assert counts.top_params(cfg) == 2 * 200_192 * 2048 + 2048 \
        == 819_988_480
    assert counts.total_params(cfg) == 65_020_160 + 4 * 839_131_520 \
        + 819_988_480 == 4_241_534_720
    published = dict(cfg, num_hidden_layers=32, num_dense_layers=2)
    assert counts.total_params(published) == 26_123_974_400
    # a token multiplies 5 attentions, the dense MLP, 4 x (router + shared
    # + 8 experts)
    assert counts.active_matmul_params_per_token(cfg) == (
        5 * 27_262_976 + 37_748_736 + 4 * (6_553_600 + 8 * 6_291_456)
    ) == 401_604_608
    assert counts.attended_pairs(16384) == 134_225_920
    assert counts.attended_pairs(16384, 2048) == 31_458_304
    assert counts.attended_pairs(100, 2048) == 5050
    # a batch: 211 TFLOP of weights, 68 of attended pairs; unclamped 176
    weights = 2 * 401_604_608 * 16 * 16384
    assert counts.prefill_flops(cfg, 16 * 16384, 16, 16384) == weights \
        + 4 * 32 * 128 * (134_225_920 + 4 * 31_458_304) * 16 \
        == 278_729_418_866_688
    assert counts.prefill_flops(dict(cfg, sliding_window=10 ** 9),
                                16 * 16384, 16, 16384) - weights \
        == 4 * 32 * 128 * 5 * 134_225_920 * 16
    # the ring: 2048 + 1024 positions in blocks of 32; K and V of 4 x 128
    assert counts.ring_tokens(cfg) == 3072 and counts.key_bytes(cfg) == 2048
    assert counts.window_bytes_per_slot(cfg) == 4 * 3072 * 2048 \
        == 25_165_824
    # the one full layer alone is in blocks
    assert counts.cache_bytes_per_token(cfg) == 2_048
    # a decode step's weights outside the routed experts: 1.22 GB
    assert counts.fixed_weight_bytes_per_step(cfg) == 2 * (
        5 * (27_263_232 + 8_192) + 37_748_736 + 4 * (6_553_600 + 128)
        + 2048 + 2048 * 200_192) == 1_220_632_064
    # a step at contexts ~16,450: 1.22 fixed + 81 experts a layer 4.08 +
    # keys 0.81 GB, the ISSUE's ~6.1 GB
    step = counts.decode_bytes_needed(cfg, 1, 4 * 81, 16 * 16450, 16 * 2048)
    assert step == 1_220_632_064 + 324 * 12_582_912 \
        + (16 * 16450 + 4 * 16 * 2048) * 2048
    assert 6.0e9 < step < 6.2e9
    # what blocks would take for the window layers at 16,512 tokens a
    # request against the rings: 2.16 GB against 0.40 GB
    assert 16 * 4 * 16512 * 2048 == 2_164_260_864
    assert 16 * counts.window_bytes_per_slot(cfg) == 402_653_184
