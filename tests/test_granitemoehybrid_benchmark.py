"""The benchmark's third family (``granitemoehybrid``: Mamba-2 state-space
layers beside attention, routed experts of which a chip holds its share)
rehearsed on the CPU, and its hand counts.

The rehearsal is the whole of a run but the look for a chip and the
profiler's trace: ``benchmark/run.py::run_cell(..., tracing=False)`` on the
toy files ``benchmark/configs/toy-ssm-moe.json``, ``benchmark/cells/
toy.ssm-moe.json`` and the toy traffic, with an index built here that gives
the toy cell every per-layer metric of the real cell (``rehearsal.json`` and
``selfcheck.py`` are not edited). The hand counts are those of PERF.md
section 3 and of ISSUE 32's table.
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

from benchmark.harness import counts_granitemoehybrid as counts  # noqa: E402

SEED = 2147483777
CELL = "granite-4.0-h-small.chat1k-o128"
CONFIG = "granite-4.0-h-small-l10-ep2"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"state_bytes_per_slot", "moe_held_load_max_over_mean"}
TRACE_READERS = {"hybrid_decode_hbm_bw_pct", "hybrid_mixed_mfu_pct"}
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
        "configs": [{"name": "toy-ssm-moe",
                     "file": "benchmark/configs/toy-ssm-moe.json"}],
        "workloads": [{"name": "toy.ssm-moe", "config": "toy-ssm-moe",
                       "traffic": "toy-batches", "chips": 1}],
        "end_to_end": [],
        "per_layer": [dict(m, workloads=["toy.ssm-moe"])
                      for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_family(run, index, trace, monkeypatch):
    """Trace 0 and 1: `correct` true, the control not correct, and in the
    traced run every new reader called (those that read the device trace
    find none on the CPU and say nothing; the others give a number)."""
    called = []
    real_load = run.load_by_name

    def load(folder, name):
        mod = real_load(folder, name)
        if folder == "metrics":
            called.append(name)
        return mod

    monkeypatch.setattr(run, "load_by_name", load)
    out = selfcheck.rehearse_cell(run, index, "toy.ssm-moe", SEED,
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

    obs = {"config": cfg, "batches": 1, "batch": 64, "prompt_len": 1024,
           "new_tokens": 128, "pool": {},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "engine_steps": {"prefill_tokens": 65536, "mixed_steps": 8,
                            "decode_quanta": 16},
           "trace": {"module_seconds": {"jit_mixed": 3.0,
                                        "jit_quantum": 4.0}}}
    # 8 mixed steps of 64 x 128 valid positions whose held experts got
    # exactly half of the 10 choices a token and layer; 16 quanta of 8
    # steps in which every one of 36 x 10 held experts got a row, the
    # fullest 18 of a mean of 64 x 10 / 72
    events = [_span(i, "engine.mixed", rows=64, prefill_tokens=8192,
                    bucket=128, padded_tokens=0, moe_rows=8192 * 5 * 10)
              for i in range(8)]
    for i in range(16):
        events += [_span(100 + 2 * i, "engine.decode", half="dispatch"),
                   _span(101 + 2 * i, "engine.decode", half="collect",
                         moe_rows=8 * 10 * 320, moe_experts_touched=2880,
                         moe_rows_max=8 * 10 * 18, moe_layer_steps=80,
                         moe_offshare_rows=8 * 10 * 320)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(events))
    mfu = run.load_by_name("metrics", "hybrid_mixed_mfu_pct").read(obs)
    flops = (2 * 1_153_761_280 * 65536 + 2 * 9_437_184 * 65536 * 50
             + 47_185_920 * 65536 + 4 * 4096 * 524_800 * 64)
    assert mfu == pytest.approx(100 * flops / 3.0 / 197e12)
    assert 30 < mfu < 40
    bw = run.load_by_name("metrics", "hybrid_decode_hbm_bw_pct").read(obs)
    nbytes = (127 * 3_130_692_864 + 16 * 2880 * 18_874_368
              + 127 * 64 * 2 * 38_204_928
              + sum(range(1025, 1152)) * 64 * 4096)
    assert bw == pytest.approx(100 * nbytes / 4.0 / 819e9)
    assert 55 < bw < 65
    load = run.load_by_name("metrics", "moe_held_load_max_over_mean").read(obs)
    assert load == pytest.approx(18 * 36 / 320)
    # padded positions take their share of the counted rows away
    half = [_span(i, "engine.mixed", rows=64, prefill_tokens=4096, bucket=128,
                  padded_tokens=4096, moe_rows=8192 * 5 * 10)
            for i in range(8)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(half))
    assert run.load_by_name("metrics", "hybrid_mixed_mfu_pct").read(
        obs) == pytest.approx(100 * (
            2 * 1_153_761_280 * 32768 + 2 * 9_437_184 * 32768 * 50
            + 47_185_920 * 32768 + 4 * 4096 * 524_800 * 64) / 3.0 / 197e12)
    # another family, and a program without the spans: silent
    monkeypatch.setattr(program_spans, "rows", lambda: [])
    kanana = run.load_json("benchmark", "configs", "kanana-2-30b-a3b-l8.json")
    for name in NEW_READERS | TRACE_READERS:
        reader = run.load_by_name("metrics", name)
        assert reader.read(dict(obs, config=kanana)) is None
        if name != "state_bytes_per_slot":
            assert reader.read(obs) is None


def test_the_state_gauge_has_to_match_the_shapes(run, cfg):
    """``state_bytes_per_slot`` reads the program's gauge and raises when
    it differs from what the configuration's shapes give."""
    from paddle_tpu.obs.registry import MetricsRegistry

    gauge = MetricsRegistry.process().gauge(
        "serving_state_bytes_per_slot", "")
    reader = run.load_by_name("metrics", "state_bytes_per_slot")
    gauge.set(38_204_928.0, pool="target")
    assert reader.read({"config": cfg, "pool": {}}) == 38_204_928
    gauge.set(38_204_928.0 + 4, pool="target")
    with pytest.raises(RuntimeError, match="bytes of state"):
        reader.read({"config": cfg, "pool": {}})
    assert reader.read({"config": cfg}) is None


def test_the_cell_and_its_files(run, real, cfg):
    """The cell's files against ISSUE 32's parameters."""
    cell, entry, config, traffic, limits = run.resolve(real, CELL)
    assert config == cfg and cell["chips"] == 1
    assert cell["config"] == CONFIG and cell["traffic"] == "batch64-p1024-o128"
    assert set(entry["reduced"]) == {"num_hidden_layers", "num_local_experts",
                                     "layer_types"}
    assert traffic == dict(traffic, kind="closed_batches", batch=64,
                           prompt_len=1024, new_tokens=128, margin=1.25,
                           check_requests=16, traced_batches=1)
    assert cfg["engine"] == {
        "num_slots": 64, "block_size": 32, "num_blocks": 2560,
        "max_context": 1280, "prefill_chunk": 128, "decode_quantum": 8,
        "decode_strategy": "greedy"}
    assert cfg["family"] == "granitemoehybrid"
    assert cfg["torch_dtype"] == "bfloat16"
    assert (cfg["num_local_experts"], cfg["published_experts"],
            cfg["held_experts"]) == (36, 72, [0, 36])
    assert cfg["reduced_from"]["num_hidden_layers"] == 40
    assert cfg["reduced_from"]["num_local_experts"] == 72
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"granite-4.0-h-small"' in line)
        assert entry["source"] == row["source_url"] == cfg["source"]
        # every published key as given, but the depth, its layer_types
        # (the first ten: one period) and the experts held
        changed = {k for k, v in row["config"].items() if cfg[k] != v}
        assert changed == set(entry["reduced"])
        assert cfg["layer_types"] == row["config"]["layer_types"][:10]
    assert set(limits["limits"]) == {"gap_max", "gap_mean"}
    names = {m["name"] for m in run.metrics_of(real, cell, "per_layer")}
    assert names == NEW_READERS | TRACE_READERS | SHARED_READERS
    assert {m["name"] for m in run.metrics_of(real, cell, "end_to_end")} \
        == {"out_tok_s", "gap_p95_ms", "setup_s"}
    # the pool's peak: 64 requests x ceil(1151 / 32) blocks + the scratch
    assert 64 * 36 + 1 <= cfg["engine"]["num_blocks"]


def test_hand_counts(cfg):
    """ISSUE 32's table and PERF.md section 3's hand counts for
    granite-4.0-h-small-l10-ep2."""
    # in_proj 4096 x (8192 + 8448 + 128) + out_proj 8192 x 4096
    assert counts.mamba_matmul_params(cfg) == 68_681_728 + 33_554_432
    # + conv 8448 x 4 + bias 8448 + dt_bias, A_log, D 3 x 128 + norm 8192
    assert counts.mamba_params(cfg) == 102_286_976
    # q, o 4096 x 4096; k, v 4096 x 1024
    assert counts.attention_params(cfg) == 41_943_040
    # router 4096 x 72 + shared 4096 x 3072 + 1536 x 4096
    assert counts.beside_mixer_matmul_params(cfg) == 294_912 + 18_874_368
    assert counts.expert_params(cfg) == 3 * 4096 * 768 == 9_437_184
    # 9 x (mixer + beside + 2 norms) + attention layer + 10 x 36 experts
    # + tied embedding 100352 x 4096 + final norm
    assert counts.total_params(cfg) == (
        9 * 121_464_448 + 61_120_512 + 10 * 339_738_624 + 411_045_888
    ) == 4_962_732_672
    assert counts.fixed_matmul_params_per_token(cfg) == (
        9 * 102_236_160 + 41_943_040 + 10 * 19_169_280) == 1_153_761_280
    # with 5 of a token's 10 experts held, a layer: 1.63 G a token
    assert counts.fixed_matmul_params_per_token(cfg) \
        + 10 * 5 * 9_437_184 == 1_625_620_480
    # 5 operations a state element: 9 layers x 128 x 64 x 128
    assert counts.recurrence_flops_per_token(cfg) == 47_185_920
    assert counts.causal_pairs(1024) == 524_800
    assert counts.prefill_flops(cfg, 65536, 65536 * 50, 64, 1024) == (
        2 * 1_153_761_280 * 65536 + 2 * 9_437_184 * 65536 * 50
        + 47_185_920 * 65536 + 4 * 32 * 128 * 524_800 * 64 * 1)
    # the slot: 9 x (128 x 64 x 128 x 4 + 3 x 8448 x 2)
    assert counts.state_bytes_per_slot(cfg) == 9 * (4_194_304 + 50_688) \
        == 38_204_928
    # the one attention layer: K and V of 8 x 128 in bf16
    assert counts.cache_bytes_per_token(cfg) == 4_096
    # a decode step's weights outside the experts: the ISSUE's 3.13 GB
    assert counts.fixed_weight_bytes_per_step(cfg) == 2 * (
        9 * 121_464_448 + 61_120_512 + 411_045_888) == 3_130_692_864
    # a closed batch's decode: 127 steps, all 360 held experts touched a
    # step, 64 slots' state read and written, the keys of 1025..1151
    assert counts.decode_bytes_needed(cfg, 127 * 360, 1, 64, 1024, 128) == (
        127 * 3_130_692_864 + 127 * 360 * 18_874_368
        + 127 * 64 * 2 * 38_204_928 + sum(range(1025, 1152)) * 64 * 4096)
    # a step: 3.13 + 6.79 + 4.89 + ~0.29 GB, the ISSUE's ~15.0 GB
    step = counts.decode_bytes_needed(cfg, 127 * 360, 1, 64, 1024, 128) / 127
    assert 15.0e9 < step < 15.2e9


def test_seeded_scales_give_y_a_floor(run, cfg):
    """The configuration's ``seeded_leaf_scale_log2`` (embedding 2^-3,
    convolution taps 2^5, D 2^4) and WHY D is among them: with leaves drawn
    as the harness draws them, the recurrence's ``H C`` is a sum over ``C_t
    . B_s`` that cancels at a rare position; with D near 1 ``y`` is then
    ~1/16 of its usual size, the gated norm scales it back up and the
    layer passes a rounding on many times larger (the driver's seed
    1284026447 failed `correct` on one such position). With D at ``H C``'s
    size the smallest ``y`` of a few thousand positions stays within a
    small factor of the median (2.7 here, 7.4 with D near 1)."""
    import numpy as np

    fam = run.load_by_name("families", "granitemoehybrid")
    scale = fam.leaf_scale(cfg)
    assert (scale("top.embed"), scale("L0.conv_w"), scale("L3.D")) \
        == (2.0 ** -3, 2.0 ** 5, 2.0 ** 4)
    assert scale("L0.in_w") == scale("L0.ssm_ln") == scale("top.norm") == 1.0

    rng = np.random.default_rng(32)
    n, heads, p, t_len, back = int(cfg["mamba_d_state"]), 4, 64, 3000, 16
    ch = heads * p + 2 * n

    def draw(*shape):  # a matrix or bias leaf: k / 8192
        return rng.integers(-255, 256, shape) / 8192.0

    def silu(v):
        return v / (1.0 + np.exp(-v))

    # in_proj of a unit-RMS input: a column's sum of 4096 draws of 0.018
    xbc = rng.normal(0.0, 0.018 * 64, (t_len + 3, ch))
    taps = draw(ch, 4) * scale("L0.conv_w")
    conv = silu(sum(xbc[j:j + t_len] * taps[:, j] for j in range(4))
                + draw(ch))
    xs = conv[:, :heads * p].reshape(t_len, heads, p)
    b, c = conv[:, heads * p:heads * p + n], conv[:, heads * p + n:]
    dt = np.log1p(np.exp(rng.normal(0.0, 0.018 * 64, (t_len, heads))
                         + draw(heads)))
    cum = np.cumsum(-np.exp(draw(heads)) * dt, axis=0)

    def y_rms(d_scale):
        d = (1.0 + rng.integers(-12, 13, heads) / 128.0) * d_scale
        out = []
        for t in range(back, t_len):
            s = np.arange(t - back + 1, t + 1)
            w = np.exp(cum[t] - cum[s]) * dt[s] * (b[s] @ c[t])[:, None]
            y = (w[:, :, None] * xs[s]).sum(0) + d[:, None] * xs[t]
            out.append(np.sqrt(np.mean(y * y)))
        return np.asarray(out)

    floor, bare = y_rms(scale("L0.D")), y_rms(1.0)
    assert np.median(floor) / floor.min() < 4.0
    assert np.median(bare) / bare.min() > 5.0
