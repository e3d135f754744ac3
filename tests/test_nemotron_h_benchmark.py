"""The benchmark's fifth family (``nemotron_h``: layers of ONE part each, a
Mamba-2 mixer with groups, attention without positions, or ungated relu^2
routed experts of which a chip holds its share) rehearsed on the CPU, and its
hand counts.

The rehearsal is the whole of a run but the look for a chip and the
profiler's trace: ``benchmark/run.py::run_cell(..., tracing=False)`` on the
toy files ``benchmark/configs/toy-ssm-relu2-moe.json``, ``benchmark/cells/
toy.ssm-relu2-moe.json`` and the toy traffic, with an index built here that
gives the toy cell every per-layer metric of the real cell (``rehearsal.json``
and ``selfcheck.py`` are not edited). The hand counts are those of PERF.md
section 3 and of ISSUE 39's table (which counts 16 layers; the cut is 14:
PERF.md section 6, PR 39).
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

from benchmark.harness import counts_nemotron_h as counts  # noqa: E402

SEED = 2147483777
CELL = "nemotron-3-nano-30b-a3b.gen512-o256"
CONFIG = "nemotron-3-nano-30b-a3b-l14-ep2"
TOY = "toy.ssm-relu2-moe"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_READERS = {"nemotronh_state_bytes_per_slot",
               "nemotronh_held_load_max_over_mean"}
TRACE_READERS = {"nemotronh_decode_hbm_bw_pct", "nemotronh_mixed_mfu_pct",
                 "nemotronh_paged_decode_attention_roofline"}
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
        "configs": [{"name": "toy-ssm-relu2-moe",
                     "file": "benchmark/configs/toy-ssm-relu2-moe.json"}],
        "workloads": [{"name": TOY, "config": "toy-ssm-relu2-moe",
                       "traffic": "toy-batches", "chips": 1}],
        "end_to_end": [],
        "per_layer": [dict(m, workloads=[TOY]) for m in real["per_layer"]
                      if CELL in m.get("workloads", ())]}


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_new_family(run, index, trace, monkeypatch):
    """Trace 0 and 1: `correct` true, the int8 control not correct, and in
    the traced run every new reader called (those that read the device
    trace find none on the CPU and say nothing; the others give a number).
    Eight layers ``MEM*EMEM``: three cache nothing."""
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
    """The two device readers on numbers a hand can check, and every new
    reader silent (None, no raise) on a configuration of another family
    and on a program without the spans."""
    from benchmark.harness import program_spans

    obs = {"config": cfg, "batches": 1, "batch": 64, "prompt_len": 512,
           "new_tokens": 128, "pool": {},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "engine_steps": {"prefill_tokens": 32768, "mixed_steps": 4,
                            "decode_quanta": 16},
           "trace": {"module_seconds": {"jit_mixed": 0.7,
                                        "jit_quantum": 2.5},
                     "op_seconds": {
                         "jit_quantum/paged_decode_attention": 0.02,
                         "jit_quantum/fusion": 1.0}}}
    # 4 mixed steps of 64 x 128 valid positions whose held experts got
    # exactly half of the 6 choices a token and layer; 16 quanta of 8
    # steps in which 60 of each of 6 layers' 64 held experts got a row, the
    # fullest 9 of a mean of 64 x 6 / 128 = 3
    events = [_span(i, "engine.mixed", rows=64, prefill_tokens=8192,
                    bucket=128, padded_tokens=0, moe_rows=8192 * 3 * 6)
              for i in range(4)]
    for i in range(16):
        events += [_span(100 + 2 * i, "engine.decode", half="dispatch"),
                   _span(101 + 2 * i, "engine.decode", half="collect",
                         moe_rows=8 * 6 * 192, moe_experts_touched=8 * 360,
                         moe_rows_max=8 * 6 * 9, moe_layer_steps=48,
                         moe_offshare_rows=8 * 6 * 192)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(events))
    mfu = run.load_by_name("metrics", "nemotronh_mixed_mfu_pct").read(obs)
    flops = (2 * 400_834_560 * 32768 + 2 * 9_977_856 * 32768 * 18
             + 15_728_640 * 32768 + 4 * 4096 * 131_328 * 64 * 2)
    assert mfu == pytest.approx(100 * flops / 0.7 / 197e12)
    assert 25 < mfu < 35
    bw = run.load_by_name("metrics", "nemotronh_decode_hbm_bw_pct").read(obs)
    nbytes = (127 * 1_506_814_464 + 16 * 8 * 360 * 19_955_712
              + 127 * 64 * 2 * 12_804_096
              + sum(range(513, 640)) * 64 * 2048)
    assert bw == pytest.approx(100 * nbytes / 2.5 / 819e9)
    assert 60 < bw < 70
    # the two attention layers' keys and values of contexts 513..639, read
    # once a step and stream: memory-bound (its operations take 0.7 ms)
    roof = run.load_by_name(
        "metrics", "nemotronh_paged_decode_attention_roofline").read(obs)
    assert roof == pytest.approx(
        100 * sum(range(513, 640)) * 64 * 2048 / 819e9 / 0.02)
    assert 55 < roof < 60
    assert run.load_by_name(
        "metrics", "nemotronh_paged_decode_attention_roofline").read(
            dict(obs, trace=dict(obs["trace"], op_seconds={}))) is None
    load = run.load_by_name(
        "metrics", "nemotronh_held_load_max_over_mean").read(obs)
    assert load == pytest.approx(9 * 64 / 192) == 3.0
    # padded positions take their share of the counted rows away
    half = [_span(i, "engine.mixed", rows=64, prefill_tokens=4096, bucket=128,
                  padded_tokens=4096, moe_rows=8192 * 3 * 6)
            for i in range(4)]
    monkeypatch.setattr(program_spans, "rows",
                        lambda: program_spans.from_events(half))
    assert run.load_by_name("metrics", "nemotronh_mixed_mfu_pct").read(
        obs) == pytest.approx(100 * (
            2 * 400_834_560 * 16384 + 2 * 9_977_856 * 16384 * 18
            + 15_728_640 * 16384 + 4 * 4096 * 131_328 * 64 * 2)
            / 0.7 / 197e12)
    # another family, and a program without the spans: silent
    monkeypatch.setattr(program_spans, "rows", lambda: [])
    granite = run.load_json("benchmark", "configs",
                            "granite-4.0-h-small-l10-ep2.json")
    for name in NEW_READERS | TRACE_READERS:
        reader = run.load_by_name("metrics", name)
        assert reader.read(dict(obs, config=granite)) is None
        if name not in ("nemotronh_state_bytes_per_slot",
                        "nemotronh_paged_decode_attention_roofline"):
            assert reader.read(obs) is None    # these read the spans
    # and the siblings' readers are silent on this family's configuration
    for name in ("state_bytes_per_slot", "moe_held_load_max_over_mean",
                 "hybrid_decode_hbm_bw_pct", "hybrid_mixed_mfu_pct"):
        assert run.load_by_name("metrics", name).read(obs) is None


def test_the_state_gauge_has_to_match_the_shapes(run, cfg):
    """``nemotronh_state_bytes_per_slot`` reads the program's gauge and
    raises when it differs from what the configuration's shapes give."""
    from paddle_tpu.obs.registry import MetricsRegistry

    gauge = MetricsRegistry.process().gauge(
        "serving_state_bytes_per_slot", "")
    reader = run.load_by_name("metrics", "nemotronh_state_bytes_per_slot")
    gauge.set(12_804_096.0, pool="target")
    assert reader.read({"config": cfg, "pool": {}}) == 12_804_096
    gauge.set(12_804_096.0 + 4, pool="target")
    with pytest.raises(RuntimeError, match="bytes of state"):
        reader.read({"config": cfg, "pool": {}})
    assert reader.read({"config": cfg}) is None


def test_the_cell_and_its_files(run, real, cfg):
    """The cell's files against ISSUE 39's parameters (and the one that
    memory forced: 14 layers where the issue counted 16)."""
    cell, entry, config, traffic, limits = run.resolve(real, CELL)
    assert config == cfg and cell["chips"] == 1
    assert cell["config"] == CONFIG and cell["traffic"] == "batch64-p512-o256"
    assert entry["reduced"] == ["num_hidden_layers",
                                "hybrid_override_pattern",
                                "n_routed_experts"]
    assert set(cfg["reduced_from"]) == set(entry["reduced"])
    assert traffic == dict(traffic, kind="closed_batches", batch=64,
                           prompt_len=512, new_tokens=256, margin=1.25,
                           check_requests=16, traced_batches=1)
    assert cfg["engine"] == {
        "num_slots": 64, "block_size": 32, "num_blocks": 1664,
        "max_context": 800, "prefill_chunk": 128, "decode_quantum": 8,
        "decode_strategy": "greedy"}
    assert cfg["family"] == "nemotron_h"
    assert cfg["torch_dtype"] == "bfloat16"
    assert (cfg["n_routed_experts"], cfg["published_experts"],
            cfg["held_experts"]) == (64, 128, [0, 64])
    assert cfg["reduced_from"]["num_hidden_layers"] == 52
    assert cfg["reduced_from"]["n_routed_experts"] == 128
    assert cfg["deployment"]["chips"] == 2
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"]) \
        == (14, "MEMEM*EMEMEM*E")
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
        assert entry["source"] == row["source_url"] == cfg["source"]
        # every published key as given, but the depth, the pattern (its
        # first 14 characters) and the experts held
        changed = {k for k, v in row["config"].items() if cfg[k] != v}
        assert changed == set(entry["reduced"])
        published = row["config"]["hybrid_override_pattern"]
        assert cfg["hybrid_override_pattern"] == published[:14]
        assert cfg["reduced_from"]["hybrid_override_pattern"] == published
    assert set(limits["limits"]) == {"gap_max", "gap_mean"}
    names = {m["name"] for m in run.metrics_of(real, cell, "per_layer")}
    assert names == NEW_READERS | TRACE_READERS | SHARED_READERS
    assert {m["name"] for m in run.metrics_of(real, cell, "end_to_end")} \
        == {"out_tok_s", "gap_p95_ms", "setup_s"}
    # the new entries were appended, and the cell joined its lists at the
    # end (what later PRs append follows them)
    assert [c["name"] for c in real["configs"]].index(CONFIG) == 6
    assert [w["name"] for w in real["workloads"]].index(CELL) == 6
    later = {w["name"] for w in real["workloads"][7:]}
    joined = [m for m in real["per_layer"] + real["end_to_end"]
              if CELL in m.get("workloads", ())]
    assert len(joined) == 2 + 17 + 5 + 1    # PR 48: quanta_ahead_pct
    assert all(set(m["workloads"][m["workloads"].index(CELL) + 1:]) <= later
               for m in joined)
    assert [m["name"] for m in real["per_layer"]][-5:] == [
        "nemotronh_mixed_mfu_pct", "nemotronh_decode_hbm_bw_pct",
        "nemotronh_held_load_max_over_mean",
        "nemotronh_state_bytes_per_slot",
        "nemotronh_paged_decode_attention_roofline"] or later
    # the pool's peak: 64 requests x ceil(767 / 32) blocks + the scratch
    assert 64 * 24 + 1 <= cfg["engine"]["num_blocks"]
    # the names say what the files hold
    assert CONFIG.endswith(f"-l{cfg['num_hidden_layers']}-ep2")
    assert cell["traffic"] == (f"batch{traffic['batch']}-p"
                               f"{traffic['prompt_len']}-o"
                               f"{traffic['new_tokens']}")
    assert CELL.endswith(f"gen{traffic['prompt_len']}-o"
                         f"{traffic['new_tokens']}")


def test_hand_counts(cfg):
    """ISSUE 39's table (per layer and for the whole model) and PERF.md
    section 3's hand counts for the cut."""
    # in_proj 2688 x (4096 + 6144 + 64) + out_proj 4096 x 2688
    assert counts.conv_dim(cfg) == 4096 + 2 * 8 * 128 == 6144
    assert counts.mamba_matmul_params(cfg) == 27_697_152 + 11_010_048
    # + conv 6144 x 4 + bias 6144 + dt_bias, A_log, D 3 x 64 + norm 4096;
    # with the layer's norm 2688 the issue's 38,744,896
    assert counts.mamba_params(cfg) + 2688 == 38_744_896
    # q, o 2688 x 4096; k, v 2688 x 256; + norm: 23,399,040
    assert counts.attention_params(cfg) + 2688 == 23_399_040
    # router 2688 x 128 + bias 128 + shared 2 x 2688 x 3712; + norm
    assert counts.expert_layer_fixed_params(cfg) + 2688 == 20_302_592
    assert counts.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    # embedding + untied head + final norm
    top = 2 * 131072 * 2688 + 2688
    assert top == 704_645_760
    whole = dict(cfg, n_routed_experts=128, published_experts=128,
                 hybrid_override_pattern=cfg["reduced_from"][
                     "hybrid_override_pattern"])
    assert counts.total_params(whole) == (
        23 * 38_744_896 + 6 * 23_399_040
        + 23 * (20_302_592 + 128 * 9_977_856) + top) == 31_577_940_288
    issue = dict(cfg, hybrid_override_pattern="MEMEM*EMEMEM*EME")
    assert counts.total_params(issue) == 5_634_855_744     # 11.27 GB
    assert counts.total_params(cfg) == (
        6 * 38_744_896 + 2 * 23_399_040
        + 6 * (20_302_592 + 64 * 9_977_856) + top) == 4_937_225_472
    assert counts.fixed_matmul_params_per_token(cfg) == (
        6 * 38_707_200 + 2 * 23_396_352 + 6 * 20_299_776) == 400_834_560
    # with 3 of a token's 6 experts held, a layer: 1.16 G operations a token
    assert 2 * (counts.fixed_matmul_params_per_token(cfg)
                + 6 * 3 * 9_977_856) == 1_160_871_936
    # 5 operations a state element: 6 layers x 64 x 64 x 128
    assert counts.recurrence_flops_per_token(cfg) == 15_728_640
    assert counts.causal_pairs(512) == 131_328
    assert counts.prefill_flops(cfg, 32768, 32768 * 18, 64, 512) == (
        2 * 400_834_560 * 32768 + 2 * 9_977_856 * 32768 * 18
        + 15_728_640 * 32768 + 4 * 32 * 128 * 131_328 * 64 * 2)
    # the slot: 6 x (64 x 64 x 128 x 4 + 3 x 6144 x 2)
    assert counts.state_bytes_per_slot(cfg) == 6 * (2_097_152 + 36_864) \
        == 12_804_096
    # the two attention layers: K and V of 2 x 128 in bf16
    assert counts.cache_bytes_per_token(cfg) == 2_048
    # a decode step's weights outside the experts: 1.51 GB
    assert counts.fixed_weight_bytes_per_step(cfg) == 2 * (
        6 * 38_744_896 + 2 * 23_399_040 + 6 * 20_302_592 + 2688
        + 131072 * 2688) == 1_506_814_464
    # a closed batch's decode: 255 steps, all 384 held experts touched a
    # step, 64 slots' state read and written, the keys of 513..767
    assert counts.decode_bytes_needed(cfg, 255 * 384, 1, 64, 512, 256) == (
        255 * 1_506_814_464 + 255 * 384 * 19_955_712
        + 255 * 64 * 2 * 12_804_096 + sum(range(513, 768)) * 64 * 2048)
    # a step: 1.51 + 7.66 + 1.64 + ~0.08 GB
    step = counts.decode_bytes_needed(cfg, 255 * 384, 1, 64, 512, 256) / 255
    assert 10.8e9 < step < 11.0e9
    # the decode kernel: the keys of 513..767 in the two attention layers,
    # K and V of 2 x 128 in bf16 read once a stream and step, 32 query
    # heads x 128 over them twice (QK^T, PV)
    assert counts.paged_attention_needs(cfg, 64, 512, 256) == (
        4 * 32 * 128 * sum(range(513, 768)) * 64 * 2,
        sum(range(513, 768)) * 64 * 2048) == (
            342_255_206_400, 21_390_950_400)


def test_the_leaf_table_is_the_programs_parameters(run, cfg):
    """Every leaf of the reference's table has the program's path and
    shape, and no other (from shapes: nothing of the cut is allocated);
    ``leaf_scale`` scales the convolution's taps and D (so that the
    recurrence shows), the experts' down projections and the selection
    bias (so that the seeded routing is as even as a trained router's),
    and nothing else."""
    import jax

    fam = run.load_by_name("families", "nemotron_h")
    table = fam.reference.leaf_table(cfg)
    assert sum(1 for n, _, _ in table if n.endswith(".e_up")) == 6
    assert not [n for n, _, _ in table
                if n.startswith("L1.") and n.split(".")[1] in ("in_w", "q_w")]
    import paddle_tpu as paddle

    dtype_was = paddle.get_default_dtype()  # build_model sets the cell's
    try:
        shapes = jax.eval_shape(lambda: {
            k: p._value for k, p in fam.build_model(cfg).named_parameters()})
    finally:    # ... and a later test of this worker would inherit bfloat16
        paddle.set_default_dtype(dtype_was)
    assert {fam.program_path(n): tuple(s) for n, s, _ in table} \
        == {k: tuple(v.shape) for k, v in shapes.items()}
    scale = fam.leaf_scale(cfg)
    assert (scale("L0.conv_w"), scale("L2.D")) == (2.0 ** 5, 2.0 ** 4)
    assert (scale("L1.e_down"), scale("L1.s_down"), scale("L1.router_b")) \
        == (2.0 ** -2, 2.0 ** -2, 2.0 ** -3)
    assert scale("top.embed") == scale("top.head") == scale("L1.e_up") \
        == scale("L1.s_up") == scale("L1.router_w") == scale("L0.in_w") \
        == scale("L0.out_w") == 1.0
