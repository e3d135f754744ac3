"""The benchmark's second family (``deepseek_v3``: latent attention, routed
experts beside shared ones) rehearsed on the CPU, and its hand counts.

The rehearsal is the whole of a run but the look for a chip and the
profiler's trace: ``benchmark/run.py::run_cell(..., tracing=False)`` on the
toy files ``benchmark/configs/toy-mla-moe.json``, ``benchmark/cells/
toy.mla-moe.json`` and the toy traffic, with an index built here that gives
the toy cell every per-layer metric of the real cell (``rehearsal.json`` and
``selfcheck.py`` are not edited). The hand counts are those of PERF.md
section 3, as ``selfcheck.check_counts`` holds Llama's.
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

from benchmark.harness import counts_deepseek_v3 as counts  # noqa: E402

SEED = 2147483777
CELL = "kanana-2-30b-a3b.doc4k-o256"
NEW_READERS = {"moe_load_max_over_mean", "cache_bytes_per_token"}
TRACE_READERS = {"moe_decode_hbm_bw_pct", "mixed_mfu_pct"}


@pytest.fixture(scope="module")
def run():
    return selfcheck.load_run()


@pytest.fixture(scope="module")
def real(run):
    return run.load_json("BENCHMARK.json")


@pytest.fixture(scope="module")
def index(real):
    """One toy cell of the new family with every per-layer metric the real
    cell reports."""
    cell = next(w for w in real["workloads"] if w["name"] == CELL)
    return {
        "configs": [{"name": "toy-mla-moe",
                     "file": "benchmark/configs/toy-mla-moe.json"}],
        "workloads": [{"name": "toy.mla-moe", "config": "toy-mla-moe",
                       "traffic": "toy-batches", "chips": 1}],
        "end_to_end": [],
        "per_layer": [dict(m, workloads=["toy.mla-moe"])
                      for m in real["per_layer"]
                      if cell["name"] in m.get("workloads", ())]}


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
    out = selfcheck.rehearse_cell(run, index, "toy.mla-moe", SEED,
                                  trace=trace, control=1)
    assert out["correct"] is True and out["control_correct"] is False
    assert out["failed"] == 0 and out["attempted"] >= 4
    if trace:
        assert NEW_READERS | TRACE_READERS <= set(called)
        assert NEW_READERS <= set(out["metrics_read"])
        assert not TRACE_READERS & set(out["metrics_read"])


def test_new_readers_on_a_hand_made_observation(run):
    """The two device readers on numbers a hand can check, and every new
    reader silent (None, no raise) on a Llama configuration and on a
    program without the spans."""
    cfg = run.load_json("benchmark", "configs", "kanana-2-30b-a3b-l8.json")
    obs = {"config": cfg, "batches": 1, "batch": 32, "prompt_len": 4096,
           "new_tokens": 256, "pool": {},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "engine_steps": {"prefill_tokens": 131072, "mixed_steps": 0,
                            "decode_quanta": 0},
           "trace": {"module_seconds": {"jit_mixed": 2.0}}}
    mfu = run.load_by_name("metrics", "mixed_mfu_pct").read(obs)
    flops = 2 * 514_588_672 * 131072 + 20480 * 8_390_656 * 32 * 8
    assert mfu == pytest.approx(100 * flops / 2.0 / 197e12)
    assert 40 < mfu < 50
    qwen = run.load_json("benchmark", "configs", "qwen2-7b-l22.json")
    for name in ("moe_decode_hbm_bw_pct", "mixed_mfu_pct",
                 "moe_load_max_over_mean"):
        reader = run.load_by_name("metrics", name)
        assert reader.read(dict(obs, config=qwen)) is None
        assert reader.read({"config": cfg}) is None
    # no decode span of the program carries expert rows here: silent
    assert run.load_by_name("metrics", "moe_decode_hbm_bw_pct").read(
        dict(obs, trace={"module_seconds": {"jit_quantum": 1.0}})) is None


def test_the_cell_and_its_files(run, real):
    cell, entry, cfg, traffic, limits = run.resolve(real, CELL)
    assert cell["chips"] == 1 and entry["reduced"] == ["num_hidden_layers"]
    assert traffic == dict(traffic, kind="closed_batches", batch=32,
                           prompt_len=4096, new_tokens=128, margin=1.25,
                           check_requests=16, traced_batches=1)
    assert cfg["engine"] == {
        "num_slots": 32, "block_size": 32, "num_blocks": 5120,
        "max_context": 4352, "prefill_chunk": 512, "decode_quantum": 8,
        "decode_strategy": "greedy"}
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"kanana-2-30b-a3b-instruct-2601"' in line) \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else None
    if row is not None:     # every published key as given, but the depth
        assert entry["source"] == row["source_url"]
        assert {k: v for k, v in row["config"].items()
                if cfg[k] != v} == {"num_hidden_layers": 48}
    assert set(limits["limits"]) == {"gap_max", "gap_mean"}
    names = {m["name"] for m in run.metrics_of(real, cell, "per_layer")}
    assert NEW_READERS | TRACE_READERS <= names
    assert not {"paged_decode_attention_roofline",
                "decode_hbm_bw_pct"} & names


def test_hand_counts():
    """PERF.md section 3's hand counts for kanana-2-30b-a3b-l8."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kanana-2-30b-a3b-l8.json")) as f:
        cfg = json.load(f)
    # q 2048x6144, kv_a 2048x576, kv_b 512x8192, o 4096x2048
    assert counts.attention_matmul_params(cfg) == 26_345_472
    assert counts.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    # router 2048x128 + shared 3 x 2048x1536
    assert counts.expert_layer_fixed_params(cfg) == 262_144 + 9_437_184
    # 8 x attention + dense 3 x 2048x6144 + 7 x (fixed + 6 experts)
    assert counts.active_matmul_params_per_token(cfg) == (
        8 * 26_345_472 + 37_748_736 + 7 * (9_699_328 + 6 * 4_718_592)
    ) == 514_588_672
    # a 4,096-token prompt: 4096 x 4097 / 2 pairs, 32 heads x (192 + 128)
    # lanes x 2 operations, 8 layers
    assert counts.causal_pairs(4096) == 8_390_656
    assert counts.prefill_flops(cfg, 131072, 32, 4096) == (
        2 * 514_588_672 * 131072 + 2 * 32 * 320 * 8_390_656 * 32 * 8)
    # a decode step's weights outside the experts: 8 x (attention + two
    # norms of 2048 + the latent's of 512) + dense + 7 x (router + bias of
    # 128 + shared) + final norm + head 2048x128256, 2 bytes each
    assert counts.fixed_weight_bytes_per_step(cfg) == 2 * (
        8 * (26_345_472 + 4096 + 512) + 37_748_736
        + 7 * (9_699_328 + 128) + 2048 + 2048 * 128256) == 1_158_231_808
    # a stream of prompt 4096 and 256 new tokens: 255 steps over
    # 4097..4351 rows of 576 values, 2 bytes, 8 layers, 32 streams
    assert counts.latent_cache_bytes(cfg, 32, 4096, 256) == (
        sum(range(4097, 4352)) * 32 * 8 * 576 * 2) == 317_655_613_440
    assert counts.cache_bytes_per_token(cfg) == 9_216
    # 256 steps, 100 experts a layer and step: fixed + experts + cache
    assert counts.decode_bytes_needed(cfg, 256, 256 * 7 * 100, 1, 32, 4096,
                                      256) == (
        256 * 1_158_231_808 + 179_200 * 9_437_184 + 317_655_613_440)
