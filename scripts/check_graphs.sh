#!/usr/bin/env bash
# Pre-merge static gate: tracer-hazard lint + graph-budget audit +
# golden-fingerprint compare over every registered recipe, then the
# observability smoke checks. Exits non-zero on any hazard, budget
# violation, stale allowlist entry, or fingerprint drift. Run from
# anywhere; ~1 min on the CPU backend. It measures no speed: that is
# `python3 benchmark/run.py` on the chip (PERF.md).
#
#     scripts/check_graphs.sh
#
# After an INTENTIONAL graph change: regenerate the goldens with
# `python -m paddle_tpu.analysis --update-goldens`, review their git
# diff, and re-run this gate.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PYTHONPATH="${PYTHONPATH:+$PYTHONPATH:}."

python -m paddle_tpu.analysis.lint paddle_tpu/ scripts/ tests/
python -m paddle_tpu.analysis --check --fingerprint --cost
# Observability gate (ISSUE 5 + 6): rebuild the serving + speculative
# recipes — whose engines run with FULL instrumentation (metrics
# registry + request tracer + SLOs + flight recorder) — and assert
# budgets (0 host callbacks, donation) and golden fingerprints are
# UNCHANGED, i.e. the obs layer provably never touches the compiled
# quantum. Also asserts the instrumentation actually recorded (metrics
# counted, trace validates), then runs the SLO-evaluation smoke on the
# demo engine: lenient objectives read ok, impossible ones critical,
# and every forced threshold crossing dumps a schema-valid flight
# journal.
#
# Front-door gate (ISSUE 7): the `--check --fingerprint` pass above
# also audits `serving_frontdoor_step` (the sampling quantum, whose
# per-slot temperature is an input, built through the full policy tier after a forced
# preemption: 0 host callbacks, pools donated, its own golden), and
# `obs check` runs the front-door smoke — a forced priority preemption
# must fire the preempted/resumed/recomputed counters, resume must
# continue the stream, drain must flush the flight journals, and the
# watch dashboard must render the overload line. H106/H107 lint covers
# serving/{frontend,policy}.py through the repo-wide scan above.
#
# Prefix-cache gate (ISSUE 9): `--check --fingerprint` audits
# `serving_prefix_step` (the prefix_cache=True engine's quantum after
# a REAL cache hit + copy-on-write: 0 host callbacks, pools donated,
# same caps as serving_decode_step — the proof the whole
# content-addressed cache policy is host-side allocator work), and
# `obs check` runs the prefix smoke: forced hit/COW must fire the
# serving_prefix_cache_* counters, streams must stay bit-identical to
# an unshared engine, and the dashboard must render the prefix line.
# TP-serving gate (ISSUE 11): `--check --fingerprint` above also
# audits `serving_tp_step` — the tp=2 quantum on the ("mp",) mesh:
# params head/ffn-sharded through the training recipes' mp layers, KV
# pool leaves split along kv heads, still ONE dispatch with in-graph
# collectives. Its budget pins the collective census (<=8 ops /
# <=46 KB per quantum), demands the pool leaves CARRY the mp axis
# (min_sharded_params=4, max_replicated_param_bytes=0) and keeps 0
# host callbacks + donation; the tp=1 recipes' goldens must stay
# byte-identical (the mesh enters only through the tp recipe). The
# CLI re-execs with 8 virtual CPU devices when the host exposes fewer.
#
# Resilience gate (ISSUE 13): the recipe engines above now carry a
# DISARMED FaultInjector (faults.py threads every host boundary), so
# the `--check --fingerprint` pass doubles as the proof that the
# fault-injection seams change no compiled graph: 0 host callbacks
# and byte-identical goldens with the injector present. `obs check`
# then runs the bounded chaos-soak smoke (~30 s): a seeded
# faults x preemption x COW run where every non-poisoned stream must
# stay bit-exact vs the fault-free arm and the pools must drain to
# zero leaked blocks; the full 200-round soak lives in
# tests/test_resilience.py (slow) and scripts/soak.py.
#
# Quantized-serving gate (ISSUE 14): `--check --fingerprint` above
# also audits `serving_int8_step` — the weight-only-int8 + int8-KV
# decode quantum. Its budget demands quantization is LIVE in the
# compiled graph (min_int8_matmuls=10 contractions fed from int8
# storage; a silently-disabled quant path would stream bit-identical
# tokens but blows this floor), keeps 0 host callbacks + full pool
# donation, and pins temp/peak bytes (~613 KB / ~286 KB audited).
# Every float recipe's golden must stay byte-identical — the KV scale
# pools ride the quantum signature as EMPTY pytrees when unquantized,
# so the float graphs never see them. `obs check` then runs the int8
# smoke: a forced prefix hit + COW on an int8 pool whose streams are
# bit-identical to the unshared int8 engine, a >=2x pool-residency
# win over the float twin, and the dtype-labeled serving_pool_bytes
# gauge live in the registry.
#
# Cost-model gate (ISSUE 16): the lint scan above now covers tests/
# and the host-escape rules H108-H110 (implicit device->host syncs in
# HOST code: bare .item(), float()/np.* over jax values,
# block_until_ready outside bench/test paths) with a justified-only
# allowlist; `--cost` prints each recipe's FLOP/byte counts, roofline
# placement and device-time floor on the default chip, and gates that
# BOTH cost sources (XLA cost_analysis + the jaxpr walker) populated
# and agree within the pinned band. The per-recipe FLOP/byte/intensity
# caps ride `--check`; the exact counts ride the goldens.
#
# Mixed-step gate (ISSUE 27): `--check --fingerprint` above also audits
# `serving_mixed_step` — the ONE jitted program a step with prefilling
# rows dispatches (`paged_chunk_math` with per-row counts, a prefill
# chunk and a decode row riding along, the quantum's own token selection
# at its end): 0 host callbacks, 0 collectives at tp=1, every KV pool
# leaf donated, bf16 stays bf16, and a temp cap that (S, C, V) logits
# or a lost donation would blow. The verify pass shares its chunk
# attention (`_paged_chunk_attn`: grouped heads, streamed over key
# blocks past 256 MiB of f32 scores), so `speculative_verify_step`'s
# golden was regenerated with it; every quantum golden is untouched.
#
# Multi-quantum gate (ISSUE 17): `--check --fingerprint` above also
# audits `serving_multiquantum_step` — the K=4 on-device decode driver
# (lax.while_loop over the scanned quantum, retiring rows against the
# eos/max-len masks WITHOUT re-entering the host). Its budget pins the
# driver: 0 host callbacks over K quanta, full pool donation, no f32
# matmul, and the quantum's own temp/FLOP/byte caps (both cost walkers
# count the loop body once). The single-quantum recipes' goldens are
# those of K=1 engines, which build the same scanned quantum.
#
# Cluster gate (ISSUE 15): the router is pure host code riding the
# same engines, so `--check --fingerprint` above (0 host callbacks,
# byte-identical goldens) already proves the cluster tier touches no
# compiled graph. `obs check` then runs the cluster smoke: a
# 2-replica ClusterFrontDoor on a shared-prefix trace must re-land
# twin prompts on their prefix owner (affinity hits live in the
# serving_router_* counters), stream bit-identical to a cluster-of-1
# run, and render the merged ClusterExporter dashboard's cluster line.
python -m paddle_tpu.obs check
echo "check_graphs: lint + budgets + fingerprints + cost agreement + obs all green"
