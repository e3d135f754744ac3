"""paddle_tpu.serving — continuous-batching inference over the paged
KV pool (reference: the 2.6-era serving loop around AnalysisPredictor /
``Predictor.run`` and the blocked-cache predictor — SURVEY.md §0/§2.6/
§3.5).

:class:`ServingEngine` multiplexes many in-flight requests over one
shared :class:`~paddle_tpu.nlp.paged_cache.PagedKVCachePool` and one
single-dispatch jitted decode step; :mod:`.scheduler` holds the
admission queue, slot table, and block accounting. With a
``spec_draft`` model the decode quantum becomes the ON-DEVICE
speculative round of :mod:`.speculative` (draft-γ scan + one-forward
verify + in-graph acceptance, both paged pools donated).

The FRONT DOOR (:mod:`.frontend` + :mod:`.policy`, entry point
``paddle.inference.serve()``) is the serving *system* over that loop:
:class:`ServingFrontDoor` streams tokens per request
(:class:`TokenStream`, sync or ``async for``), applies priority
classes (``BATCH < NORMAL < INTERACTIVE``) with pool-pressure
preemption (evict-and-recompute-on-resume, bit-exact continuation),
sheds load off the SLO burn-rate health report
(:class:`FrontDoorPolicy`), and drains gracefully.

PREFIX CACHING (``ServingEngine(prefix_cache=True)``, default off):
the pool's content-addressed index
(:mod:`paddle_tpu.nlp.paged_cache`) lets admissions alias full prompt
blocks another request already prefilled — copy-on-write isolates
writers, refcount-aware eviction reclaims cached blocks only at
refcount one, and the scheduler admits on NOVEL block demand. Streams
stay bit-identical to the unshared engine; prefill compute scales
with unique tokens.

RESILIENCE (:mod:`.faults` + :mod:`.resilience`, engine kwargs
``faults=`` / ``resilience=``): a deterministic seeded
:class:`FaultInjector` at the host boundaries (default disarmed —
byte-identical goldens), a p99-calibrated :class:`QuantumWatchdog`
with exponential-backoff retry, batch-bisect poison quarantine
(``finish_reason="error"``, everyone else keeps serving), degradation
ladders (spec auto-disable to the plain quantum, prefix-subtree
quarantine on content-verify mismatch, pool accounting rebuild from
live block tables), and crash recovery via ``engine.snapshot()`` /
``ServingEngine.restore()`` (recompute-on-resume, bit-exact greedy
continuation) — also exposed on the front door.

CLUSTER TIER (:mod:`.cluster`): :class:`ClusterRouter` fronts N
replicas with prefix-cache affinity (the public
:func:`~paddle_tpu.nlp.paged_cache.prompt_prefix_key` on a
consistent-hash ring), health-weighted balancing off each replica's
serializable load report (WARN demoted, CRITICAL skipped), and
prefill/decode role disaggregation with recompute-on-resume hand-off;
:class:`ClusterFrontDoor` keeps the exact :class:`TokenStream` API
plus cluster-wide drain, shed coordination, and fleet
snapshot/restore. Streams stay bit-identical to a single-replica run.

The compiled programs are pinned by the ``serving_decode_step`` /
``speculative_verify_step`` / ``serving_frontdoor_step`` /
``serving_prefix_step`` analysis Budgets (zero involuntary remat,
zero host callbacks, KV pools donated). Measured by ``benchmark/run.py``
on the chip (``PERF.md``).
"""
from .scheduler import Request, Scheduler, SchedulerConfig
from .engine import ServingEngine
from .speculative import make_spec_round
from .policy import (
    BATCH, INTERACTIVE, NORMAL, FrontDoorPolicy, choose_victim,
    no_shed_policy,
)
from .frontend import ServingFrontDoor, TokenStream
from .faults import FaultInjector, FaultSpec, InjectedFault
from .resilience import QuantumWatchdog, ResiliencePolicy
from .cluster import ClusterFrontDoor, ClusterReplica, ClusterRouter
from ..nlp.paged_cache import prompt_prefix_key

__all__ = ["Request", "Scheduler", "SchedulerConfig", "ServingEngine",
           "make_spec_round",
           "BATCH", "NORMAL", "INTERACTIVE", "FrontDoorPolicy",
           "choose_victim", "no_shed_policy",
           "ServingFrontDoor", "TokenStream",
           "FaultInjector", "FaultSpec", "InjectedFault",
           "QuantumWatchdog", "ResiliencePolicy",
           "ClusterReplica", "ClusterRouter", "ClusterFrontDoor",
           "prompt_prefix_key"]
