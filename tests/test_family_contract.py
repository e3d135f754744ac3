"""The contract every served family holds, each scenario written once and run
over ``family_harness.FAMILIES`` (the family's name leads the case id): the
program against the benchmark's plain float32 reference on the family's toy
configuration, through the whole-sequence forward and through the engine's
two bodies driven by hand; what the engine and the config refuse, by name;
the published preset's size; the step programs' text. The scenarios that go
through the engine itself (served tokens, the int8 control, a reused slot and
a preemption, a snapshot, counters spans and scopes) are
``tests/test_family_contract_served.py``: two files, so that neither is one
worker's long pole. What is a family's own (its kernels, its pool side, its
mixer, its multipliers) is in ``tests/test_<family>.py``. The helpers, the
tolerances and their reasons are in ``tests/family_harness.py``.
"""
import glob
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.serving import ServingEngine

from family_harness import (
    ELSEWHERE, FAMILIES, GOLDEN, ROOT, Paged, cases, host, llama_tiny,
    max_abs, prompts, stamp, step_program_hashes, toy, toy_forward)

NAMES = list(FAMILIES)


# ------------------------------------------------------ forward, reference
@pytest.mark.parametrize("name", NAMES)
def test_forward_matches_the_reference_logits(name):
    """Two sequences of 40 tokens (five windows; a state-space chunk of 16
    and an uneven last one) by the model's whole-sequence pass against the
    reference."""
    row = FAMILIES[name]
    cfg, _, get_leaf = toy(name)
    ids = np.stack(prompts(cfg, (40, 40)))
    ref = row.reference.logits(cfg, get_leaf, ids)
    got = toy_forward(name)(ids)
    assert max_abs(ref) > row.magnitude
    assert max_abs(ref, got) < row.logit_tol
    # the tolerance is earned: the int8-operand control fails it
    control = row.reference.logits(cfg, get_leaf, ids, control=True)
    assert max_abs(ref, control) > 100 * row.logit_tol


# -------------------------------------- the engine's two bodies, by hand
@pytest.mark.parametrize("name,plan", [
    pytest.param(name, plan, id=f"{name}-{plan}")
    for name, row in FAMILIES.items() for plan in row.paged["plans"]])
def test_chunked_prefill_then_decode_through_the_pool(name, plan):
    """A prompt in chunks that split it as the plan says (per step the
    counts of rows 0 and 2: the whole chunk length, lengths that do not
    divide it, one token riding along), then decode steps to the end of
    the sequence, teacher-forced: every logit the program hands out is
    the reference's full pass's, so what the chunks left in the blocks, in
    a slot's state and in a window's ring is right. Beside it a row that
    is never live keeps its side of the pool bit for bit, a row that is
    masked in decode keeps its state and its blocks, and a new request in
    a used slot reads nothing the old one left (a state starts from zero
    inside the program; a ring needs no reset: positions alone keep the
    old keys unseen)."""
    row = FAMILIES[name]
    cfg, model, get_leaf = toy(name)
    how = dict(row.paged)
    counts, chunk = how.pop("plans")[plan], how["chunk"]
    seq, other = prompts(cfg, how.pop("lengths"), seed=3)
    # one pass for both rows: the shorter right-padded (nothing causal
    # sees the padding)
    both = np.zeros((2, len(seq)), np.int32)
    both[0], both[1, :len(other)] = seq, other
    ref, ref_other = row.reference.logits(cfg, get_leaf, both)
    run = Paged(model, **how)
    row.pool_shapes(run.pool, 3, chunk)
    stamp(run.pool, 1, 7.0)
    at, at2 = 0, 0
    for n, n2 in counts:
        ids = np.zeros((3, chunk), np.int32)
        ids[0, :n] = seq[at:at + n]
        ids[2, :n2] = other[at2:at2 + n2]
        logits = run.chunk(ids, [n, 0, n2])
        at, at2 = at + n, at2 + n2
        if n:
            assert max_abs(logits[0], ref[at - 1]) < row.logit_tol
        if n2:
            assert max_abs(logits[2], ref_other[at2 - 1]) < row.logit_tol
    held = jax.tree_util.tree_map(lambda a: host(a[2]), run.pool.state)
    blocks = jnp.asarray(run.pool._tables["r2"])
    keys = [host(k[blocks]) for k in run.pool.k_pools]
    for j in range(at, len(seq)):    # row 2 rides along masked, row 1 idle
        logits = run.decode([seq[j], 0, 5], [True, False, False])
        assert max_abs(logits[0], ref[j]) < row.logit_tol
    for layer, want in zip(run.pool.state, held):
        for a, w in zip(layer, want):
            np.testing.assert_array_equal(host(a[2]), w)    # masked
            assert float(host(a[1]).min()) == 7.0 == float(
                host(a[1]).max())                           # never live
    for k, want in zip(run.pool.k_pools, keys):
        np.testing.assert_array_equal(host(k[blocks]), want)
    # the slot of row 0 is handed to a new request
    run.pool.free("r0")
    run.lens[0] = 0
    for lo in range(0, 16, chunk):
        ids = np.zeros((3, chunk), np.int32)
        ids[0] = other[lo:lo + chunk]
        logits = run.chunk(ids, [chunk, 0, 0])
        assert max_abs(logits[0], ref_other[lo + chunk - 1]) < row.logit_tol


# ------------------------------------------------------------ the refusals
@pytest.mark.parametrize("name,refusal", cases("refusals", lambda r: r[0]))
def test_refusals_by_name(name, refusal):
    """What a latent pool, a slot's recurrent state or a window layer's ring
    cannot do yet is refused by name, by what the layers cache and never by
    the model's class; nothing is silently ignored."""
    row = FAMILIES[name]
    _, kwargs, words = refusal
    model, kwargs = row.tiny_model(), dict(kwargs)
    if kwargs.get("mesh"):
        kwargs = {"mesh": jax.sharding.Mesh(host(jax.devices()[:2]),
                                            ("mp",))}
    draft = kwargs.get("spec_draft")
    if draft == "llama":
        kwargs["spec_draft"] = llama_tiny()
    elif draft == "family":
        model, kwargs["spec_draft"] = llama_tiny(), model
    elif draft == "self":
        kwargs["spec_draft"] = row.tiny_model()
    if "sliding_window" in kwargs:
        model.config.sliding_window = kwargs.pop("sliding_window")
    with pytest.raises(NotImplementedError) as err:
        ServingEngine(model, num_slots=2, block_size=8, max_context=32,
                      **kwargs)
    assert all(word in str(err.value) for word in words)


@pytest.mark.parametrize("name,refusal", cases(
    "config_refusals", lambda r: "-".join(r[0])))
def test_the_config_refuses_what_the_model_does_not_compute(name, refusal):
    overrides, what = refusal
    with pytest.raises(NotImplementedError, match=what):
        FAMILIES[name].tiny_model(**overrides)


@pytest.mark.parametrize("name", NAMES)
def test_the_published_preset_counts_the_issues_parameters(name):
    """The family's published preset is the source's config: whole, and at
    each cut a cell or an issue names, it counts the parameters the row
    says (from shapes: nothing is allocated)."""
    row = FAMILIES[name]
    try:
        for preset, keywords, count, check in row.presets:
            cfg = preset(**keywords)
            shapes = jax.eval_shape(lambda: [
                p._value for _, p in row.model(cfg).named_parameters()])
            assert sum(int(np.prod(s.shape)) for s in shapes) == count, \
                keywords
            if check is not None:
                check(cfg)
    finally:
        # the initialisers drew their keys inside the trace: the global
        # generator holds a tracer until it is seeded again
        paddle.seed(0)


# ------------------------------------- every family's programs, as they were
with open(GOLDEN) as _f:
    _GOLDEN = json.load(_f)


@pytest.mark.parametrize("name", sorted(_GOLDEN))
def test_the_families_step_programs_are_byte_for_byte(name):
    """A change to what the families share (``Mamba2Mixer``,
    ``PlainAttention``, ``routed_experts.py``, the engine's
    ``_layer_caches`` / ``_collect_caches``) that is not meant to change a
    family's programs: the two jitted programs of each family's tiny preset
    lower to the text they lowered to when the golden was written
    (``tests/goldens/family_step_programs.json``, by ``python
    tests/test_family_contract.py``; after an INTENDED change to a family's
    programs, write it again and review the diff)."""
    assert step_program_hashes(name) == _GOLDEN[name]


def test_every_served_toy_family_is_in_the_table():
    """Every toy cell whose configuration has an ``engine`` block names a
    family that is a row of ``FAMILIES`` (or one of ``ELSEWHERE``, with
    where its parity suite is): a new served family joins the contract; it
    cannot copy it unnoticed."""
    with open(os.path.join(ROOT, "benchmark", "rehearsal.json")) as f:
        named = {w["name"]: w["config"] for w in json.load(f)["workloads"]}
    served = set()
    for path in glob.glob(os.path.join(ROOT, "benchmark", "cells",
                                       "toy.*.json")):
        cell = os.path.basename(path)[:-len(".json")]
        config = named.get(cell, cell.replace(".", "-", 1))
        with open(os.path.join(ROOT, "benchmark", "configs",
                               config + ".json")) as f:
            cfg = json.load(f)
        if "engine" in cfg:
            served.add(cfg["family"])
            if cfg["family"] in FAMILIES:
                assert FAMILIES[cfg["family"]].config == config + ".json"
    assert served >= set(FAMILIES)
    assert served <= set(FAMILIES) | set(ELSEWHERE), \
        served - set(FAMILIES) - set(ELSEWHERE)


if __name__ == "__main__":
    with open(GOLDEN, "w") as _f:
        json.dump({name: step_program_hashes(name) for name in _GOLDEN},
                  _f, indent=1, sort_keys=True)
        _f.write("\n")
