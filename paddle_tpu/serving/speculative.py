"""On-device speculative decoding round for the serving engine
(reference: the speculative-decoding serving mode of the reference NLP
stack — unverified, SURVEY.md §0; algorithm: speculative sampling à la
Leviathan et al. / Chen et al.).

A host-driven round would pay γ draft dispatches, one verify dispatch
and a host sync per proposal round. Here the ENTIRE round is one jitted
program batched over the serving slot dimension:

- **draft phase**: a ``lax.scan`` of γ+1 single-token draft steps over
  the draft's own paged pool (``engine.paged_decode_math`` — the same
  step definition the plain quantum scans). Step j consumes token j-1's
  output, so the extra step γ exists purely to write proposal γ-1's KV
  for the full-accept path (the host engine's PR-1 stale-KV fix, now
  in-graph and unconditional: for rejecting slots that write lands
  beyond the valid length and is overwritten next round).
- **verify phase**: ONE target forward over the γ+1-token chunk
  ``[last_tok, p_0..p_{γ-1}]`` per slot (``paged_chunk_math``) — every
  position's logits in a single dispatch, KV written at
  ``seq_lens + j``.
- **acceptance in-graph**: the greedy arm accepts the longest prefix
  matching the target argmax and emits the target's own choice at the
  first mismatch, so the emitted stream IS the target's greedy stream
  (exact by construction). The sampling arm is rejection sampling:
  accept p_j with probability min(1, p(x)/q(x)) (p, q the FILTERED
  target/draft distributions), resample the first rejection from
  norm(max(p-q, 0)), bonus-sample position γ from the target — exact
  in distribution for ``decode_strategy="sampling"``. Token draws use
  the same ``fold_in(key, n_emitted)`` stream as the plain engine
  (acceptance/resample draws ride separate fold_in tags), so a
  draft==target sampling engine reproduces the plain sampling engine
  bit-for-bit on fixed seeds.
- **roll forward/back by length mask**: both pools advance
  ``seq_lens`` by the emitted count only; rejected proposals' KV slots
  simply fall beyond the new length and are overwritten by the next
  round's writes. eos/max-new retirement masks compose with the
  variable per-round yield exactly like the plain quantum's.

The engine jits this with the draft AND target pool buffers — plus
their int8 scale pools, empty pytrees on a float engine — donated
(``donate_argnums=(0, ..., 7)``); the compiled program is pinned by
the ``speculative_verify_step`` analysis budget (0 involuntary remat,
0 host syncs, 0 collectives, bf16 stays bf16, both pools donated).

TENSOR PARALLELISM: the round needs no code of its own — it is built
from the SAME ``paged_decode_math`` / ``paged_chunk_math`` the plain
quantum scans, whose KV writes re-pin the kv-head sharding under an
installed mesh (nlp/paged_attention.py ``_pin_kv``). When the engine runs ``tp>1``
both models' params are mesh-sharded at build, BOTH paged pools carry
the kv-head split, and the whole draft+verify round stays one dispatch
whose collectives live in-graph — the ``serving_tp_step`` recipe's
census caps and the tp2 parity tests pin that shape.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import autograd
from ..jit import functional_call
from ..nlp.generation import _filter_logits
from .engine import paged_decode_math, paged_chunk_math

__all__ = ["make_spec_round"]

# fold_in stream tags: acceptance-test uniforms and residual-resample
# draws must be independent of the token-proposal stream (which reuses
# the plain engine's fold_in(key, n_emitted) discipline for parity)
_ACC_TAG = 0x5ACC
_RES_TAG = 0x5E5A


def _stream_keys(keys, base, tag, n):
    """(S, n) raw keys: fold the per-slot key with ``tag`` then with
    the absolute emission index base+j — deterministic per (slot,
    position), independent across tags."""
    def per_slot(key, b):
        tagged = jax.random.fold_in(key, tag)
        return jax.vmap(lambda j: jax.random.fold_in(tagged, b + j))(
            jnp.arange(n))

    return jax.vmap(per_slot)(keys, base)


def make_spec_round(engine):
    """Build the speculative round for ``engine`` (a
    :class:`~paddle_tpu.serving.ServingEngine` with ``spec_draft``):
    returns the pure function the engine jits with both pools donated.

    State contract (mirrors the plain quantum): ``seq_lens`` counts
    tokens IN both caches (identical histories by construction),
    ``last_tok`` is the newest emitted token not yet cached. Returns
    ``(t_kc, t_vc, t_ks, t_vs, d_kc, d_vc, d_ks, d_vs, seq_lens,
    last_tok, n_gen, done, stream, emitted, accepted)`` where
    ``stream`` is the (S, γ+1) emission matrix, ``emitted`` the
    per-slot valid prefix length (yield after eos/max-new caps), and
    ``accepted`` the raw per-slot acceptance count for the serving
    stats. The ``*_ks``/``*_vs`` pytrees are the int8 pools' per-row
    scale pools; on a float engine they are EMPTY tuples (zero avals —
    the compiled round and its golden are byte-identical)."""
    target = engine.model
    draft = engine.spec_draft
    gamma = int(engine.spec_gamma)
    greedy = engine.decode_strategy == "greedy"
    top_k, top_p, temp = engine.top_k, engine.top_p, engine.temperature
    has_eos = engine.eos_token_id is not None
    eos = -1 if engine.eos_token_id is None else int(engine.eos_token_id)
    t_scratch = engine._scratch_block
    d_scratch = engine._d_scratch_block

    def spec_round(t_kc, t_vc, t_ks, t_vs, d_kc, d_vc, d_ks, d_vs,
                   t_pv, d_pv, t_tables, d_tables, seq_lens, last_tok,
                   n_gen, done, max_new, keys):
        live = ~done
        s_ = last_tok.shape[0]

        # -- draft: γ+1 single-token steps under one lax.scan ---------
        def draft_body(carry, j):
            kcs, vcs, kss, vss, cur = carry
            with autograd.no_grad():
                def fwd(tok_t):
                    return paged_decode_math(
                        draft, d_scratch, tok_t, seq_lens + j,
                        d_tables, kcs, vcs, live, ks=kss, vs=vss)

                (logits, kcs2, vcs2, kss2, vss2, _), _ = functional_call(
                    draft, fwd,
                    [Tensor(cur[:, None], stop_gradient=True)], {},
                    d_pv, [])
            if greedy:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                q = jnp.zeros((s_, 1), jnp.float32)  # unused, DCE'd
            else:
                filt = _filter_logits(logits, top_k, top_p, temp)
                step_keys = jax.vmap(jax.random.fold_in)(keys,
                                                         n_gen + j)
                nxt = jax.vmap(jax.random.categorical)(
                    step_keys, filt).astype(jnp.int32)
                q = jax.nn.softmax(filt, axis=-1)
            return (kcs2, vcs2, kss2, vss2, nxt), (nxt, q)

        (d_kc, d_vc, d_ks, d_vs, _), (props, qs) = jax.lax.scan(
            draft_body,
            (d_kc, d_vc, tuple(d_ks), tuple(d_vs), last_tok),
            jnp.arange(gamma + 1))
        prop_sg = jnp.transpose(props[:gamma])           # (S, γ)
        chunk = jnp.concatenate([last_tok[:, None], prop_sg], axis=1)

        # -- verify: ONE target forward over all γ+1 positions --------
        with autograd.no_grad():
            def tfwd(ids_t):
                return paged_chunk_math(
                    target, t_scratch, ids_t, seq_lens, t_tables,
                    t_kc, t_vc, live, ks=t_ks, vs=t_vs)

            (t_logits, t_kc2, t_vc2, t_ks2, t_vs2, _), _ = functional_call(
                target, tfwd, [Tensor(chunk, stop_gradient=True)], {},
                t_pv, [])

        # -- acceptance prefix + bonus/resample, in-graph -------------
        pos = jnp.arange(gamma + 1)
        if greedy:
            # accepted proposals EQUAL the target argmax, so the
            # emission stream is the target's own choice at every
            # position — exactness by construction
            t_choice = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)
            match = prop_sg == t_choice[:, :gamma]
            a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                        axis=1)
            stream = t_choice
        else:
            v = t_logits.shape[-1]
            filt_t = _filter_logits(
                t_logits.reshape(s_ * (gamma + 1), v), top_k, top_p,
                temp).reshape(s_, gamma + 1, v)
            p_probs = jax.nn.softmax(filt_t, axis=-1)
            q_probs = jnp.transpose(qs[:gamma], (1, 0, 2))
            p_at = jnp.take_along_axis(
                p_probs[:, :gamma], prop_sg[..., None], axis=-1)[..., 0]
            q_at = jnp.take_along_axis(
                q_probs, prop_sg[..., None], axis=-1)[..., 0]
            ratio = p_at / jnp.maximum(q_at, 1e-30)
            acc_keys = _stream_keys(keys, n_gen, _ACC_TAG, gamma)
            u = jax.vmap(jax.vmap(jax.random.uniform))(acc_keys)
            accept = u < jnp.minimum(ratio, 1.0)
            a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32), axis=1),
                        axis=1)
            # first rejection resamples the residual max(p-q, 0); a
            # numerically-empty residual (p==q) can only pair with an
            # always-accept ratio, but guard with the target dist
            resid = jnp.maximum(p_probs[:, :gamma] - q_probs, 0.0)
            rsum = resid.sum(-1, keepdims=True)
            corr_logits = jnp.where(rsum > 0.0, jnp.log(resid),
                                    filt_t[:, :gamma])
            res_keys = _stream_keys(keys, n_gen, _RES_TAG, gamma)
            res = jax.vmap(jax.vmap(jax.random.categorical))(
                res_keys, corr_logits).astype(jnp.int32)
            # full accept: bonus-sample position γ from the target on
            # the TOKEN stream key — a draft==target engine therefore
            # replays the plain sampling engine exactly
            bonus_keys = jax.vmap(jax.random.fold_in)(keys,
                                                      n_gen + gamma)
            bonus = jax.vmap(jax.random.categorical)(
                bonus_keys, filt_t[:, gamma]).astype(jnp.int32)
            corr = jnp.concatenate([res, bonus[:, None]], axis=1)
            stream = jnp.where(
                pos[None, :] < a[:, None],
                jnp.concatenate([prop_sg, prop_sg[:, :1]], axis=1),
                corr)

        # -- yield caps (max_new, eos) + state roll ------------------
        remaining = jnp.maximum(max_new - n_gen, 0)
        e = jnp.minimum(a + 1, remaining)
        if has_eos:
            hit = (stream == eos) & (pos[None, :] < e[:, None])
            any_hit = jnp.any(hit, axis=1)
            first = jnp.argmax(hit, axis=1)
            e = jnp.where(any_hit, first + 1, e)
        e = jnp.where(live, e, 0).astype(jnp.int32)
        n_gen2 = n_gen + e
        done2 = done | (n_gen2 >= max_new)
        if has_eos:
            done2 = done2 | (live & any_hit)
        # roll both caches forward by the emitted count only — stale
        # proposal slots beyond seq_lens2 ARE the rollback (length
        # masks hide them; next round's writes reuse them)
        seq_lens2 = seq_lens + e
        idx = jnp.maximum(e - 1, 0)
        new_last = jnp.take_along_axis(stream, idx[:, None],
                                       axis=1)[:, 0]
        last_tok2 = jnp.where(e > 0, new_last, last_tok) \
            .astype(jnp.int32)
        acc = jnp.where(live, a, 0).astype(jnp.int32)
        return (t_kc2, t_vc2, t_ks2, t_vs2, d_kc, d_vc, d_ks, d_vs,
                seq_lens2, last_tok2, n_gen2, done2, stream, e, acc)

    return spec_round
