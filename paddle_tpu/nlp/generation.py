"""Text generation — the decode serving path (BASELINE.md config #5
class of workloads; reference: fused_multi_transformer decode HOT LOOP,
SURVEY.md §3.5).

Entry points:
- ``greedy_search``: host loop, one jitted step per token (debuggable,
  supports eos early-exit).
- ``generate_on_device`` / ``sampling_search`` / ``beam_search``: the
  ENTIRE decode loop inside one XLA program (prefill + ``lax.scan`` of
  single-token steps, static cache shapes) — one dispatch per sequence,
  the idiomatic TPU serving shape; compiled programs cached per model.
- ``generate``: the paddle-style facade routing decode_strategy to the
  on-device loops above.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import autograd
from ..jit import functional_call

__all__ = ["greedy_search", "generate_on_device", "sampling_search",
           "beam_search", "generate", "speculative_generate"]


def _logits_fn(model, p_vals, ids, offset_val, kc, vc):
    """Pure fn: one forward over ids with stacked caches (L,B,S,HK,D)."""
    caches = [(Tensor(kc[i], stop_gradient=True),
               Tensor(vc[i], stop_gradient=True))
              for i in range(kc.shape[0])]
    with autograd.no_grad():
        def fwd(ids_t):
            logits, new_caches = model(ids_t, position_offset=offset_val,
                                       caches=caches)
            return logits, new_caches

        (logits, new_caches), _ = functional_call(
            model, fwd, [Tensor(ids, stop_gradient=True)], {}, p_vals, [])
    new_kc = jnp.stack([c[0]._value for c in new_caches])
    new_vc = jnp.stack([c[1]._value for c in new_caches])
    return logits._value, new_kc, new_vc


def greedy_search(model, input_ids, max_new_tokens=32, max_length=None,
                  eos_token_id=None):
    """Host-driven greedy decode on a LlamaForCausalLM-shaped model.
    Returns (B, S_in + max_new_tokens) token ids."""
    import paddle_tpu as paddle

    input_ids = input_ids if isinstance(input_ids, Tensor) else paddle.to_tensor(input_ids)
    b, s_in = input_ids.shape
    total = max_length or (s_in + max_new_tokens)
    cfg = model.config
    p_vals = [p._value for _, p in model.named_parameters()]

    cache_len = (min(total, cfg.sliding_window)
                 if getattr(cfg, "sliding_window", None) else total)
    kc = jnp.zeros((cfg.num_hidden_layers, b, cache_len,
                    cfg.num_key_value_heads, cfg.head_dim), jnp.float32)
    vc = jnp.zeros_like(kc)

    prefill = jax.jit(
        lambda pv, ids, kc, vc: _logits_fn(model, pv, ids, 0, kc, vc))
    logits, kc, vc = prefill(p_vals, input_ids._value, kc, vc)
    next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]

    # decode steps share one compiled fn (offset passed as static int per
    # position would retrace; instead dynamic offset via closure trick:
    # re-jit per offset is avoided by using a dynamic slice update inside)
    step = jax.jit(
        lambda pv, tok, off, kc, vc: _decode_step(model, pv, tok, off, kc, vc))

    out = [input_ids._value, next_tok]
    pos = s_in
    while pos + 1 < total:
        logits, kc, vc = step(p_vals, next_tok, jnp.int32(pos), kc, vc)
        next_tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(next_tok)
        pos += 1
        if eos_token_id is not None and bool(jnp.all(next_tok == eos_token_id)):
            break
    return paddle.to_tensor(jnp.concatenate(out, axis=1))


def _decode_step(model, p_vals, tok, offset, kc, vc):
    """One-token decode with a TRACED offset: rebuilds the per-layer cache
    update with lax.dynamic_update_slice (model._update_cache uses the
    same primitive, but its position_offset must be traced here)."""
    cfg = model.config
    b = tok.shape[0]

    # run the decoder manually over stacked caches to keep offset traced
    with autograd.no_grad():
        def fwd(ids_t):
            return _manual_decode(model, ids_t, offset, kc, vc)

        (logits, new_kc, new_vc), _ = functional_call(
            model, fwd, [Tensor(tok, stop_gradient=True)], {}, p_vals, [])
    return logits, new_kc, new_vc


def _manual_decode(model, ids_t, offset, kc, vc):
    """Decode forward with traced position offset over stacked caches."""
    from ..nn.functional.rope import build_rope_cache, apply_rotary_emb
    import paddle_tpu as paddle

    cfg = model.config
    core = model.llama
    hidden = core.embed_tokens(ids_t)
    b, s, _ = hidden.shape
    cache_len = kc.shape[2]  # (L, B, S_cache, HK, D)
    windowed = bool(getattr(cfg, "sliding_window", None))
    h, hk, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim)

    inv_freq = 1.0 / (cfg.rope_theta ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = offset.astype(jnp.float32) + jnp.arange(s, dtype=jnp.float32)
    freqs = jnp.outer(pos, inv_freq)
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)

    new_kcs, new_vcs = [], []
    for i, layer in enumerate(core.layers):
        attn = layer.self_attn
        residual = hidden
        x = layer.input_layernorm(hidden)
        q = attn.q_proj(x).reshape([b, s, h, d])
        k = attn.k_proj(x).reshape([b, s, hk, d])
        v = attn.v_proj(x).reshape([b, s, hk, d])
        qv = apply_rotary_emb(q._value, cos, sin)
        kv = apply_rotary_emb(k._value, cos, sin)

        write_pos = (offset.astype(jnp.int32) % cache_len
                     if windowed else offset.astype(jnp.int32))
        kci = jax.lax.dynamic_update_slice(
            kc[i], kv.astype(kc.dtype)[:, :], (0, write_pos, 0, 0))
        vci = jax.lax.dynamic_update_slice(
            vc[i], v._value.astype(vc.dtype), (0, write_pos, 0, 0))
        new_kcs.append(kci)
        new_vcs.append(vci)

        lens = jnp.full((b,), offset + s, jnp.int32)
        if windowed:
            # rolling buffer: a single query attends every live slot
            # (wrapped order is irrelevant to softmax)
            lens = jnp.minimum(lens, cache_len)
        if jax.default_backend() == "tpu":
            from ..ops.pallas.decode_attention import decode_attention

            att = decode_attention(qv[:, 0], kci, vci, lens)[:, None]
        else:
            from ..incubate.nn.fused_transformer import _masked_decode_attn

            att = _masked_decode_attn(qv, kci, vci, lens)
        att_t = Tensor(att.reshape(b, s, h * d), stop_gradient=True)
        hidden = residual + attn.o_proj(att_t)
        hidden = hidden + layer.mlp(layer.post_attention_layernorm(hidden))
    hidden = core.norm(hidden)
    logits = model.lm_head(hidden)
    return logits._value, jnp.stack(new_kcs), jnp.stack(new_vcs)


def _ondevice_decode(model, input_ids, max_new_tokens, select,
                     cache_tag, eos_token_id=None, pad_token_id=None,
                     seed=0):
    """Shared whole-loop decode driver: prefill + ``lax.scan`` of
    single-token steps inside one jitted program, compiled once per
    (model, cache_tag, shapes). ``select(logits, i, key) -> (B,) int32``
    is the per-step token choice (argmax for greedy, filtered
    categorical for sampling — the key is unused/DCE'd for greedy).
    Rows that emit ``eos_token_id`` keep emitting ``pad_token_id``
    (default: the eos id) for the remaining fixed-trip steps."""
    import paddle_tpu as paddle

    input_ids = input_ids if isinstance(input_ids, Tensor) \
        else paddle.to_tensor(input_ids)
    b, s_in = input_ids.shape
    total = s_in + max_new_tokens
    cfg = model.config
    p_vals = [p._value for _, p in model.named_parameters()]
    cache_dtype = p_vals[0].dtype
    eos = None if eos_token_id is None else int(eos_token_id)
    pad = eos if pad_token_id is None else int(pad_token_id)

    cache_len = (min(total, cfg.sliding_window)
                 if getattr(cfg, "sliding_window", None) else total)

    def full(pv, ids, key):
        kc = jnp.zeros((cfg.num_hidden_layers, b, cache_len,
                        cfg.num_key_value_heads, cfg.head_dim), cache_dtype)
        vc = jnp.zeros_like(kc)
        logits, kc, vc = _logits_fn(model, pv, ids, 0, kc, vc)
        first = select(logits[:, -1], 0, key)[:, None]
        done0 = jnp.zeros((b,), jnp.bool_)

        def body(carry, i):
            pos, tok, done, kc, vc = carry
            with autograd.no_grad():
                def fwd(t_):
                    return _manual_decode(model, t_, pos, kc, vc)

                (lg, kc2, vc2), _ = functional_call(
                    model, fwd, [Tensor(tok, stop_gradient=True)], {},
                    pv, [])
            nxt = select(lg[:, -1], i + 1, key)[:, None]
            if eos is not None:
                # a row that has emitted eos keeps emitting pad (the
                # scan stays fixed-trip; the reference's early-exit
                # becomes pad fill)
                done = done | (tok[:, 0] == eos)
                nxt = jnp.where(done[:, None], jnp.int32(pad), nxt)
            return (pos + 1, nxt, done, kc2, vc2), tok[:, 0]

        (_, last, _, _, _), toks = jax.lax.scan(
            body, (jnp.int32(s_in), first, done0, kc, vc),
            jnp.arange(max_new_tokens - 1))
        # toks: (K-1, B) tokens at positions s_in .. total-2; append last
        gen = jnp.concatenate([toks.T, last], axis=1)
        return jnp.concatenate([ids.astype(jnp.int32), gen], axis=1)

    jitted = _model_jit_cache(
        model, cache_tag + (b, s_in, max_new_tokens, str(cache_dtype),
                            eos, pad),
        lambda: jax.jit(full))
    tokens = jitted(p_vals, input_ids._value, jax.random.PRNGKey(seed))
    return paddle.to_tensor(tokens)


def generate_on_device(model, input_ids, max_new_tokens=32,
                       eos_token_id=None, pad_token_id=None):
    """Whole greedy decode in ONE dispatch (see _ondevice_decode)."""

    def select(logits, i, key):
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    return _ondevice_decode(model, input_ids, max_new_tokens, select,
                            ("greedy",), eos_token_id=eos_token_id,
                            pad_token_id=pad_token_id)


def _filter_logits(logits, top_k, top_p, temperature):
    """Sampling logits transform (reference: the TopK/TopP process logic
    in generation_utils — unverified, SURVEY.md §0): temperature scale,
    then top-k cut, then nucleus (top-p) cut. Pure jax, (B, V) f32.
    temperature=0 is near-greedy (clamped to 1e-6, an effective
    argmax); top-k uses lax.top_k and top-p one descending sort — this
    runs inside the scanned decode hot loop."""
    logits = logits.astype(jnp.float32)
    if temperature is not None and temperature != 1.0:
        logits = logits / jnp.float32(max(float(temperature), 1e-6))
    v = logits.shape[-1]
    if top_k and 0 < top_k < v:
        kth = jax.lax.top_k(logits, int(top_k))[0][:, -1][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and 0.0 < top_p < 1.0:
        sorted_l = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_l, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest prefix with cumulative prob >= top_p (the
        # first token always survives)
        keep_sorted = cum - probs < top_p
        n_keep = jnp.sum(keep_sorted, axis=-1)  # (B,)
        cutoff = jnp.take_along_axis(
            sorted_l, jnp.maximum(n_keep - 1, 0)[:, None], axis=-1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


def _model_jit_cache(model, key, build):
    """Per-model compiled-program cache (a fresh closure per call would
    recompile the whole decode loop every time)."""
    cache = getattr(model, "_generate_jit_cache", None)
    if cache is None:
        cache = model._generate_jit_cache = {}
    if key not in cache:
        cache[key] = build()
    return cache[key]


def sampling_search(model, input_ids, max_new_tokens=32, top_k=0,
                    top_p=1.0, temperature=1.0, seed=0,
                    eos_token_id=None, pad_token_id=None):
    """Whole SAMPLING decode in one dispatch (reference:
    generation_utils' decode_strategy="sampling" — unverified, SURVEY
    §0): each step draws from the temperature/top-k/top-p-filtered
    distribution with a per-step fold_in of the seed; deterministic
    given (seed, inputs). None for a knob disables it. See
    _ondevice_decode for the loop/eos mechanics."""
    top_k = 0 if top_k is None else int(top_k)
    top_p = 1.0 if top_p is None else float(top_p)
    temperature = 1.0 if temperature is None else float(temperature)

    def select(logits, i, key):
        filt = _filter_logits(logits, top_k, top_p, temperature)
        return jax.random.categorical(
            jax.random.fold_in(key, i), filt).astype(jnp.int32)

    return _ondevice_decode(
        model, input_ids, max_new_tokens, select,
        ("sampling", top_k, top_p, temperature),
        eos_token_id=eos_token_id, pad_token_id=pad_token_id, seed=seed)


def beam_search(model, input_ids, max_new_tokens=32, num_beams=4,
                length_penalty=1.0, eos_token_id=None, pad_token_id=None):
    """Whole BEAM-SEARCH decode in one dispatch (reference:
    generation_utils' decode_strategy="beam_search" — unverified,
    SURVEY §0): beams ride the batch dim (B*num_beams rows), the scan
    step reorders the stacked KV caches with the surviving beams'
    indices, and the best beam per batch row — sum log-prob divided by
    generated length ** ``length_penalty`` — is returned.

    With ``eos_token_id``, a beam that emits it RETIRES: its score
    freezes, its only continuation is ``pad_token_id`` (default: eos)
    at zero cost, and its generated length stops growing — so beams
    end at different lengths and the length penalty is live. Without
    eos all beams share one length and the penalty cannot change the
    argmax."""
    import paddle_tpu as paddle

    input_ids = input_ids if isinstance(input_ids, Tensor) \
        else paddle.to_tensor(input_ids)
    b, s_in = input_ids.shape
    total = s_in + max_new_tokens
    cfg = model.config
    vocab = cfg.vocab_size
    p_vals = [p._value for _, p in model.named_parameters()]
    cache_dtype = p_vals[0].dtype
    nb = int(num_beams)
    eos = None if eos_token_id is None else int(eos_token_id)
    pad = eos if pad_token_id is None else int(pad_token_id)

    cache_len = (min(total, cfg.sliding_window)
                 if getattr(cfg, "sliding_window", None) else total)

    def full(pv, ids):
        kc = jnp.zeros((cfg.num_hidden_layers, b, cache_len,
                        cfg.num_key_value_heads, cfg.head_dim), cache_dtype)
        vc = jnp.zeros_like(kc)
        logits, kc, vc = _logits_fn(model, pv, ids, 0, kc, vc)
        logp0 = jax.nn.log_softmax(
            logits[:, -1].astype(jnp.float32), axis=-1)  # (B, V)
        scores0, tok0 = jax.lax.top_k(logp0, nb)          # (B, nb)
        # beams ride the batch dim: row layout (b0beam0, b0beam1, ...)
        kc = jnp.repeat(kc, nb, axis=1)
        vc = jnp.repeat(vc, nb, axis=1)
        tok = tok0.reshape(b * nb, 1).astype(jnp.int32)
        scores = scores0.reshape(b * nb)
        seqs = jnp.zeros((b * nb, max_new_tokens), jnp.int32)
        seqs = seqs.at[:, 0].set(tok[:, 0])
        done0 = jnp.zeros((b * nb,), jnp.bool_)
        lens0 = jnp.ones((b * nb,), jnp.int32)

        def body(carry, i):
            pos, tok, scores, seqs, done, lens, kc, vc = carry
            with autograd.no_grad():
                def fwd(t_):
                    return _manual_decode(model, t_, pos, kc, vc)

                (lg, kc2, vc2), _ = functional_call(
                    model, fwd, [Tensor(tok, stop_gradient=True)], {},
                    pv, [])
            logp = jax.nn.log_softmax(
                lg[:, -1].astype(jnp.float32), axis=-1)   # (B*nb, V)
            if eos is not None:
                done = done | (tok[:, 0] == eos)
                # retired beams: single zero-cost pad continuation (any
                # other child would duplicate the frozen hypothesis)
                logp = jnp.where(done[:, None], -jnp.inf, logp)
                logp = logp.at[:, pad].set(
                    jnp.where(done, 0.0, logp[:, pad]))
            cand = scores[:, None] + logp                  # (B*nb, V)
            cand = cand.reshape(b, nb * vocab)
            new_scores, flat = jax.lax.top_k(cand, nb)     # (B, nb)
            beam_idx = flat // vocab                       # within-group
            new_tok = (flat % vocab).astype(jnp.int32)
            gidx = (jnp.arange(b)[:, None] * nb + beam_idx).reshape(-1)
            # surviving beams carry their history and caches
            kc2 = jnp.take(kc2, gidx, axis=1)
            vc2 = jnp.take(vc2, gidx, axis=1)
            seqs = jnp.take(seqs, gidx, axis=0)
            seqs = seqs.at[:, i + 1].set(new_tok.reshape(-1))
            done = jnp.take(done, gidx, axis=0)
            lens = jnp.take(lens, gidx, axis=0)
            lens = lens + (~done).astype(jnp.int32)
            return (pos + 1, new_tok.reshape(b * nb, 1),
                    new_scores.reshape(-1), seqs, done, lens, kc2,
                    vc2), None

        (pos, tok, scores, seqs, done, lens, _, _), _ = jax.lax.scan(
            body, (jnp.int32(s_in), tok, scores, seqs, done0, lens0,
                   kc, vc),
            jnp.arange(max_new_tokens - 1))
        # best beam per batch row: sum log-prob over generated length ^
        # penalty (lengths differ only when eos retirement happened)
        norm = scores.reshape(b, nb) / (
            lens.reshape(b, nb).astype(jnp.float32)
            ** jnp.float32(length_penalty))
        best = jnp.argmax(norm, axis=-1)                   # (B,)
        seqs_b = seqs.reshape(b, nb, max_new_tokens)
        gen = jnp.take_along_axis(
            seqs_b, best[:, None, None], axis=1)[:, 0]
        out = jnp.concatenate([ids.astype(jnp.int32), gen], axis=1)
        best_scores = jnp.take_along_axis(
            scores.reshape(b, nb), best[:, None], axis=1)[:, 0]
        return out, best_scores

    jitted = _model_jit_cache(
        model,
        ("beam", b, s_in, max_new_tokens, str(cache_dtype), nb,
         float(length_penalty), eos, pad),
        lambda: jax.jit(full))
    tokens, best_scores = jitted(p_vals, input_ids._value)
    return paddle.to_tensor(tokens), paddle.to_tensor(best_scores)


def generate(model, input_ids, max_new_tokens=32,
             decode_strategy="greedy_search", top_k=0, top_p=1.0,
             temperature=1.0, num_beams=1, length_penalty=1.0, seed=0,
             eos_token_id=None, pad_token_id=None, **kwargs):
    """paddle generation facade (reference:
    paddlenlp GenerationMixin.generate — unverified, SURVEY §0):
    routes to the on-device greedy / sampling / beam loops. Rows (or
    beams) that emit ``eos_token_id`` pad out / retire. Unknown kwargs
    raise — a silently-absorbed sampling knob under the default greedy
    strategy would otherwise produce wrong-strategy output without
    warning."""
    if kwargs:
        raise TypeError(
            f"generate: unsupported kwargs {sorted(kwargs)}")
    sampling_knobs = ((top_k or 0) > 0
                      or (top_p is not None and top_p < 1.0)
                      or (temperature is not None and temperature != 1.0))
    beam_knobs = num_beams != 1 or length_penalty != 1.0
    if decode_strategy in ("greedy_search", "greedy"):
        if sampling_knobs or beam_knobs:
            raise ValueError(
                "generate: sampling/beam knobs require "
                "decode_strategy='sampling'/'beam_search' (greedy would "
                "silently ignore them)")
        return generate_on_device(model, input_ids, max_new_tokens,
                                  eos_token_id=eos_token_id,
                                  pad_token_id=pad_token_id)
    if decode_strategy == "sampling":
        if beam_knobs:
            raise ValueError(
                "generate: num_beams/length_penalty require "
                "decode_strategy='beam_search'")
        return sampling_search(model, input_ids, max_new_tokens,
                               top_k=top_k, top_p=top_p,
                               temperature=temperature, seed=seed,
                               eos_token_id=eos_token_id,
                               pad_token_id=pad_token_id)
    if decode_strategy == "beam_search":
        if sampling_knobs:
            raise ValueError(
                "generate: top_k/top_p/temperature require "
                "decode_strategy='sampling' (beam search would silently "
                "ignore them)")
        out, _ = beam_search(model, input_ids, max_new_tokens,
                             num_beams=num_beams,
                             length_penalty=length_penalty,
                             eos_token_id=eos_token_id,
                             pad_token_id=pad_token_id)
        return out
    raise ValueError(
        f"decode_strategy must be greedy_search|sampling|beam_search, "
        f"got {decode_strategy!r}")


def speculative_generate(target, draft, input_ids, max_new_tokens=32,
                         gamma=4, decode_strategy="greedy", top_k=0,
                         top_p=1.0, temperature=1.0, seed=0,
                         eos_token_id=None, block_size=32, obs=None):
    """ON-DEVICE speculative decoding through the serving engine
    (reference: the speculative-decoding serving mode of the reference
    NLP stack — unverified, SURVEY.md §0). Every batch row rides a
    serving slot; each round — draft scans ``gamma`` proposals, target
    verifies all γ+1 positions in ONE forward, acceptance prefix and
    bonus/resample token computed in-graph, both paged KV pools rolled
    forward/back by length mask — is a single jitted dispatch
    (serving/speculative.py). The greedy arm emits EXACTLY the
    target's greedy decode; ``decode_strategy="sampling"`` is
    distribution-exact rejection sampling (row i seeds with
    ``seed + i``), deterministic given seeds. For an operated service
    around this loop — streaming, priorities with preemption, SLO load
    shedding, drain — front the engine with ``paddle.inference.serve()``
    instead of calling this batch facade (a speculative engine composes
    with the front door's priority / preemption / shedding tier;
    per-request temperature needs the plain quantum for now).

    Returns ``(tokens, acceptance_rate)``: (B, S_in+max_new) ids (rows
    finishing early at ``eos_token_id`` pad the tail with it) and the
    draft-proposal acceptance rate across the run.

    ``obs`` forwards to the engine — pass a
    :class:`paddle_tpu.obs.ServingObs` to collect this call's TTFT /
    latency / acceptance metrics (and trace spans, if its tracer is
    set) into a registry you scrape; all recording happens at host
    round boundaries, never in the jitted dispatch."""
    import numpy as np
    import paddle_tpu as paddle
    from ..serving import ServingEngine

    input_ids = input_ids if isinstance(input_ids, Tensor) \
        else paddle.to_tensor(input_ids)
    b, s_in = input_ids.shape
    rows = np.asarray(input_ids._value).astype(np.int32)
    strategy = ("greedy" if decode_strategy in ("greedy",
                                                "greedy_search")
                else decode_strategy)
    engine = ServingEngine(
        target, spec_draft=draft, spec_gamma=gamma, num_slots=b,
        block_size=block_size, max_context=s_in + max_new_tokens,
        decode_strategy=strategy, top_k=top_k, top_p=top_p,
        temperature=temperature, eos_token_id=eos_token_id, obs=obs)
    reqs = [engine.submit(rows[i], max_new_tokens=max_new_tokens,
                          seed=seed + i) for i in range(b)]
    engine.run()
    pad = 0 if eos_token_id is None else int(eos_token_id)
    out = np.full((b, s_in + max_new_tokens), pad, np.int32)
    for i, req in enumerate(reqs):
        toks = engine.output_tokens(req)
        out[i, :toks.shape[0]] = toks
    stats = engine.engine_stats()
    return paddle.to_tensor(out), stats["spec_acceptance_rate"]
