"""The Solar-Open2-shaped decoder (three layers in four a Kimi Delta Attention
mixer whose heads each keep a d x d matrix state under a delta rule and a
per-channel decay, the fourth a gated GQA without positions, routed experts
of which a chip holds a share): what is this family's own. The contract every
served family holds is ``tests/test_family_contract.py`` over this family's
row of ``tests/family_harness.py`` (which says how the test's weights are
drawn: a decay of -0.02 to -0.3 a token, so that a wrong carry of the state
shows; and why the tolerances are what they are). Here: the two forms of the
recurrence against the position-by-position scan, the ``kda_decode_update``
kernel through the interpreter, the convolutions' tails over a chunk
boundary, and the eight shares of the experts adding up to the uncut layer.
Every program is jitted.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.nlp import solar_open2 as S
from paddle_tpu.ops.pallas import kda_decode

from family_harness import FAMILIES, host, max_abs, undrawn

ROW = FAMILIES["solar_open2"]
reference = ROW.reference
F32 = jnp.float32


def scan(q, k, v, g, beta, s0):
    """The three lines, position by position, in float32 at HIGHEST: o
    (S, C, H, dv) and the last state."""
    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        decayed = state * jnp.exp(g_t)[..., None]
        answered = jnp.einsum("shkv,shk->shv", decayed, k_t,
                              precision="highest")
        state = decayed + k_t[..., None] * (
            b_t[..., None] * (v_t - answered))[..., None, :]
        return state, jnp.einsum("shkv,shk->shv", state, q_t,
                                 precision="highest")

    last, o = jax.lax.scan(step, s0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def draw(rows=3, c=64, heads=2, d=32, decay=0.1, beta=(0.2, 1.9), seed=0):
    """q^, k^ (normalised), v, g <= 0 (about ``-decay`` a token and
    channel), beta and a state to start from, as float32 arrays."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((rows, c, heads, d))) * d ** -0.5
    k = unit(rng.standard_normal((rows, c, heads, d)))
    v = rng.standard_normal((rows, c, heads, d))
    g = -decay * rng.uniform(0.5, 1.5, (rows, c, heads, d))
    b = rng.uniform(*beta, (rows, c, heads))
    s0 = rng.standard_normal((rows, heads, d, d))
    return [jnp.asarray(t, F32) for t in (q, k, v, g, b, s0)]


chunk = jax.jit(S.kda_chunk)
chunk_kept = jax.jit(lambda *a, keep: S.kda_chunk(*a, keep=keep))
step = jax.jit(S.kda_step)
scanned = jax.jit(scan)


# ---------------------------------------------------- the chunked form
# what each case draws, and the tolerance on o and on the state (o is ~0.3,
# the state ~3; float32 sums in another order). ``overflow``: g about -20 a
# token, Gamma reaches -1900 in the chunk and exp(-Gamma) is inf: only the
# ratios exist. ``beta_near_2``: the transition's eigenvalue along k near
# -1, the solve's powers at their largest.
CASES = {
    "plain": (dict(), 2e-5),
    "slow_decay": (dict(decay=0.01), 5e-5),
    "overflow": (dict(decay=20.0), 2e-6),
    "beta_near_2": (dict(beta=(1.9, 2.0), decay=0.3), 5e-5),
    "one_position": (dict(c=1), 2e-6),
    "two_positions": (dict(c=2), 2e-6),
    "wide_head": (dict(rows=2, c=32, d=128), 2e-5),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kda_chunk_is_the_scan(case):
    """One chunk from a given state: every output and the outgoing state
    are the position-by-position scan's."""
    how, tol = CASES[case]
    args = draw(**how)
    o, s1 = chunk(*args)
    ref_o, ref_s = scanned(*args)
    assert np.isfinite(host(o)).all() and np.isfinite(host(s1)).all()
    assert max_abs(ref_o) > 0.05
    assert max_abs(o, ref_o) < tol
    assert max_abs(s1, ref_s) < 10 * tol


def test_kda_chunk_carries_the_state_over_chunk_boundaries():
    """Four chunks of 16 one after the other are one scan over 64: the
    state a chunk hands on is the state the next starts from."""
    q, k, v, g, beta, s0 = draw(c=64, decay=0.05)
    ref_o, ref_s = scanned(q, k, v, g, beta, s0)
    state, outs = s0, []
    for lo in range(0, 64, 16):
        o, state = chunk(*(t[:, lo:lo + 16] for t in (q, k, v, g, beta)),
                         state)
        outs.append(o)
    assert max_abs(jnp.concatenate(outs, axis=1), ref_o) < 2e-5
    assert max_abs(state, ref_s) < 2e-4
    # and it matters: the state of one chunk back is far outside
    stale, _ = chunk(*(t[:, 48:] for t in (q, k, v, g, beta)), s0)
    assert max_abs(stale, ref_o[:, 48:]) > 1e-2


def test_kda_chunk_one_valid_position_a_masked_row_and_a_row_from_zeros():
    """What the mixed step asks: a position with g = 0 and beta = 0 is the
    identity (row 0 has ONE valid position, row 1 none: its state comes
    back bit for bit), and ``keep`` 0 starts a row from zeros without the
    state being zeroed (row 2)."""
    q, k, v, g, beta, s0 = draw(c=16)
    valid = jnp.asarray(np.array([[1] + [0] * 15, [0] * 16, [1] * 16]), F32)
    g, beta = g * valid[..., None, None], beta * valid[..., None]
    keep = jnp.asarray([1.0, 1.0, 0.0])
    o, s1 = chunk_kept(q, k, v, g, beta, s0, keep=keep)
    one_o, one_s = scanned(*(t[:1, :1] for t in (q, k, v, g, beta)), s0[:1])
    assert max_abs(o[0, 0], one_o[0, 0]) < 2e-6
    assert max_abs(s1[0], one_s[0]) < 2e-5
    np.testing.assert_array_equal(host(s1[1]), host(s0[1]))
    zero_o, zero_s = scanned(*(t[2:] for t in (q, k, v, g, beta)),
                             jnp.zeros_like(s0[2:]))
    assert max_abs(o[2], zero_o[0]) < 2e-5
    assert max_abs(s1[2], zero_s[0]) < 2e-4
    assert max_abs(zero_s) > 0.1


def test_kda_chunk_refuses_a_length_that_is_no_power_of_two():
    with pytest.raises(ValueError, match="power of two"):
        S.kda_chunk(*draw(c=12))


# ------------------------------------------------------- the one-step form
def test_kda_step_is_one_position_of_the_scan():
    q, k, v, g, beta, s0 = draw(c=4, d=32)
    state = s0
    ref_o, ref_s = scanned(q, k, v, g, beta, s0)
    for t in range(4):
        o, state = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                        state)
        assert max_abs(o, ref_o[:, t]) < 2e-6
    assert max_abs(state, ref_s) < 2e-5


def test_the_decode_kernel_is_kda_step_through_the_interpreter():
    """``kda_decode_update`` (Pallas, interpret mode on the CPU) against
    ``kda_step`` at the cell's head (d 128), 16 heads in two groups: the
    outputs and the new state; a row with g = 0 and beta = 0 (a slot that
    is not live) keeps its state bit for bit."""
    q, k, v, g, beta, s0 = draw(rows=3, c=1, heads=16, d=128, seed=3)
    q, k, v, g, beta = (t[:, 0] for t in (q, k, v, g, beta))
    g, beta = g.at[1].set(0.0), beta.at[1].set(0.0)
    assert kda_decode.supports(s0)
    o, s1 = jax.jit(kda_decode.kda_decode_update)(q, k, v, g, beta, s0)
    ref_o, ref_s = step(q, k, v, g, beta, s0)
    assert max_abs(ref_o) > 0.05
    assert max_abs(o, ref_o) < 2e-5
    assert max_abs(s1, ref_s) < 2e-5
    np.testing.assert_array_equal(host(s1[1]), host(s0[1]))
    # shapes the kernel does not take go the plain way
    assert not kda_decode.supports(jnp.zeros((2, 4, 32, 32), F32))
    assert not kda_decode.supports(jnp.zeros((2, 12, 128, 128), F32))
    assert not kda_decode.supports(jnp.zeros((2, 8, 128, 128), jnp.bfloat16))


# ------------------------------------------------------------- the mixer
@pytest.fixture(scope="module")
def mixer():
    """A KDA mixer at toy sizes (4 heads of 32, chunks of 8) with drawn
    weights whose decay is slow (``dt_bias`` about -4)."""
    paddle.seed(11)
    layer = S.KimiDeltaAttention(64, 4, 32, chunk_size=8)
    layer.dt_bias._value = jnp.full((128,), -4.0)
    layer.A_log._value = jnp.linspace(0.0, 2.0, 4)
    for name in "qkv":
        conv = getattr(layer, f"{name}_conv1d").weight
        conv._value = conv._value * 8.0
    layer.eval()
    return layer


def _state(layer, rows, fill=0.0):
    return [jnp.full((rows, *shape), fill, dtype or F32)
            for shape, dtype in layer.state_arrays()]


def test_the_tails_and_the_state_cross_a_chunk_boundary(mixer):
    """Twenty positions at once, and as 13 + 7 through the protocol's
    chunk form (a mixed step of 16 with 13 valid positions, then one of 8
    with 7): the same outputs, the same state and the same three tails;
    the tails are the convolutions' inputs that END at the last valid
    position, not at the chunk's end."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 20, 64)), F32)

    @jax.jit
    def whole(x):
        out, cache = mixer._mix(Tensor(x, stop_gradient=True),
                                jnp.ones(x.shape[:2], F32),
                                _state(mixer, 2))
        return out._value, cache

    @jax.jit
    def part(x, counts, lens, cache):
        c = x.shape[1]
        step_ = {"valid": jnp.arange(c)[None, :] < counts[:, None],
                 "live": counts > 0, "lens": lens}
        out, cache = mixer.paged_chunk(Tensor(x, stop_gradient=True), step_,
                                       cache)
        return out._value, cache

    ref, ref_cache = whole(x)
    first = jnp.zeros((2, 16, 64), F32).at[:, :13].set(x[:, :13])
    second = jnp.zeros((2, 8, 64), F32).at[:, :7].set(x[:, 13:])
    # a used slot: what an earlier request left is not read
    o1, cache = part(first, jnp.asarray([13, 13]), jnp.asarray([0, 0]),
                     _state(mixer, 2, fill=7.0))
    o2, cache = part(second, jnp.asarray([7, 7]), jnp.asarray([13, 13]),
                     cache)
    assert max_abs(ref) > 0.05
    assert max_abs(o1[:, :13], ref[:, :13]) < 2e-5
    assert max_abs(o2[:, :7], ref[:, 13:]) < 2e-5
    for got, want in zip(cache, ref_cache):
        assert max_abs(got, want) < 2e-5
    # the tail is the last three INPUTS of each convolution
    q_raw = host(mixer.q_proj(Tensor(x, stop_gradient=True))._value)
    np.testing.assert_allclose(host(cache[1]), q_raw[:, 17:], atol=1e-5)


def test_decode_takes_the_kernel_where_the_head_is_whole_tiles():
    """``paged_decode`` with ``FLAGS_pallas_force`` (the interpreter on the
    CPU) at a head of 128 goes through ``kda_decode_update`` and gives what
    the plain path gives; a row that is not live keeps its cache."""
    with undrawn():
        layer = S.KimiDeltaAttention(32, 8, 128, chunk_size=8)
    rng = np.random.default_rng(9)
    for _, p in layer.named_parameters():
        p._value = jnp.asarray(
            rng.standard_normal(p._value.shape) * 0.2, F32)
    layer.eval()
    x = jnp.asarray(rng.standard_normal((3, 1, 32)), F32)
    cache = [jnp.asarray(rng.standard_normal((3, *shape)), F32)
             for shape, _ in layer.state_arrays()]
    live = jnp.asarray([True, False, True])

    def program():
        """A function of its own each time: the route is read while the
        program is traced, and a trace is cached by the function."""
        def decode(x, cache):
            out, new = layer.paged_decode(Tensor(x, stop_gradient=True),
                                          {"live": live}, cache)
            return out._value, new
        return decode

    ref, ref_cache = jax.jit(program())(x, cache)
    assert "pallas_call" not in str(jax.make_jaxpr(program())(x, cache))
    paddle.set_flags({"FLAGS_pallas_force": True})
    try:
        assert "pallas_call" in str(jax.make_jaxpr(program())(x, cache))
        out, new = jax.jit(program())(x, cache)
    finally:
        paddle.set_flags({"FLAGS_pallas_force": False})
    assert max_abs(out, ref) < 2e-5
    for got, want, old in zip(new, ref_cache, cache):
        assert max_abs(got, want) < 2e-5
        np.testing.assert_array_equal(host(got[1]), host(old[1]))


def test_the_gated_attention_is_the_shared_gate_over_no_position_attention():
    """``SolarOpen2Attention`` adds ONE leaf to ``NoPositionAttention``
    (``gate_proj``) and no form of its own: with the gate's product at
    zero its output is half the ungated one (sigmoid(0))."""
    from paddle_tpu.nlp.granitemoehybrid import NoPositionAttention

    cfg = S.SolarOpen2Config.tiny()
    paddle.seed(3)
    gated = S.SolarOpen2Attention(cfg)
    assert {n for n, _ in gated.named_parameters()} == {
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight",
        "gate_proj.weight"}
    assert "forward" not in vars(S.SolarOpen2Attention)
    gated.gate_proj.weight._value = jnp.zeros_like(
        gated.gate_proj.weight._value)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 9, 32)),
                    F32)
    run = jax.jit(lambda x: gated(Tensor(x, stop_gradient=True))._value)
    plain = jax.jit(lambda x: NoPositionAttention.forward(
        gated, Tensor(x, stop_gradient=True))._value)
    ungated = jax.jit(lambda x: NoPositionAttention._project_out(
        gated, Tensor(x, stop_gradient=True), None)._value)
    assert max_abs(run(x)) > 0.01
    assert max_abs(run(x), plain(x)) == 0.0
    att = jnp.asarray(np.random.default_rng(2).standard_normal((2, 9, 64)),
                      F32)
    halved = jax.jit(lambda a, x: gated._project_out(
        Tensor(a, stop_gradient=True),
        Tensor(x, stop_gradient=True))._value)
    assert max_abs(halved(att, x), 0.5 * ungated(att)) < 1e-6


# ----------------------------------------------- the shares of the experts
def test_the_shares_add_up_to_the_uncut_layer():
    """Sixteen experts in four shares of four: the routed parts that the
    four chips' programs compute, plus what every chip computes alike (the
    shared expert) counted ONCE, are the uncut reference layer's
    feed-forward; and one share alone is the reference's share."""
    cfg = ROW.toy_cfg(n_routed_experts=16, published_experts=16,
                      held_experts=[0, 16])
    leaves = ROW.leaves(cfg, seed=4)
    m = reference.dims(cfg)
    lp = {n.split(".", 1)[1]: leaves[n] for n in reference.layer_leaves(
        cfg, 1) if n.split(".")[1].startswith(("router", "e_", "s_"))}
    y = jnp.asarray(np.random.default_rng(2).standard_normal((24, 128)),
                    F32)
    whole = reference.routed_experts(y, lp, m)
    shared = reference.shared_expert(y, lp, False)
    assert max_abs(whole) > 0.1 and max_abs(shared) > 0.1

    def share(lo):
        """The PROGRAM's block holding experts lo .. lo + 3: its output
        (routed part + shared expert)."""
        with undrawn():
            block = S.SolarOpen2MoE(S.SolarOpen2Config(
                hidden_size=128, moe_intermediate_size=40,
                n_routed_experts=16, num_experts_per_tok=3,
                held_experts=(lo, 4), num_hidden_layers=4))
        block.gate.weight._value = lp["router_w"]
        block.gate.e_score_correction_bias._value = lp["router_b"]
        block.experts.gate_up_proj._value = lp["e_gate_up"][lo:lo + 4]
        block.experts.down_proj._value = lp["e_down"][lo:lo + 4]
        for name in ("gate", "up", "down"):
            getattr(block.shared_experts, f"{name}_proj").weight._value = \
                lp[f"s_{name}"]
        block.eval()
        return jax.jit(lambda y: block(Tensor(y, stop_gradient=True))._value
                       )(y)

    outs = [share(lo) for lo in range(0, 16, 4)]
    routed = sum(o - shared for o in outs)
    assert max_abs(routed, whole) < 2e-5
    assert max_abs(routed + shared, whole + shared) < 2e-5
    second = dict(lp, e_gate_up=lp["e_gate_up"][4:8],
                  e_down=lp["e_down"][4:8])      # slab 0 is expert 4
    assert max_abs(outs[1] - shared,
                   reference.routed_experts(y, second, m, held=(4, 8))) < 2e-5
    # a share alone is NOT the layer
    assert max_abs(outs[0] - shared, whole) > 1e-2


# ------------------------------------------------------- planted faults
def _next_slot_state(model, monkeypatch):
    decode = S.KimiDeltaAttention.paged_decode
    monkeypatch.setattr(
        S.KimiDeltaAttention, "paged_decode",
        lambda self, x, step, cache: decode(
            self, x, step, (jnp.roll(cache[0], -1, axis=0), *cache[1:])))


def _beta_not_doubled(model, monkeypatch):
    for layer in model.model.layers[1:]:
        monkeypatch.setattr(layer.self_attn, "beta_scale", 1.0)


def _decay_after_correction(model, monkeypatch):
    def step(q, k, v, g, beta, s0):
        u = beta[..., None] * (v - jnp.sum(s0 * k[..., None], axis=-2))
        s1 = (s0 + k[..., None] * u[..., None, :]) * jnp.exp(g)[..., None]
        return jnp.sum(s1 * q[..., None], axis=-2), s1
    monkeypatch.setattr(S, "kda_step", step)


def _dt_bias_dropped(model, monkeypatch):
    for layer in model.model.layers[1:]:
        monkeypatch.setattr(layer.self_attn.dt_bias, "_value",
                            jnp.zeros_like(layer.self_attn.dt_bias._value))


def _wrong_chunk_state(model, monkeypatch):
    chunk, first = S.kda_chunk, {}

    def stale(q, k, v, g, beta, s0, keep=None):
        if keep is not None:        # a step's first chunk
            first["s"], first["keep"] = s0, keep
            return chunk(q, k, v, g, beta, s0, keep)
        return chunk(q, k, v, g, beta, first["s"], first["keep"])
    monkeypatch.setattr(S, "kda_chunk", stale)


def _keys_one_late(model, monkeypatch):
    k_proj = model.model.layers[0].self_attn.k_proj
    forward = k_proj.forward

    def late(x):
        out = forward(x)
        if x.shape[1] > 1:
            out._value = jnp.roll(out._value, 1, axis=1)
        return out
    monkeypatch.setattr(k_proj, "forward", late)


FAULTS = {"next_slot_state": _next_slot_state,
          "beta_not_doubled": _beta_not_doubled,
          "decay_after_correction": _decay_after_correction,
          "dt_bias_dropped": _dt_bias_dropped,
          "wrong_chunk_state": _wrong_chunk_state,
          "keys_one_late": _keys_one_late}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_is_far_outside_the_gap_tolerance(fault, monkeypatch):
    """The faults the cell's `correct` has to refuse on the chip (PERF.md
    section 6, PR 46), each planted in the PROGRAM alone and served at the
    toy size, chunks of 32 (two KDA chunks a mixed step): the served tokens
    lie further below the reference's best than a thousand times the
    family's gap tolerance, where the sound program's gap is 0."""
    from family_harness import drain, prompts, serve

    cfg = ROW.toy_cfg()
    model, get_leaf = ROW.build(cfg)
    for layer in model.model.layers[1:]:    # the program's is 64, one chunk
        layer.self_attn.chunk_size = 16
    FAULTS[fault](model, monkeypatch)
    rows = prompts(cfg, (37, 20, 9), seed=16)
    tokens = drain(serve(model, prefill_chunk=32), rows, 12)
    gaps, _ = reference.gap_below_best(cfg, get_leaf,
                                       list(zip(rows, tokens)))
    assert float(host(gaps).max()) > 1000 * ROW.gap_tol
