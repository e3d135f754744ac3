"""Flagship model family tests: Llama/GPT forward+train, decode-cache
parity, and the hybrid parallel==serial oracle through the fully-jitted
train step (the bench/dryrun path)."""
import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.parallel import mesh as mesh_state
from paddle_tpu.nlp import (
    LlamaConfig, LlamaForCausalLM, LlamaPretrainingCriterion,
    GPTConfig, GPTForCausalLM,
)
from paddle_tpu.jit.train import JittedTrainStep


@pytest.fixture(autouse=True)
def reset_mesh():
    yield
    mesh_state.set_mesh(None)


def test_llama_forward_backward_eager():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    m = LlamaForCausalLM(cfg)
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 128, (2, 16)))
    logits = m(ids)
    assert logits.shape == [2, 16, 128]
    loss = LlamaPretrainingCriterion()(logits, ids)
    loss.backward()
    g = m.llama.layers[0].self_attn.q_proj.weight.grad
    assert g is not None and float(paddle.abs(g).sum()) > 0


def test_llama_decode_cache_matches_full_forward():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    m = LlamaForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, 128, (2, 24)))
    step = paddle.to_tensor(rng.randint(0, 128, (2, 1)))

    caches = m.init_caches(2, 64)
    _, caches = m(ids, position_offset=0, caches=caches)
    lg, caches = m(step, position_offset=24, caches=caches)

    full = m(paddle.concat([ids, step], axis=1))
    np.testing.assert_allclose(
        lg.numpy()[:, 0], full.numpy()[:, -1], atol=2e-5
    )


def test_llama_recompute_matches_plain():
    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, 128, (2, 16))

    def loss_with(recompute):
        paddle.seed(0)
        cfg = LlamaConfig.tiny(tensor_parallel=False, use_recompute=recompute)
        m = LlamaForCausalLM(cfg)
        ids = paddle.to_tensor(ids_np)
        loss = LlamaPretrainingCriterion()(m(ids), ids)
        loss.backward()
        g = m.llama.layers[0].self_attn.q_proj.weight.grad.numpy()
        return float(loss), g

    l1, g1 = loss_with(False)
    l2, g2 = loss_with(True)
    assert abs(l1 - l2) < 1e-5
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-5)


def test_gpt_forward():
    paddle.seed(0)
    m = GPTForCausalLM(GPTConfig.tiny())
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 128, (2, 16)))
    assert m(ids).shape == [2, 16, 128]


def _fleet_mesh():
    """dp 2 x sharding 2 x mp 2 through ``fleet.init``, the batch over
    the step's default (``dp``)."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
        "sharding_degree": 2,
    }
    fleet.init(is_collective=True, strategy=strategy)
    return {"state_sharding_axis": "sharding"}


def _cell_mesh():
    """The benchmark's mesh cell: sharding 2 x mp 2 on exactly four
    devices (``fleet.init`` hands dp the rest of the eight), ZeRO's group
    the data-parallel group: the batch over ``sharding``."""
    mesh_state.set_mesh(jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(1, 2, 1, 2),
        ("dp", "sharding", "sep", "mp")))
    return {"state_sharding_axis": "sharding",
            "input_batch_axes": ("sharding",)}


def _train_losses(install_mesh, steps=3):
    """The toy TP step's first losses and its first gradient's norm a
    leaf (from AdamW's first moment after one step, as the benchmark
    reads it), on the mesh ``install_mesh`` installs (it returns the
    step's keywords) or, None, on one device."""
    mesh_state.set_mesh(None)
    step_kw = install_mesh() if install_mesh else {}
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=True)
    m = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion()
    opt = paddle.optimizer.AdamW(
        1e-3, parameters=m.parameters(), weight_decay=0.01)
    step = JittedTrainStep(
        m, lambda out, labels: crit(out, labels), opt, **step_kw)
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(0, 128, (4, 32)))
    losses = [float(step(ids, ids))]
    grad_norms = [np.linalg.norm(m) / (1.0 - 0.9) for m in jax.device_get(
        [s["moment1"] for s in step._s_vals])]
    losses += [float(step(ids, ids)) for _ in range(steps - 1)]
    return losses, grad_norms


@pytest.mark.parametrize("install_mesh", [_fleet_mesh, _cell_mesh])
def test_llama_jitted_hybrid_train_matches_serial(install_mesh):
    """TP(mp=2) x ZeRO(sharding=2) (x DP(2)) fully-jitted step == serial:
    the first losses and the first gradient's norms."""
    lp, gp = _train_losses(install_mesh)
    ls, gs = _train_losses(None)
    np.testing.assert_allclose(lp, ls, rtol=5e-4, atol=5e-5)
    np.testing.assert_allclose(gp, gs, rtol=5e-4, atol=5e-5)


def test_jitted_multi_step_scan_matches_single_steps():
    """run_steps (K steps per dispatch via lax.scan) == K single steps."""
    mesh_state.set_mesh(None)

    def build():
        paddle.seed(0)
        cfg = LlamaConfig.tiny(tensor_parallel=False)
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion()
        opt = paddle.optimizer.AdamW(
            1e-3, parameters=m.parameters(), weight_decay=0.01)
        return JittedTrainStep(m, lambda o, l: crit(o, l), opt)

    rng = np.random.RandomState(2)
    batches = rng.randint(0, 128, (3, 4, 32))

    s1 = build()
    singles = [float(s1(paddle.to_tensor(b), paddle.to_tensor(b)))
               for b in batches]
    s2 = build()
    multi = s2.run_steps(paddle.to_tensor(batches), paddle.to_tensor(batches))
    np.testing.assert_allclose(multi.numpy(), singles, rtol=1e-4, atol=1e-5)


def test_graft_entry_contract():
    """__graft_entry__.entry() compiles single-chip."""
    import sys, os
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (2, 128, 1024)


@pytest.mark.parametrize("gran", ["full", "full_attn", "core_attn",
                                  "selective"])
def test_recompute_granularities_match_plain(gran):
    mesh_state.set_mesh(None)

    def losses(use_recompute):
        paddle.seed(0)
        cfg = LlamaConfig.tiny(
            tensor_parallel=False, use_recompute=use_recompute,
            recompute_granularity=gran,
        )
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion()
        opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
        step = JittedTrainStep(m, lambda o, l: crit(o, l), opt)
        ids = paddle.to_tensor(
            np.random.RandomState(3).randint(0, 128, (2, 32)))
        return [float(step(ids, ids)) for _ in range(2)]

    np.testing.assert_allclose(losses(True), losses(False),
                               rtol=2e-5, atol=2e-6)


def test_bad_recompute_granularity_raises():
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_recompute=True,
                           recompute_granularity="bogus")
    m = LlamaForCausalLM(cfg)
    ids = paddle.to_tensor(np.zeros((1, 8), "int32"))
    with pytest.raises(ValueError, match="recompute_granularity"):
        m(ids)


def test_core_attn_remat_eager_grads_flow():
    """Regression: attention-only remat must register attention params
    with the tape in eager mode (bare-closure recompute froze them)."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny(tensor_parallel=False, use_recompute=True,
                           recompute_granularity="core_attn")
    m = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion()
    ids = paddle.to_tensor(np.random.RandomState(0).randint(0, 128, (2, 16)))
    loss = crit(m(ids), ids)
    loss.backward()
    q = m.llama.layers[0].self_attn.q_proj.weight
    assert q.grad is not None
    assert float(np.abs(np.asarray(q.grad._value)).sum()) > 0


def test_llama_packed_varlen_matches_per_sequence():
    """Packed cu_seqlens training path (round-4): logits of each packed
    segment must equal a separate forward of that segment alone (same
    rope restart, no cross-segment attention), and the packed criterion
    must equal the mean of per-segment shifted CE."""
    mesh_state.set_mesh(None)
    paddle.seed(11)
    cfg = LlamaConfig.tiny(tensor_parallel=False)
    model = LlamaForCausalLM(cfg)
    model.eval()

    rng = np.random.RandomState(3)
    lens = [5, 9, 2]
    T = sum(lens)
    ids_np = rng.randint(1, cfg.vocab_size, (1, T)).astype(np.int64)
    cu = np.cumsum([0] + lens).astype(np.int32)

    packed = model(paddle.to_tensor(ids_np),
                   cu_seqlens=paddle.to_tensor(cu))
    packed_np = np.asarray(packed._value)

    fwd = jax.jit(model)    # the oracle: one program a length (ROADMAP D7)
    for i in range(len(lens)):
        seg = ids_np[:, cu[i]:cu[i + 1]]
        alone = np.asarray(fwd(paddle.to_tensor(seg))._value)
        np.testing.assert_allclose(
            packed_np[:, cu[i]:cu[i + 1]], alone, rtol=2e-4, atol=2e-4)

    # criterion: boundary positions masked out
    crit = LlamaPretrainingCriterion()
    labels = paddle.to_tensor(ids_np)
    packed_loss = float(crit(packed, labels,
                             cu_seqlens=paddle.to_tensor(cu)))
    tok_losses = []
    for i in range(len(lens)):
        seg = ids_np[:, cu[i]:cu[i + 1]]
        if seg.shape[1] < 2:
            continue
        out = fwd(paddle.to_tensor(seg))
        import paddle_tpu.nn.functional as F

        per = F.cross_entropy(
            out[:, :-1, :].reshape([-1, cfg.vocab_size]),
            paddle.to_tensor(seg[:, 1:]).reshape([-1]),
            reduction="none")
        tok_losses.extend(np.asarray(per._value).tolist())
    np.testing.assert_allclose(
        packed_loss, float(np.mean(tok_losses)), rtol=2e-4, atol=2e-4)


def test_gpt_recompute_matches_plain():
    """GPT block-level remat (round 4, behind the 40.1% MFU bench
    config): full and selective must reproduce the plain loss AND grads
    (guards the bare-closure param-freezing failure mode)."""
    rng = np.random.RandomState(0)
    ids_np = rng.randint(0, 128, (2, 16))

    def loss_with(recompute, gran="full"):
        paddle.seed(0)
        cfg = GPTConfig.tiny(use_recompute=recompute,
                             recompute_granularity=gran)
        m = GPTForCausalLM(cfg)
        ids = paddle.to_tensor(ids_np)
        ce = paddle.nn.CrossEntropyLoss()
        loss = ce(m(ids).reshape([-1, cfg.vocab_size]), ids.reshape([-1]))
        loss.backward()
        return float(loss), m.gpt.blocks[0].qkv.weight.grad.numpy()

    l0, g0 = loss_with(False)
    for gran in ("full", "selective"):
        l1, g1 = loss_with(True, gran)
        assert abs(l0 - l1) < 1e-5, gran
        np.testing.assert_allclose(g0, g1, rtol=1e-4, atol=1e-6,
                                   err_msg=gran)
    with pytest.raises(ValueError, match="recompute_granularity"):
        loss_with(True, "core_attn")


def test_mistral_qwen2_style_configs():
    """Round-5 model-family knobs on the llama stack: Mistral = GQA +
    sliding window (window genuinely cuts attention), Qwen2 =
    attention_bias (q/k/v biases exist, train, and change outputs)."""
    paddle.seed(0)
    cfg_m = LlamaConfig.tiny(tensor_parallel=False, sliding_window=8)
    assert LlamaConfig.mistral_7b().sliding_window == 4096
    assert LlamaConfig.qwen2_7b().attention_bias is True

    m = LlamaForCausalLM(cfg_m)
    m.eval()
    ids = paddle.to_tensor(np.random.RandomState(3).randint(0, 128, (1, 24)))
    out = m(ids)
    assert np.isfinite(out.numpy()).all()

    # attention_bias: biases exist on q/k/v (not o), and a train step
    # moves them
    paddle.seed(0)
    cfg_q = LlamaConfig.tiny(tensor_parallel=False, attention_bias=True)
    q = LlamaForCausalLM(cfg_q)
    attn = q.llama.layers[0].self_attn
    assert attn.q_proj.bias is not None
    assert attn.k_proj.bias is not None
    assert attn.v_proj.bias is not None
    assert attn.o_proj.bias is None
    names = [n for n, _ in q.named_parameters()]
    assert any("q_proj.bias" in n for n in names)

    from paddle_tpu.nlp import LlamaPretrainingCriterion

    crit = LlamaPretrainingCriterion()
    opt = paddle.optimizer.AdamW(1e-2, parameters=q.parameters())
    b0 = attn.q_proj.bias.numpy().copy()
    loss = crit(q(ids), ids)
    loss.backward()
    opt.step()
    assert np.abs(attn.q_proj.bias.numpy() - b0).max() > 0

    # after the update the biases are nonzero → outputs differ from a
    # freshly-built no-bias model with the same seed
    paddle.seed(0)
    nb = LlamaForCausalLM(LlamaConfig.tiny(tensor_parallel=False))
    assert np.abs(q(ids).numpy() - nb(ids).numpy()).max() > 1e-6
