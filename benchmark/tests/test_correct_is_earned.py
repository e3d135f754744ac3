"""`correct` is a comparison that has been shown to fail. Two tests drive
the whole of a run but the look for a chip (``run.run_cell`` on the CPU, toy
cells of ``benchmark/rehearsal.json``, kernels interpreted); a third holds
the served check's blocks of rows to the whole:

* the CONTROL, the reference put in the program's place one precision lower
  (both operands of every matrix product rounded to int8), comes out not
  correct while the program itself comes out correct;
* with the timed path BROKEN underneath (a training step that returns its
  state unchanged; a served model whose output head differs from the
  seed's), `correct` comes out false.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import selfcheck  # noqa: E402

SEED = 2147483777


@pytest.fixture(scope="module")
def run():
    return selfcheck.load_run()


@pytest.fixture(scope="module")
def index(run):
    return selfcheck.rehearsal_index(run)


@pytest.mark.parametrize("workload", ["toy.train", "toy.batches"])
def test_control_comes_out_not_correct(run, index, workload):
    out = selfcheck.rehearse_cell(run, index, workload, SEED, trace=0,
                                  control=1)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["control_correct"] is False


def test_step_that_returns_its_state_unchanged(run, index, monkeypatch):
    from paddle_tpu.jit.train import JittedTrainStep

    real = JittedTrainStep.run_steps

    def frozen(self, inputs, labels):
        state = (self._p_vals, self._s_vals, self._b_vals, self._step_no)
        # the program still computes a loss, on copies it throws away
        self._p_vals, self._s_vals, self._b_vals = __import__("jax").tree.map(
            lambda a: a.copy(), state[:3])
        losses = real(self, inputs, labels)
        self._p_vals, self._s_vals, self._b_vals, self._step_no = state
        return losses

    monkeypatch.setattr(JittedTrainStep, "run_steps", frozen)
    out = selfcheck.rehearse_cell(run, index, "toy.train", SEED, trace=0,
                                  control=0)
    assert out["correct"] is False


def test_served_token_altered_where_it_is_produced(run, index, monkeypatch):
    real = run.load_by_name

    def load(folder, name):
        mod = real(folder, name)
        if folder == "families":
            install = mod.install_weights

            def broken(model, cfg, seed):
                out = install(model, cfg, seed)
                head = dict(model.named_parameters())["lm_head.weight"]
                head._value = head._value[:, ::-1]  # every logit moves
                return out

            mod.install_weights = broken
        return mod

    monkeypatch.setattr(run, "load_by_name", load)
    out = selfcheck.rehearse_cell(run, index, "toy.batches", SEED, trace=0,
                                  control=0)
    assert out["correct"] is False


def test_served_check_reads_the_same_in_blocks_of_rows(run):
    """Five requests of two shapes, read in blocks of two rows and in one
    block per shape: the same gaps, the control's too."""
    import numpy as np

    cfg = run.load_json("benchmark", "configs", "toy-bias.json")
    fam = run.load_by_name("families", cfg["family"])
    rng = np.random.default_rng(SEED)
    rows = [(rng.integers(1, cfg["vocab_size"], p, dtype=np.int32),
             rng.integers(1, cfg["vocab_size"], t, dtype=np.int32))
            for p, t in [(24, 8), (24, 8), (12, 6), (24, 8), (12, 6)]]
    get_leaf = fam.leaf_reader(cfg, SEED)
    whole = fam.reference.gap_below_best(cfg, get_leaf, rows, control=True)
    split = fam.reference.gap_below_best(cfg, get_leaf, rows, control=True,
                                         block_rows=2)
    assert whole[0].shape == (36,) and float(whole[0].max()) > 0
    for a, b in zip(whole, split):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
