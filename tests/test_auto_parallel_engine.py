"""Auto-parallel Engine: fit/evaluate/predict/save/load over a device
mesh (SURVEY.md §2.3 auto-parallel row; reference
auto_parallel/static/engine.py — unverified)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.io import Dataset
from paddle_tpu.metric import Accuracy
from paddle_tpu.parallel import mesh as mesh_state
from paddle_tpu.distributed.auto_parallel import Engine


@pytest.fixture(autouse=True)
def reset_mesh():
    yield
    mesh_state.set_mesh(None)


class _ToyData(Dataset):
    def __init__(self, n=64, din=8, classes=4, seed=0):
        rng = np.random.RandomState(seed)
        self.x = rng.randn(n, din).astype("float32")
        self.y = (np.abs(self.x.sum(1)).astype("int64") % classes)

    def __len__(self):
        return len(self.x)

    def __getitem__(self, i):
        return self.x[i], self.y[i]


def _mlp(din=8, classes=4):
    paddle.seed(0)
    # the loader shuffles from numpy's global generator, whose state is
    # whatever the worker's earlier tests left: 4 of 80 states make two
    # epochs of this toy end above where they began
    np.random.seed(0)
    return nn.Sequential(
        nn.Linear(din, 32), nn.ReLU(), nn.Linear(32, classes)
    )


def _loss():
    ce = nn.CrossEntropyLoss()
    return lambda out, label: ce(out, label)


def test_engine_fit_decreases_loss():
    model = _mlp()
    opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())
    eng = Engine(model, _loss(), opt)
    assert eng._mesh is not None  # default dp mesh over all devices
    hist = eng.fit(_ToyData(), batch_size=16, epochs=4, verbose=0)
    assert hist["loss"][-1] < hist["loss"][0]


def test_engine_evaluate_and_predict():
    model = _mlp()
    opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())
    eng = Engine(model, _loss(), opt, metrics=[Accuracy()])
    eng.fit(_ToyData(), batch_size=16, epochs=3, verbose=0)
    res = eng.evaluate(_ToyData(seed=1), batch_size=16, verbose=0)
    assert "loss" in res and "acc" in res
    outs = eng.predict(_ToyData(seed=1), batch_size=16)
    assert len(outs) == 4 and outs[0].shape == [16, 4]


def test_engine_fleet_strategy_mesh():
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        "dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
    }
    model = _mlp()
    opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())
    eng = Engine(model, _loss(), opt, strategy=strategy)
    assert eng._mesh.shape["dp"] == 4 and eng._mesh.shape["mp"] == 2
    hist = eng.fit(_ToyData(), batch_size=16, epochs=2, verbose=0)
    assert np.isfinite(hist["loss"][-1])


def test_engine_save_load_roundtrip(tmp_path):
    model = _mlp()
    opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())
    eng = Engine(model, _loss(), opt)
    eng.fit(_ToyData(), batch_size=16, epochs=1, verbose=0)
    ref = eng.evaluate(_ToyData(seed=1), batch_size=16, verbose=0)["loss"]
    eng.save(str(tmp_path / "ckpt"))

    model2 = _mlp()
    opt2 = paddle.optimizer.Adam(1e-2, parameters=model2.parameters())
    eng2 = Engine(model2, _loss(), opt2)
    eng2.load(str(tmp_path / "ckpt"))
    got = eng2.evaluate(_ToyData(seed=1), batch_size=16, verbose=0)["loss"]
    np.testing.assert_allclose(got, ref, rtol=1e-6)


def test_engine_shard_tensor_annotated_model():
    """shard_tensor-annotated weights flow through Engine.fit (GSPMD
    plans the collectives — reference planner/partitioner analog)."""
    from paddle_tpu.distributed.auto_parallel import (
        ProcessMesh, shard_tensor, Shard,
    )

    mesh = ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]], dim_names=["dp", "mp"])
    model = _mlp()
    shard_tensor(model[0].weight, mesh, [Shard(1)])
    shard_tensor(model[2].weight, mesh, [Shard(0)])
    opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())
    eng = Engine(model, _loss(), opt, mesh=mesh)
    hist = eng.fit(_ToyData(), batch_size=16, epochs=2, verbose=0)
    assert hist["loss"][-1] < hist["loss"][0]


def test_engine_fit_with_validation_data():
    """Per-epoch evaluate must read the live (donated) train-step params."""
    model = _mlp()
    opt = paddle.optimizer.Adam(1e-2, parameters=model.parameters())
    eng = Engine(model, _loss(), opt, metrics=[Accuracy()])
    hist = eng.fit(
        _ToyData(), valid_data=_ToyData(seed=1), batch_size=16, epochs=3,
        verbose=0,
    )
    assert len(hist["loss"]) == 3 and len(hist["val_acc"]) == 3
    assert len(hist["val_loss"]) == 3


def test_engine_predict_keeps_partial_batch():
    model = _mlp()
    eng = Engine(model, _loss(), paddle.optimizer.Adam(
        1e-2, parameters=model.parameters()))
    outs = eng.predict(_ToyData(n=50), batch_size=16)
    total = sum(o.shape[0] for o in outs)
    assert total == 50  # 16+16+16+2 — final partial batch kept


def test_engine_missing_data_raises():
    model = _mlp()
    eng = Engine(model, _loss(), paddle.optimizer.Adam(
        1e-2, parameters=model.parameters()))
    with pytest.raises(ValueError, match="train_data"):
        eng.fit()
    with pytest.raises(ValueError, match="valid_data"):
        eng.evaluate()
    with pytest.raises(ValueError, match="test_data"):
        eng.predict()
