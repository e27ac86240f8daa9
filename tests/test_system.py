"""End-to-end system tests: train loop (loss ↓), checkpoint/elastic
restart, DS-FD training integrations, data pipeline determinism."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.data.tokens import TokenPipeline
from repro.launch.mesh import make_mesh
from repro.train import checkpoint as ckpt
from repro.train.loop import LoopConfig, train
from repro.train.train_step import TrainStepConfig


def _mesh1():
    return make_mesh((1, 1), ("data", "model"))


@pytest.fixture(scope="module")
def tiny_cfg():
    return get_config("smollm-135m").reduced()


def test_train_loss_decreases(tiny_cfg):
    res = train(tiny_cfg, _mesh1(),
                loop=LoopConfig(steps=25, log_every=100),
                seq_len=64, global_batch=8)
    losses = [h["loss"] for h in res["history"]]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses


def test_checkpoint_resume_and_elastic(tiny_cfg, tmp_path):
    d = str(tmp_path / "ck")
    r1 = train(tiny_cfg, _mesh1(),
               loop=LoopConfig(steps=6, ckpt_dir=d, ckpt_every=3),
               seq_len=32, global_batch=4)
    assert ckpt.latest_step(d) == 6
    # resume on a *different* mesh layout (elastic restart): same 1 device,
    # but a (1,) pure-data mesh exercises restore-with-resharding.
    mesh2 = make_mesh((1,), ("data",))
    r2 = train(tiny_cfg, mesh2,
               loop=LoopConfig(steps=10, ckpt_dir=d, ckpt_every=4),
               seq_len=32, global_batch=4)
    assert r2["step"] == 10
    assert np.isfinite([h["loss"] for h in r2["history"]]).all()


def test_checkpoint_atomicity(tmp_path):
    d = str(tmp_path / "ck")
    tree = {"w": jnp.arange(12.0).reshape(3, 4),
            "b": {"x": jnp.ones((2,), jnp.bfloat16)}}
    ckpt.save(d, 5, tree)
    ckpt.save(d, 9, jax.tree.map(lambda x: x * 2, tree))
    got, manifest = ckpt.restore(d, tree)
    assert manifest["step"] == 9
    np.testing.assert_allclose(np.asarray(got["w"], np.float32),
                               np.asarray(tree["w"]) * 2)
    assert got["b"]["x"].dtype == jnp.bfloat16
    # stale tmp dirs never shadow finals
    assert not [p for p in os.listdir(d) if p.startswith(".tmp")]


def test_train_with_sketch_monitor_and_compress(tiny_cfg):
    from repro.sketch import SketchConfig, CompressConfig
    tsc = TrainStepConfig(
        sketch=SketchConfig(d=64, eps=0.25, window=64),
        compress=CompressConfig(rank=4, eps=0.25, window=8,
                                min_size=2048, summary_rows=2))
    res = train(tiny_cfg, _mesh1(), loop=LoopConfig(steps=12, log_every=100),
                tsc=tsc, seq_len=32, global_batch=4)
    ms = res["history"][-1]
    assert "sketch/top_energy" in ms
    assert np.isfinite([h["loss"] for h in res["history"]]).all()
    # compression EF should not destroy optimization
    assert res["history"][-1]["loss"] < res["history"][0]["loss"] + 0.5


def test_sketchy_optimizer_trains(tiny_cfg):
    from repro.sketch import SketchyConfig, sketchy_dsfd
    opt = sketchy_dsfd(SketchyConfig(lr=2e-2, rank=4, eps=0.5, window=16,
                                     summary_rows=2, warmup=4))
    res = train(tiny_cfg, _mesh1(), loop=LoopConfig(steps=20, log_every=100),
                opt=opt, seq_len=32, global_batch=4)
    losses = [h["loss"] for h in res["history"]]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-4:]) < np.mean(losses[:4])


def test_token_pipeline_deterministic_and_shardable():
    pipe = TokenPipeline(vocab=128, seq_len=16, global_batch=8, seed=3)
    s0 = pipe.init_state()
    s1, b1 = pipe.next_batch(s0)
    _, b1b = pipe.next_batch(s0)
    np.testing.assert_array_equal(b1["tokens"], b1b["tokens"])
    _, b2 = pipe.next_batch(s1)
    assert not np.array_equal(b1["tokens"], b2["tokens"])
    sl = pipe.shard_slice(b1, 1, 4)
    np.testing.assert_array_equal(sl["tokens"], b1["tokens"][2:4])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_straggler_watchdog():
    from repro.train.loop import StragglerWatchdog
    wd = StragglerWatchdog(LoopConfig(straggler_factor=3.0))
    for _ in range(10):
        assert not wd.observe(0.1)
    assert wd.observe(1.0)
    assert wd.flagged == 1
