"""Optimizer / checkpoint / fault-tolerance behaviour."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.launch.mesh import make_local_mesh
from repro.models.config import ShapeSpec
from repro.models.transformer import init_params
from repro.train.checkpoint import CheckpointManager
from repro.train.fault import (FailureInjector, Heartbeat, StragglerDetector,
                               reassign_shards)
from repro.train.optimizer import (AdamWConfig, apply_updates, global_norm,
                                   init_state, schedule)
from repro.train.train_step import make_train_step


def test_adamw_converges_quadratic():
    cfg = AdamWConfig(lr=0.1, warmup_steps=5, total_steps=200,
                      weight_decay=0.0, grad_clip=10.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_state(params)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}            # d/dw (w^2)
        params, state, _ = apply_updates(params, grads, state, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.15


def test_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=100,
                      min_lr_ratio=0.1)
    lrs = [float(schedule(cfg, jnp.asarray(s))) for s in range(0, 101, 10)]
    assert lrs[0] == 0.0
    assert max(lrs) == pytest.approx(1.0, abs=0.02)
    assert lrs[-1] == pytest.approx(0.1, abs=0.02)


def test_grad_clip_applies():
    cfg = AdamWConfig(lr=1e-3, grad_clip=1.0, weight_decay=0.0)
    params = {"w": jnp.zeros(4)}
    state = init_state(params)
    big = {"w": jnp.full(4, 1e6)}
    _, _, metrics = apply_updates(params, big, state, cfg)
    assert float(metrics["grad_norm"]) > 1e6  # reported pre-clip


def test_train_step_reduces_loss_tiny_model():
    cfg = reduced_config("qwen3-1.7b")
    mesh = make_local_mesh()
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=1e-2, warmup_steps=2,
                                                    total_steps=50),
                                   mesh, None, remat="none"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = init_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    losses = []
    for _ in range(8):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5    # memorizes the fixed batch


def test_checkpoint_roundtrip_and_resume(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}
    mgr = CheckpointManager(tmp_path, keep=2)
    mgr.save(1, tree, {"step": 1})
    tree2 = jax.tree.map(lambda x: x * 2, tree)
    mgr.save_async(2, tree2, {"step": 2})
    mgr.wait()
    assert mgr.latest_step() == 2
    restored, extra = mgr.restore(tree)
    assert extra["step"] == 2
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.asarray(tree2["a"]))
    # keep=2 gc
    mgr.save(3, tree, {"step": 3})
    mgr.save(4, tree, {"step": 4})
    steps = sorted(p.name for p in tmp_path.iterdir()
                   if p.name.startswith("step_"))
    assert len(steps) == 2


def test_checkpoint_elastic_restore_new_sharding(tmp_path):
    """Restore applies target shardings (elastic: mesh may differ)."""
    mesh = make_local_mesh()
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    tree = {"w": jnp.ones((8, 8))}
    mgr = CheckpointManager(tmp_path)
    mgr.save(5, tree)
    restored, _ = mgr.restore(tree, shardings={"w": sh})
    assert restored["w"].sharding == sh


def test_checkpoint_structure_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, {"a": jnp.ones(2)})
    with pytest.raises(ValueError):
        mgr.restore({"a": jnp.ones(2), "b": jnp.ones(2)})


def test_heartbeat_and_straggler():
    hb = Heartbeat(deadline_s=10.0)
    hb.beat(0, now=0.0)
    hb.beat(1, now=0.0)
    hb.beat(0, now=8.0)
    assert hb.dead_workers(now=12.0) == [1]

    sd = StragglerDetector(factor=1.5)
    for _ in range(10):
        sd.record(0, 1.0)
        sd.record(1, 1.0)
        sd.record(2, 4.0)
    assert sd.stragglers() == [2]


def test_reassign_shards_stable():
    a = reassign_shards(16, {0, 1, 2, 3})
    b = reassign_shards(16, {0, 1, 3})       # worker 2 died
    assert sum(len(v) for v in b.values()) == 16
    # shards previously on surviving workers move deterministically
    assert set(b) == {0, 1, 3}


def test_failure_injector_restart_from_checkpoint(tmp_path):
    """Crash at step 7 → restart resumes from the last checkpoint (step 5)."""
    mgr = CheckpointManager(tmp_path)
    inj = FailureInjector(crash_at={7: [0]})
    state = {"step": jnp.asarray(0)}
    step = 0
    restarts = 0
    while step < 10:
        if inj.crashed(step) and restarts == 0:
            restarts += 1
            restored, extra = mgr.restore(state)
            step = extra["step"]
            state = restored
            continue
        state = {"step": jnp.asarray(step + 1)}
        if (step + 1) % 5 == 0:
            mgr.save(step + 1, state, {"step": step + 1})
        step += 1
    assert restarts == 1
    assert int(state["step"]) == 10
