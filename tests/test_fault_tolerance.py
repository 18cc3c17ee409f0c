"""Fault-tolerance integration tests: checkpoint-cadenced training, injected
node failure -> elastic restart -> restore -> continue; straggler detection;
serving loop."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import config as C
from repro.runtime.failure import FailurePlan
from repro.runtime.server import Server
from repro.runtime.steps import init_train_state, train_bundle
from repro.runtime.trainer import Trainer


def _tiny_run_cfg(tmp_path, total=8, every=2, accum=1):
    entry = C.get("llama3-8b")
    shape = C.ShapeConfig("tiny_train", 32, 4, "train")
    train = C.TrainConfig(total_steps=total, warmup_steps=2,
                          checkpoint_every=every, keep_checkpoints=2,
                          checkpoint_dir=str(tmp_path), learning_rate=1e-3,
                          accum_steps=accum)
    return C.RunConfig(model=entry.smoke, shape=shape, mesh=C.SMOKE_MESH,
                       train=train)


def test_train_loop_loss_decreases(tmp_path):
    rc = _tiny_run_cfg(tmp_path / "a", total=10)
    trainer = Trainer(rc, use_mesh=False)
    report = trainer.train()
    assert report.steps_done == 10
    assert report.checkpoints >= 4
    first3 = np.mean(report.losses[:3])
    last3 = np.mean(report.losses[-3:])
    assert last3 < first3, f"loss did not fall: {first3} -> {last3}"


def test_no_checkpoints_when_cadence_is_off(tmp_path):
    rc = _tiny_run_cfg(tmp_path / "off", total=3, every=0)
    report = Trainer(rc, use_mesh=False).train()
    assert report.steps_done == 3 and report.checkpoints == 0
    assert len(report.step_times) == len(report.grad_norms) == 3
    from repro.checkpoint.store import list_steps
    assert list_steps(str(tmp_path / "off")) == []


def test_failure_recovery_resumes_from_checkpoint(tmp_path):
    rc = _tiny_run_cfg(tmp_path / "b", total=8, every=2)
    plan = FailurePlan(failures={4: 0})
    trainer = Trainer(rc, use_mesh=False, failure_plan=plan)
    report = trainer.train()
    assert report.restarts == 1
    # steps 0..4 ran, failure, restore from step-4 ckpt, re-run 4..8
    assert report.steps_done >= 8
    from repro.checkpoint.store import list_steps
    assert list_steps(str(tmp_path / "b"))[-1] == 8


def test_straggler_detection(tmp_path):
    """Simulated clock: the plan reports the delay instead of sleeping, the
    trainer folds it into the measured step time, and the rolling-median
    detector fires — same code path as a live slow host, no wall-clock."""
    rc = _tiny_run_cfg(tmp_path / "c", total=10, every=100)
    plan = FailurePlan(stragglers={7: 30.0}, simulated=True)
    trainer = Trainer(rc, use_mesh=False, failure_plan=plan,
                      straggler_factor=3.0)
    report = trainer.train()
    assert report.slow_steps >= 1, "injected straggler not detected"


def test_elastic_rescale_on_simulated_clock(tmp_path):
    """Two hosts dying in the same heartbeat window (accumulated via
    add_failure) trigger ONE elastic restart that rebuilds on the surviving
    devices and resumes from the last checkpoint — with the straggler plan
    on the simulated clock so the whole scenario runs without sleeping."""
    rc = _tiny_run_cfg(tmp_path / "e", total=8, every=2)
    plan = FailurePlan(stragglers={2: 30.0, 6: 45.0}, simulated=True)
    plan.add_failure(5)
    plan.add_failure(5)            # simultaneous: losses accumulate
    assert plan.failures == {5: 2}
    trainer = Trainer(rc, use_mesh=False, failure_plan=plan,
                      straggler_factor=3.0)
    report = trainer.train()
    assert report.restarts == 1    # one failure event, two devices lost
    assert report.steps_done >= 8
    assert report.slow_steps >= 1  # injected stragglers still detected
    from repro.checkpoint.store import list_steps
    assert list_steps(str(tmp_path / "e"))[-1] == 8


def test_grad_accum_matches_no_accum(tmp_path):
    """accum_steps=2 over the same data must closely match accum=1 (the
    batch-mean loss decomposes over microbatches)."""
    rc1 = _tiny_run_cfg(tmp_path / "d1", total=1, accum=1)
    rc2 = _tiny_run_cfg(tmp_path / "d2", total=1, accum=2)
    b1 = train_bundle(rc1).jit()
    b2 = train_bundle(rc2).jit()
    state = init_train_state(rc1, jax.random.key(0))
    batch = {
        "tokens": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                     rc1.model.vocab_size),
        "labels": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                     rc1.model.vocab_size),
    }
    s1, m1 = b1(state, batch)
    state = init_train_state(rc2, jax.random.key(0))
    s2, m2 = b2(state, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2
    w1 = jax.tree.leaves(s1.master)[0]
    w2 = jax.tree.leaves(s2.master)[0]
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2),
                               rtol=2e-2, atol=2e-4)


def test_server_generate():
    entry = C.get("llama3-8b")
    shape = C.ShapeConfig("tiny_serve", 32, 2, "prefill")
    rc = C.RunConfig(model=entry.smoke, shape=shape, mesh=C.SMOKE_MESH)
    from repro.models import build_model
    model = build_model(entry.smoke)
    params = model.init(jax.random.key(0))
    srv = Server(rc, params, eos_token=-1)
    tokens = jax.random.randint(jax.random.key(1), (2, 8), 0,
                                entry.smoke.vocab_size)
    out = srv.generate({"tokens": tokens}, max_new_tokens=4)
    assert out.shape == (2, 4)
    assert out.max() < entry.smoke.vocab_size   # padded-vocab ids masked
    assert srv.stats.decode_tok_per_s > 0
