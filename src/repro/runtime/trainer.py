"""Fault-tolerant training loop.

Responsibilities: build mesh + step, stream data, checkpoint at cadence,
detect injected/real failures, elastically rebuild on fewer devices, restore,
and continue — plus straggler-deadline monitoring (per-step wall-clock vs a
rolling median; slow steps are logged and counted, the real-cluster analogue
being reassignment of that host's data shard).
"""
from __future__ import annotations

import logging
import statistics
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager, latest_step, restore_resharded
from repro.config import RunConfig
from repro.data.pipeline import DataPipeline
from repro.data.synthetic import batches_for
from repro.distributed.mesh import build_mesh
from repro.distributed.sharding import logical_rules, param_shardings
from repro.models import build_model
from repro.optim import abstract_state, state_axes
from repro.runtime.failure import FailurePlan, NodeFailure
from repro.runtime.steps import init_train_state, train_bundle

log = logging.getLogger("repro.trainer")


@dataclass
class TrainReport:
    steps_done: int = 0
    restarts: int = 0
    final_loss: float = float("nan")
    losses: List[float] = field(default_factory=list)
    grad_norms: List[float] = field(default_factory=list)
    #: wall seconds per step, until its state and metrics are on the device
    step_times: List[float] = field(default_factory=list)
    slow_steps: int = 0
    checkpoints: int = 0


class Trainer:
    def __init__(self, run_cfg: RunConfig, use_mesh: bool = True,
                 failure_plan: Optional[FailurePlan] = None,
                 straggler_factor: float = 3.0):
        self.run_cfg = run_cfg
        self.use_mesh = use_mesh
        self.failure_plan = failure_plan or FailurePlan()
        self.straggler_factor = straggler_factor
        self.report = TrainReport()

    # -- setup ---------------------------------------------------------------
    def _build(self, num_devices: Optional[int] = None):
        rc = self.run_cfg
        mesh = None
        if self.use_mesh:
            devices = jax.devices()[:num_devices] if num_devices else None
            mesh = build_mesh(rc.mesh, devices=devices, allow_fewer=True)
        bundle = train_bundle(rc, mesh)
        step_fn = bundle.jit()
        model = build_model(rc.model, rc.sharding)
        rules = logical_rules(rc.mesh, rc.sharding)
        rules.update(model.logical_overrides(rc.mesh))
        _, batch_axes = model.train_input_specs(rc.shape)
        data = DataPipeline(batches_for(rc.model, rc.shape, rc.train.seed),
                            batch_axes, rules, mesh)
        return mesh, step_fn, data, model, rules

    def _init_or_restore(self, model, mesh, rules):
        rc = self.run_cfg
        ckpt_dir = rc.train.checkpoint_dir
        last = latest_step(ckpt_dir)
        abstract = abstract_state(model.abstract())
        if last is None:
            state = init_train_state(rc, jax.random.key(rc.train.seed), mesh)
            return state, 0
        if mesh is not None:
            shardings = param_shardings(state_axes(model.axes()), rules, mesh)
        else:
            shardings = jax.tree.map(lambda _: jax.devices()[0], abstract)
        state = restore_resharded(ckpt_dir, last, abstract, shardings)
        log.info("restored step %d from %s", last, ckpt_dir)
        return state, last

    # -- loop ----------------------------------------------------------------
    def train(self, num_steps: Optional[int] = None) -> TrainReport:
        rc = self.run_cfg
        total = num_steps or rc.train.total_steps
        ckpt = CheckpointManager(rc.train.checkpoint_dir, rc.train.checkpoint_every,
                                 rc.train.keep_checkpoints,
                                 async_write=rc.train.async_checkpoint)
        num_devices = None
        while True:
            mesh, step_fn, data, model, rules = self._build(num_devices)
            state, start = self._init_or_restore(model, mesh, rules)
            try:
                for step in range(start, total):
                    t0 = time.time()
                    # live plans sleep here; simulated plans only report the
                    # injected seconds, folded into the measured step time
                    # below so the straggler detector sees the same signal
                    injected = self.failure_plan.straggle(step)
                    batch = next(data)
                    state, metrics = step_fn(state, batch)
                    self.failure_plan.check(step)
                    jax.block_until_ready(state)
                    metrics = jax.device_get(metrics)
                    self.report.losses.append(float(metrics["loss"]))
                    self.report.grad_norms.append(float(metrics["grad_norm"]))
                    dt = time.time() - t0
                    if self.failure_plan.simulated:
                        dt += injected
                    self._note_step_time(step, dt)
                    if ckpt.maybe_save(step + 1, state):
                        self.report.checkpoints += 1
                    self.report.steps_done += 1
                data.close()
                if rc.train.checkpoint_every > 0:
                    ckpt.maybe_save(total, state, force=True)
                ckpt.wait()
                self.report.final_loss = self.report.losses[-1] if self.report.losses else float("nan")
                return self.report
            except NodeFailure as e:
                # elastic restart: drop the lost devices, rebuild smaller mesh
                data.close()
                ckpt.wait()
                self.report.restarts += 1
                avail = len(jax.devices()) - e.lost_devices
                num_devices = max(avail, 1)
                log.warning("failure at step %d -> elastic restart on %d devices",
                            e.step, num_devices)

    def _note_step_time(self, step: int, dt: float):
        self.report.step_times.append(dt)
        window = self.report.step_times[-21:-1]
        if len(window) >= 5:
            med = statistics.median(window)
            if dt > self.straggler_factor * med:
                self.report.slow_steps += 1
                log.warning("straggler: step %d took %.3fs (median %.3fs)",
                            step, dt, med)
