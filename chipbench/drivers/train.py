"""Pre-training through the program's ``Trainer.train`` on a mesh of every
chip, as ``repro.launch.train`` builds it.

One ``Trainer.train`` call runs every step of the run, so that the compiled
step and its state are one object from the seed to the end.  Its first
``check_steps`` steps are set-up: the first loads the compiled program, and
the float32 reference follows all of them afterwards.  The window is the
steps after them, as many as fill ``--seconds`` at the step time the
workload file records.

``Trainer`` gives no access to its state, so the driver wraps the jitted
step that ``train_bundle`` returns (``StepProbe``): during the set-up steps
it keeps the rows each step was fed, the first parameters, the norm of each
parameter's first Adam moment after step 1 and, after the last set-up step,
how far each master weight has moved.  A ``FailurePlan`` that injects
nothing stamps the start of every step, which starts and stops the profiler
in a traced run.

Once the window has closed and the memory peak is read, the state is gone
with the ``Trainer``, and the reference (``reference/rwkv6.py``) redraws the
first state from the seed and follows the set-up steps on the same rows.
"""
from __future__ import annotations

import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

KIND = "train"
SPAN_DISPATCH = "bench.step_dispatch"
#: leaves whose reference gradient is below this share of the median leaf's
#: move under Adam by round-off alone and are left out of the change
STILL_LEAF = 1e-3
CKPT_DIR = Path(__file__).resolve().parents[2] / "experiments" / "chipbench" / "ckpt"


def model_config(cell):
    from repro import config as C
    return C.get(cell.config["arch"]).full.replace(**cell.config["model"])


def lm_batches(vocab: int, batch: int, seq: int, seed: int):
    """The rows the program's synthetic LM stream yields for ``seed``: a
    Zipf unigram over at most 4096 ids, 30% of tokens tied to the one
    before.  A copy, so that the rows the reference follows are not the
    program's own."""
    rng = np.random.default_rng(seed)
    support = min(vocab, 4096)
    probs = 1.0 / np.arange(1, support + 1, dtype=np.float64)
    probs /= probs.sum()
    while True:
        base = rng.choice(support, size=(batch, seq + 1), p=probs)
        prev = np.roll(base, 1, axis=1)
        mix = rng.random((batch, seq + 1)) < 0.3
        toks = np.where(mix, (prev * 17 + 3) % support, base).astype(np.int32)
        yield toks[:, :-1], toks[:, 1:]


class StepProbe:
    """Wraps the jitted train step to read the state during set-up."""

    def __init__(self, check_steps: int):
        self.check_steps = check_steps
        self.calls = 0
        self.rows: List[Dict[str, np.ndarray]] = []
        self.first = None
        self.moment_sq: Optional[Dict[str, float]] = None
        self.change_sq: Optional[Dict[str, float]] = None

    def bundle(self, original):
        probe = self

        def train_bundle(rc, mesh=None):
            b = original(rc, mesh)

            class Bundle:
                def jit(self):
                    return probe.wrap(b.jit())

                def __getattr__(self, name):
                    return getattr(b, name)

            return Bundle()
        return train_bundle

    def wrap(self, step_fn):
        import jax
        import jax.numpy as jnp

        sqnorms = jax.jit(lambda t: jax.tree.map(
            lambda a: jnp.sum(jnp.square(a.astype(jnp.float32))), t))
        moved = jax.jit(lambda master, first: jax.tree.map(
            lambda a, b: jnp.sum(jnp.square(a - b)), master, first))

        def step(state, batch):
            i = self.calls
            self.calls += 1
            if i < self.check_steps:
                self.rows.append({k: np.asarray(jax.device_get(v))
                                  for k, v in batch.items()})
            if i == 0:
                # the step donates its state: keep a copy of the first master
                # weights (on the TPU they are the unrounded draw, not the
                # bfloat16 parameters widened)
                self.first = jax.tree.map(jnp.copy, state.master)
            with jax.profiler.TraceAnnotation(SPAN_DISPATCH):
                out = step_fn(state, batch)
            if i == 0:
                self.moment_sq = by_path(jax.device_get(sqnorms(out[0].m)))
            if i == self.check_steps - 1:
                self.change_sq = by_path(jax.device_get(moved(out[0].master, self.first)))
                self.first = None
            return out

        return step


def by_path(tree) -> Dict[str, float]:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path): float(v) for path, v in flat}


@dataclass
class StepClock:
    """Host time at the start of every step; starts the profiler at
    ``trace_from`` and stops it at ``trace_to``."""
    tracer: Any = None
    trace_from: int = -1
    trace_to: int = -1
    stamps: List[float] = field(default_factory=list)
    _window: Any = None

    def at(self, step: int):
        import jax
        if step == self.trace_to and self._window is not None:
            self._window.__exit__(None, None, None)
            self.tracer.stop()
        self.stamps.append(time.perf_counter())
        if step == self.trace_from:
            self.tracer.start()
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()

    def close(self):
        """Stop a trace that the last step left open."""
        if self._window is not None and not self.tracer.stopped:
            self._window.__exit__(None, None, None)
            self.tracer.stop()


def failure_plan(clock: StepClock):
    from repro.runtime.failure import FailurePlan

    class Clocked(FailurePlan):
        def straggle(self, step: int) -> float:
            clock.at(step)
            return 0.0

    return Clocked()


def run_trainer(rc, probe: StepProbe, clock: StepClock):
    """``Trainer(rc).train()`` with the probe around its jitted step."""
    from repro.runtime import steps, trainer
    shutil.rmtree(rc.train.checkpoint_dir, ignore_errors=True)
    saved = trainer.train_bundle
    trainer.train_bundle = probe.bundle(steps.train_bundle)
    try:
        return trainer.Trainer(rc, failure_plan=failure_plan(clock)).train()
    finally:
        trainer.train_bundle = saved


def run_config(cell, seed: int, total_steps: int):
    import jax
    from repro import config as C
    from repro.distributed.mesh import make_mesh_config
    tr = cell.traffic
    train = C.TrainConfig(total_steps=total_steps, checkpoint_every=0,
                          checkpoint_dir=str(CKPT_DIR), seed=seed)
    return C.RunConfig(model=model_config(cell),
                       shape=C.ShapeConfig("bench", tr["seq_len"], tr["batch"], "train"),
                       mesh=make_mesh_config(len(jax.devices())), train=train)


def run(cell, *, seed: int, seconds: float, tracer, t0: float,
        memory_peak) -> Dict[str, Any]:
    tr = cell.traffic
    K = tr["check_steps"]
    n_window = max(1, round(seconds / tr["step_seconds"]))
    rc = run_config(cell, seed, K + n_window)
    probe = StepProbe(K)
    traced = (K + 1, min(K + 3, K + n_window)) if tracer.enabled else (-1, -1)
    clock = StepClock(tracer, *traced)
    report = run_trainer(rc, probe, clock)
    t_end = time.perf_counter()
    clock.close()
    peak = memory_peak()

    tokens = tr["batch"] * tr["seq_len"]
    window_losses = report.losses[K:]
    failed = int(np.sum(~np.isfinite(window_losses)))
    t = time.perf_counter()
    checks = compare(cell, seed, rc, probe, report.losses[:K])
    print(f"reference_s {time.perf_counter() - t}", file=sys.stderr)
    t_start = clock.stamps[K]
    return {
        "metrics": {
            "train_tokens_per_s": (n_window * tokens / (t_end - t_start), "tokens/s"),
            "setup_s": (t_start - t0, "s"),
        },
        "attempted": n_window,
        "failed": failed,
        "checks": checks + [("window_nonfinite_losses", float(failed), 0.0)],
        "memory_peak_bytes": peak,
        "window": (t_start, t_end),
        "counts": {"traced_tokens": (traced[1] - traced[0]) * tokens,
                   "step_times": report.step_times},
    }


def reference_readings(cell, seed: int, rc, rows, quantize: bool = False):
    """The reference's losses, clipped first-gradient and change norms by
    path, following the program's set-up steps on ``rows``."""
    from chipbench.reference.rwkv6 import Reference
    t = rc.train
    ref = Reference(cell.config["model"],
                    {"learning_rate": t.learning_rate, "warmup_steps": t.warmup_steps,
                     "beta1": t.beta1, "beta2": t.beta2, "eps": t.eps,
                     "weight_decay": t.weight_decay, "grad_clip": t.grad_clip},
                    seed, quantize=quantize)
    losses, grad_sq = [], None
    for r in rows:
        loss, sq = ref.step(r["tokens"], r["labels"])
        losses.append(loss)
        grad_sq = grad_sq or sq
    return losses, grad_sq, ref.change_sqnorms()


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float], leaves,
               relative: bool = False) -> float:
    """By the worst leaf, the gap between the program's and the reference's
    norm, against the larger of that leaf's and the median leaf's reference
    norm.  ``relative`` first divides each side by its own median leaf's
    norm, which takes out a scale common to every leaf."""
    p = {k: np.sqrt(prog[k]) for k in leaves}
    r = {k: np.sqrt(ref[k]) for k in leaves}
    if relative:
        mp, mr = np.median(list(p.values())), np.median(list(r.values()))
        if not mp > 0:
            return float("inf")
        p = {k: v / mp for k, v in p.items()}
        r = {k: v / mr for k, v in r.items()}
    med = float(np.median(list(r.values())))
    return float(max(abs(p[k] - r[k]) / max(r[k], med) for k in leaves))


def gaps(prog_losses, prog_grad_sq, prog_change_sq, losses, grad_sq, change_sq):
    """The numbers compared: the worst relative loss gap over the steps; by
    the worst leaf, the gaps of the first clipped gradient's norm, absolute
    and relative to the median leaf (clipping divides every leaf by one
    global norm); and the gap of the norm of the weights' change, over the
    leaves the reference's gradient moves."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog_losses, losses))
    g = {k: np.sqrt(v) for k, v in grad_sq.items()}
    med_g = float(np.median(list(g.values())))
    moving = [k for k in change_sq if g[k] >= STILL_LEAF * med_g]
    return {"loss_gap": float(loss_gap),
            "grad_gap": worst_leaf(prog_grad_sq, grad_sq, list(grad_sq)),
            "grad_shape_gap": worst_leaf(prog_grad_sq, grad_sq, list(grad_sq), True),
            "change_gap": worst_leaf(prog_change_sq, change_sq, moving)}


def program_readings(probe: StepProbe, beta1: float):
    """The program's first clipped gradient, from its first Adam moment
    m_1 = (1 - beta1) g_1."""
    grad_sq = {k: v / (1 - beta1) ** 2 for k, v in probe.moment_sq.items()}
    return grad_sq, probe.change_sq


def compare(cell, seed: int, rc, probe: StepProbe, prog_losses) -> list:
    tr = cell.traffic
    K = tr["check_steps"]
    want = lm_batches(rc.model.vocab_size, tr["batch"], tr["seq_len"], seed)
    rows = []
    mismatch = 0
    for fed in probe.rows[:K]:
        tokens, labels = next(want)
        mismatch += int(np.sum(fed["tokens"] != tokens) + np.sum(fed["labels"] != labels))
        rows.append({"tokens": tokens, "labels": labels})
    checks = [("rows_not_as_seeded", float(mismatch), 0.0)]
    losses, grad_sq, change_sq = reference_readings(cell, seed, rc, rows)
    prog_grad_sq, prog_change_sq = program_readings(probe, rc.train.beta1)
    print(f"losses program {prog_losses} reference {losses}", file=sys.stderr)
    for k in sorted(grad_sq):
        print(f"leaf {k} grad {np.sqrt(prog_grad_sq[k])} {np.sqrt(grad_sq[k])} "
              f"change {np.sqrt(prog_change_sq[k])} {np.sqrt(change_sq[k])}",
              file=sys.stderr)
    got = gaps(prog_losses, prog_grad_sq, prog_change_sq, losses, grad_sq, change_sq)
    print(f"gaps {got}", file=sys.stderr)
    limits = tr["limits"]
    return checks + [(k, got[k], limits[k]) for k in limits]


def readings(cell, seed: int) -> Dict[str, Any]:
    """The program's and the control's readings on one seed: the set-up
    steps only, no measured window."""
    tr = cell.traffic
    K = tr["check_steps"]
    rc = run_config(cell, seed, K)
    probe = StepProbe(K)
    report = run_trainer(rc, probe, StepClock())
    rows = [{"tokens": r["tokens"], "labels": r["labels"]} for r in probe.rows]
    losses, grad_sq, change_sq = reference_readings(cell, seed, rc, rows)
    prog = program_readings(probe, rc.train.beta1)
    low = reference_readings(cell, seed, rc, rows, quantize=True)
    return {"program": gaps(report.losses[:K], *prog, losses, grad_sq, change_sq),
            "control": gaps(low[0], low[1], low[2], losses, grad_sq, change_sq)}
