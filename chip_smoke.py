"""Drive the serve, train and simulate paths once on a TPU and check them.

    python chip_smoke.py               # one chip: serve, train, simulate, kernels
    python chip_smoke.py --four-chips  # four chips: sharded rwkv6-1.6b training

One process holds the chip and everything runs in it.  Where JAX finds no
TPU the script exits non-zero before any phase runs.  Every phase raises on
failure; the last line of standard output is one JSON object naming the
device, printed only when every phase passed.  Compile seconds are XLA's
backend compile time, persistent-cache reads included, so a second run with
a warm ``.jax_cache`` prints smaller ones.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import config as C  # noqa: E402
from repro.core import Simulator, chip_for_device_kind  # noqa: E402
from repro.distributed.mesh import build_mesh, make_mesh_config  # noqa: E402
from repro.kernels.flash_attention import attention_ref, flash_attention  # noqa: E402
from repro.kernels.tiled_matmul import matmul, matmul_ref  # noqa: E402
from repro.kernels.winograd import conv3x3_ref, conv3x3_winograd  # noqa: E402
from repro.launch.serve import build_server, random_prompts  # noqa: E402
from repro.models import build_model  # noqa: E402
from repro.optim import abstract_state  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.runtime.server import Server, ServeStats  # noqa: E402
from repro.runtime.steps import train_bundle  # noqa: E402
from repro.runtime.trainer import Trainer  # noqa: E402

OUT_DIR = ROOT / "experiments" / "chip_smoke"

#: share of float32 decode tokens that must be the full-sequence argmax
MIN_AGREEMENT = 0.90
#: share of positions by which the bf16 decode tokens may match the float32
#: argmax less often than the bf16 forward pass does: about three binomial
#: standard deviations of a 0.3 share over 256 positions
MAX_BF16_SHORTFALL = 0.1
#: steps whose time includes compilation, left out of the median
WARMUP_STEPS = 3
#: relative error allowed between a Pallas kernel and its oracle
KERNEL_RTOL = 2e-2
#: relative error allowed between the sharded and the one-device train step
#: (on four v5e chips they differed by at most 6.6e-6 in the loss and
#: 1.3e-4 in the gradient norm)
SHARDED_RTOL = {"loss": 1e-5, "grad_norm": 1e-3}


class CompileClock:
    """Running total of XLA backend compile seconds in this process."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == self.EVENT:
            self.seconds += duration


def log(phase: str, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def teacher_forced_logits(model_cfg, params, prompts, out):
    """Float32 logits of one full-sequence forward pass over the prompts and
    all but the last generated token: ``[:, j]`` predicts ``out[:, j]``."""
    model = build_model(model_cfg)
    first = prompts["tokens"].shape[1] - 1
    seq = jnp.concatenate([prompts["tokens"], jnp.asarray(out[:, :-1])], axis=1)
    fwd = jax.jit(lambda p, t: model.forward(p, t)[0][
        :, first:, :model_cfg.vocab_size].astype(jnp.float32))
    return fwd(params, seq)


def argmax_agreement(logits, tokens) -> float:
    return float(np.mean(np.asarray(jnp.argmax(logits, -1)) == np.asarray(tokens)))


def phase_serve(model_cfg, *, batch: int, prompt_len: int, max_new: int):
    """Greedy generation through ``Server``, checked against a full-sequence
    forward pass over the prompt and the generated tokens.

    The witness is the same ``Server`` on the same weights with float32
    activations and full-precision matmuls: there the decode path must pick
    the forward pass's argmax.  bf16 rounding through a deep stack of random
    weights moves the logits further than the gaps between the top tokens
    (on a v5e at 40 layers the bf16 forward pass matched the float32 argmax
    at 0.30 of positions), so in bf16 the decode path is held to matching
    the float32 argmax about as often as the bf16 forward pass does."""
    server = build_server(model_cfg, batch=batch, prompt_len=prompt_len,
                          max_new=max_new)
    prompts = random_prompts(model_cfg, batch=batch, prompt_len=prompt_len)
    t0 = time.time()
    server.generate(prompts, max_new_tokens=max_new)       # warm-up: compiles
    warmup_s = time.time() - t0
    server.stats = ServeStats()
    out = server.generate(prompts, max_new_tokens=max_new)
    if out.shape != (batch, max_new):
        raise AssertionError(f"generated {out.shape}, want {(batch, max_new)}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    logits16 = teacher_forced_logits(model_cfg, server.params, prompts, out)

    f32_cfg = model_cfg.replace(dtype="float32")
    with jax.default_matmul_precision("float32"):
        witness = Server(dataclasses.replace(server.run_cfg, model=f32_cfg),
                         server.params)
        out32 = witness.generate(prompts, max_new_tokens=max_new)
        logits32 = teacher_forced_logits(f32_cfg, server.params, prompts, out32)
        # the float32 forward pass over the bf16 run's tokens
        ref32 = teacher_forced_logits(f32_cfg, server.params, prompts, out)
    finite = all(bool(jnp.isfinite(x).all()) for x in (logits16, logits32, ref32))
    res = {"prefill_s": server.stats.prefill_s,
           "decode_tok_per_s": server.stats.decode_tok_per_s,
           "f32_argmax_agreement": argmax_agreement(logits32, out32),
           "bf16_argmax_agreement": argmax_agreement(logits16, out),
           "bf16_forward_vs_f32": argmax_agreement(
               logits16, jnp.argmax(ref32, -1)),
           "bf16_decode_vs_f32": argmax_agreement(ref32, out),
           "bf16_logit_error": float(jnp.max(jnp.abs(logits16 - ref32))),
           "peak_bytes_in_use": peak}
    log("serve", model=model_cfg.name, layers=model_cfg.num_layers,
        vocab=model_cfg.vocab_size, batch=batch, prompt_len=prompt_len,
        new_tokens=max_new, warmup_s=warmup_s,
        logit_std=float(jnp.std(ref32)), **res)
    if not finite:
        raise AssertionError("full-sequence logits are not all finite")
    if res["f32_argmax_agreement"] < MIN_AGREEMENT:
        raise AssertionError(f"float32 decode/forward argmax agreement "
                             f"{res['f32_argmax_agreement']} < {MIN_AGREEMENT}")
    shortfall = res["bf16_forward_vs_f32"] - res["bf16_decode_vs_f32"]
    if shortfall > MAX_BF16_SHORTFALL:
        raise AssertionError(f"bf16 decode matches the float32 argmax "
                             f"{shortfall} less often than the bf16 forward "
                             f"pass, more than {MAX_BF16_SHORTFALL}")
    return res


def _train(rc, ckpt_dir: Path):
    """``Trainer(rc)`` from a fresh checkpoint directory, every step run."""
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    report = Trainer(rc).train()
    if report.steps_done != rc.train.total_steps:
        raise AssertionError(f"{report.steps_done} of {rc.train.total_steps} steps")
    return report


def phase_train(model_cfg, *, steps: int, ckpt_dir: Path):
    """``steps`` steps through ``Trainer`` on a mesh of the devices present,
    as ``repro.launch.train`` builds it; returns the config and median step."""
    train = C.TrainConfig(total_steps=steps, checkpoint_dir=str(ckpt_dir))
    rc = C.RunConfig(model=model_cfg, shape=C.TRAIN_4K,
                     mesh=make_mesh_config(len(jax.devices())), train=train)
    report = _train(rc, ckpt_dir)
    losses = report.losses
    step_s = statistics.median(report.step_times[WARMUP_STEPS:])
    first, last = float(np.mean(losses[:5])), float(np.mean(losses[-5:]))
    log("train", model=model_cfg.name, batch=rc.shape.global_batch,
        mesh="x".join(map(str, rc.mesh.shape)), steps=report.steps_done,
        loss_first5=first, loss_last5=last, median_step_s=step_s,
        checkpoints=report.checkpoints)
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss: {losses}")
    if not last < first:
        raise AssertionError(f"loss did not fall: first5 {first} last5 {last}")
    return rc, step_s


def phase_simulate(rc, hw, measured_s: float):
    """Capture the same train step on the default backend and price it."""
    mesh = build_mesh(rc.mesh, allow_fewer=True)
    sim = Simulator(hw=hw)
    cap = sim.capture_bundle(train_bundle(rc, mesh), name="lenet_train",
                             mesh=mesh)
    rep = sim.performance(cap)
    ops = sum(cap.module.op_census().values())
    log("simulate", hw=hw.name, hlo_ops=ops, timeline_entries=len(rep.timeline),
        simulated_step_s=rep.total_seconds, measured_step_s=measured_s)
    if ops <= 0 or not rep.total_seconds > 0:
        raise AssertionError(f"simulator priced {ops} ops at {rep.total_seconds}s")
    return rep.total_seconds


def kernel_cases(model_cfg, *, seq: int, conv_shape):
    """(name, kernel, oracle, args) at the attention and FFN widths of
    ``model_cfg``; the Winograd 3x3 conv on a (b, h, w, c) ``conv_shape``
    input with as many output channels."""
    h, d = model_cfg.num_heads, model_cfg.resolved_head_dim
    keys = jax.random.split(jax.random.key(0), 7)
    bf16 = jnp.bfloat16
    qkv = [jax.random.normal(k, (1, h, seq, d), bf16) for k in keys[:3]]
    a = jax.random.normal(keys[3], (seq, model_cfg.d_model), bf16)
    b = jax.random.normal(keys[4], (model_cfg.d_model, model_cfg.d_ff), bf16)
    ch = conv_shape[-1]
    x = jax.random.normal(keys[5], conv_shape, jnp.float32)
    w = jax.random.normal(keys[6], (3, 3, ch, ch), jnp.float32)
    return [
        ("flash_attention", lambda q, k, v: flash_attention(q, k, v, True),
         lambda q, k, v: attention_ref(q, k, v, causal=True), qkv),
        ("tiled_matmul", matmul, matmul_ref, [a, b]),
        ("winograd", conv3x3_winograd, conv3x3_ref, [x, w]),
    ]


def phase_kernels(cases):
    """Run each Pallas kernel beside its oracle.  ``compiled`` says whether
    the executable holds a TPU kernel rather than an interpreted body."""
    results = {}
    for name, kernel, oracle, args in cases:
        compiled = jax.jit(kernel).lower(*args).compile()
        got = compiled(*args)
        err = rel_err(got, jax.jit(oracle)(*args))
        on_chip = "tpu_custom_call" in compiled.as_text()
        log("kernel", name=name, shapes=[tuple(a.shape) for a in args],
            compiled=on_chip, rel_err=err)
        if err > KERNEL_RTOL:
            raise AssertionError(f"{name}: rel err {err} > {KERNEL_RTOL}")
        results[name] = {"compiled": on_chip, "rel_err": err}
    return results


def phase_four_chips(model_cfg, *, batch: int, seq: int, steps: int,
                     cut_layers: int, out_dir: Path):
    """FSDP training over a (data=N, model=1) mesh of every device, then a
    ``cut_layers`` copy of the same program on that mesh and on one device
    from the same seed, in bf16 and in float32.  The step-1 loss and
    gradient norm must agree in both, and the step-2 loss, after one
    update, in float32."""
    devices = jax.devices()
    mesh_cfg = make_mesh_config(len(devices))
    shape = C.ShapeConfig("train_smoke", seq, batch, "train")
    train = C.TrainConfig(total_steps=steps, checkpoint_every=0,
                          checkpoint_dir=str(out_dir / "ckpt"))
    rc = C.RunConfig(model=model_cfg, shape=shape, mesh=mesh_cfg, train=train)
    state_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(
        abstract_state(build_model(model_cfg).abstract())))
    full = _train(rc, out_dir / "ckpt")
    # read before the one-device run below raises device 0's peak
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    log("four_chips", model=model_cfg.name, mesh="x".join(map(str, mesh_cfg.shape)),
        tokens_per_step=batch * seq, losses=full.losses,
        median_step_s=statistics.median(full.step_times[1:]),
        state_bytes=state_bytes, peak_bytes_in_use=peaks)
    if not np.all(np.isfinite(full.losses)):
        raise AssertionError(f"non-finite loss: {full.losses}")

    # one warm-up step: the first update already takes the peak learning
    # rate, so an update that goes wrong moves the step-2 loss
    two_steps = C.TrainConfig(total_steps=2, warmup_steps=1, checkpoint_every=0,
                              checkpoint_dir=str(out_dir / "ckpt"))
    for dtype in ("bfloat16", "float32"):
        cut = model_cfg.replace(num_layers=cut_layers, dtype=dtype)
        sharded = _train(C.RunConfig(model=cut, shape=shape, mesh=mesh_cfg,
                                     train=two_steps), out_dir / "ckpt")
        single = _train(C.RunConfig(model=cut, shape=shape,
                                    mesh=make_mesh_config(1), train=two_steps),
                        out_dir / "ckpt")
        pairs = {"step1_loss": (sharded.losses[0], single.losses[0]),
                 "step1_grad_norm": (sharded.grad_norms[0], single.grad_norms[0]),
                 "step2_loss": (sharded.losses[1], single.losses[1])}
        log("four_chips_vs_one", layers=cut_layers, dtype=dtype,
            **{f"{k}_{n}": v for k, (a, b) in pairs.items()
               for n, v in (("sharded", a), ("one_device", b))})
        for name, (a, b) in pairs.items():
            if dtype == "bfloat16" and name == "step2_loss":
                # Adam's first update is +-lr wherever a gradient is not
                # zero, so bf16 rounding that flips a near-zero gradient
                # moves that weight by 2 lr: 1.1e-4 between the bf16 step-2
                # losses on four CPU devices at smoke widths, against 3e-7
                # in float32 and 1.1e-3 moved by the update itself
                continue
            rtol = SHARDED_RTOL[name.split("_", 1)[1]]
            if not abs(a - b) <= rtol * abs(b):
                raise AssertionError(f"{dtype} {name}: sharded {a} vs one "
                                     f"device {b}, rtol {rtol}")
    return peaks, state_bytes


def check_spread(peaks, state_bytes: int):
    """No device may have held the whole train state."""
    if None in peaks or max(peaks) >= state_bytes:
        raise AssertionError(f"state not spread: peaks {peaks} vs {state_bytes}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded rwkv6-1.6b training phase")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    if args.four_chips and len(devices) != 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    log("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), jax=jax.__version__,
        compile_cache=enable_compile_cache())
    hw = chip_for_device_kind(dev.device_kind)
    clock = CompileClock()

    def timed(name, fn, *a, **kw):
        c0, t0 = clock.seconds, time.time()
        out = fn(*a, **kw)
        log("phase", name=name, wall_s=time.time() - t0,
            compile_s=clock.seconds - c0)
        return out

    if args.four_chips:
        peaks, state_bytes = timed(
            "four_chips", phase_four_chips, C.get("rwkv6-1.6b").full,
            batch=8, seq=2048, steps=5, cut_layers=2, out_dir=OUT_DIR)
        check_spread(peaks, state_bytes)
    else:
        qwen = C.get("qwen1.5-4b").full
        timed("serve", phase_serve, qwen, batch=8, prompt_len=256, max_new=32)
        rc, step_s = timed("train", phase_train, C.get("lenet").full, steps=30,
                           ckpt_dir=OUT_DIR / "lenet_ckpt")
        timed("simulate", phase_simulate, rc, hw, step_s)
        kernels = timed("kernels", phase_kernels, kernel_cases(
            qwen, seq=2048, conv_shape=(8, 64, 64, 128)))
        interpreted = [k for k, r in kernels.items() if not r["compiled"]]
        if interpreted:
            raise AssertionError(f"kernels not compiled for the chip: {interpreted}")
    log("compile", total_s=clock.seconds)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
