"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b \
        [--shape train_4k] [--steps N] [--smoke] [--multi-pod] [--ckpt-dir D]

--smoke uses the reduced config + tiny shapes on local devices (CI path).
Otherwise the model runs at full width on a data-parallel (FSDP) mesh over
the devices present; --multi-pod splits them into two pods.  Checkpoints go
to experiments/ckpt/<arch> unless --ckpt-dir says otherwise, and a run
resumes from the latest one there.
"""
import argparse
import os

import jax

from repro import config as C
from repro.distributed.mesh import make_mesh_config
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    entry = C.get(args.arch)
    if args.smoke:
        model = entry.smoke
        shape = C.ShapeConfig("smoke_train", 64, 4, "train")
        mesh_cfg = C.SMOKE_MESH
        use_mesh = False
    else:
        model = entry.full
        shape = C.SHAPES_BY_NAME[args.shape]
        mesh_cfg = make_mesh_config(len(jax.devices()),
                                    pods=2 if args.multi_pod else 1)
        use_mesh = True
    train = C.TrainConfig(
        total_steps=args.steps or 100,
        checkpoint_dir=args.ckpt_dir or os.path.join("experiments", "ckpt",
                                                     args.arch),
        accum_steps=entry.accum_steps)
    rc = C.RunConfig(model=model, shape=shape, mesh=mesh_cfg, train=train)
    report = Trainer(rc, use_mesh=use_mesh).train()
    print(f"done: steps={report.steps_done} final_loss={report.final_loss:.4f} "
          f"restarts={report.restarts}")


if __name__ == "__main__":
    main()
