import os
# a CPU dry run: pin the host platform before any backend starts, so a
# machine with an accelerator still compiles for 512 virtual host devices
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: prove the distribution config is coherent without TPUs.

For every (architecture x input-shape) cell this lowers + compiles the step
function for the production meshes:

    single-pod:  (16, 16)      axes (data, model)        = 256 chips
    multi-pod:   (2, 16, 16)   axes (pod, data, model)   = 512 chips

and records ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
(FLOPs/bytes) and the collective-op byte census parsed from the compiled HLO
(for the roofline's collective term).  Artifacts land in
``experiments/dryrun/<arch>.<shape>.<mesh>.json`` and feed EXPERIMENTS.md.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only-cell ...]
"""
import argparse
import gc
import json
import sys
import time
import traceback

import jax

from repro import config as C
from repro.launch.mesh import make_production_mesh, production_mesh_config
from repro.runtime.steps import bundle_for

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def input_specs(run_cfg):
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    bundle = bundle_for(run_cfg)
    return bundle.abstract_inputs


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             save_hlo: bool = False, quiet: bool = False) -> dict:
    entry = C.get(arch)
    shape = C.SHAPES_BY_NAME[shape_name]
    reason = entry.skip_reason(shape)
    if reason is not None:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    import dataclasses
    mesh_cfg = production_mesh_config(multi_pod=multi_pod)
    train_cfg = dataclasses.replace(C.TrainConfig(), accum_steps=entry.accum_steps)
    run_cfg = C.RunConfig(model=entry.full, shape=shape, mesh=mesh_cfg,
                          train=train_cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)

    t0 = time.time()
    bundle = bundle_for(run_cfg, mesh)
    with mesh:
        lowered = bundle.lower(mesh)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    n_dev = mesh_cfg.num_devices

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(map(str, mesh_cfg.shape)),
        "num_devices": n_dev,
        "kind": shape.kind,
        "accum_steps": entry.accum_steps,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "cost": {k: cost.get(k) for k in ("flops", "transcendentals",
                                          "bytes accessed")},
    }
    # per-device live-bytes upper bound: args + temps (aliased args re-used)
    result["memory"]["per_device_bytes"] = (
        mem.argument_size_in_bytes + mem.temp_size_in_bytes
        + mem.output_size_in_bytes - mem.alias_size_in_bytes)

    # collective census + trip-count-aware op walk (the simulator IR parser)
    try:
        from repro.core.engine import Engine
        from repro.core.hlo_ir import parse_hlo_module, summarize_collectives
        hlo_text = compiled.as_text()
        module = parse_hlo_module(hlo_text)
        result["collectives"] = summarize_collectives(module)
        result["ir_ops"] = module.op_census()
        result["ir_totals"] = module.totals()
        rep = Engine().simulate(module)
        result["engine"] = rep.summary()
        if save_hlo:
            import gzip
            os.makedirs(ART_DIR, exist_ok=True)
            p = os.path.join(ART_DIR, f"{arch}.{shape_name}.{result['mesh']}.hlo.gz")
            with gzip.open(p, "wt") as f:
                f.write(hlo_text)
    except Exception as e:   # parser still in bring-up for exotic ops
        result["collectives"] = {"error": repr(e)}

    if not quiet:
        print(f"[dryrun] {arch} {shape_name} mesh={result['mesh']} "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s "
              f"per_dev={result['memory']['per_device_bytes']/2**30:.2f}GiB")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={cost.get('flops'):.3e} "
              f"bytes={cost.get('bytes accessed'):.3e}")
    os.makedirs(ART_DIR, exist_ok=True)
    with open(os.path.join(
            ART_DIR, f"{arch}.{shape_name}.{result['mesh']}.json"), "w") as f:
        json.dump(result, f, indent=1)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--skip-lenet", action="store_true", default=True)
    args = ap.parse_args()

    failures = []
    cells = []
    if args.all:
        for entry, shape, _ in C.iter_cells():
            if entry.arch_id == "lenet":
                continue
            cells.append((entry.arch_id, shape.name))
    else:
        shapes = [args.shape] if args.shape else [s.name for s in C.STANDARD_SHAPES]
        cells = [(args.arch, s) for s in shapes]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    for arch, shape_name in cells:
        for mp in meshes:
            try:
                run_cell(arch, shape_name, mp, save_hlo=args.save_hlo)
            except Exception:
                traceback.print_exc()
                failures.append((arch, shape_name, mp))
            gc.collect()

    if failures:
        print(f"FAILED cells: {failures}")
        return 1
    print("dry-run OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
