"""Run one benchmark cell on the chips of this machine and print its result.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name: ``workloads/<name>.json`` names a
configuration (``configs/<config>.json``) and a driver
(``drivers/<driver>.py``).  With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` the per-layer metrics that the
readers in ``layer_metrics/`` take from a profiler trace of part of the
window.  The last line of standard output is one JSON object; where JAX
finds no TPU, or fewer chips than the cell asks for, the run exits non-zero
before any work and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from chipbench import catalog, trace as trace_mod  # noqa: E402

#: scratch output of traced runs, inside the checkout (ignored by git)
OUT_DIR = CHECKOUT / "experiments" / "chipbench"
#: JAX's persistent compilation cache, where no other is given
CACHE_DIR = CHECKOUT / ".jax_cache"


class Tracer:
    """Starts and stops the profiler around the part of the window that a
    driver chooses; does nothing in an untraced run."""

    def __init__(self, directory):
        self.directory = directory
        self.started = self.stopped = False

    @property
    def enabled(self) -> bool:
        return self.directory is not None

    def start(self):
        if self.enabled and not self.started:
            import jax
            jax.profiler.start_trace(str(self.directory))
            self.started = True

    def stop(self):
        if self.started and not self.stopped:
            import jax
            jax.profiler.stop_trace()
            self.stopped = True


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(chips: int):
    """The devices JAX found, or an exit before any work."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU (JAX platform {devices[0].platform!r})")
    if len(devices) != chips:
        sys.exit(f"chipbench: the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def enable_compile_cache():
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileLog:
    """Host times at which XLA compiled a program in this process."""

    def __init__(self):
        import jax
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(time.perf_counter())

    def inside(self, t0: float, t1: float) -> int:
        return sum(t0 <= t <= t1 for t in self.times)


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def per_layer(cell, result, reduced, device_kind) -> dict:
    ctx = {"trace": reduced, "counts": result["counts"], "model": cell.config["model"],
           "chips": cell.chips, "peaks": catalog.peaks(device_kind)}
    out = {}
    for name, reader in catalog.layer_metric_readers(cell.driver).items():
        value = reader.read(ctx)
        if value is not None:
            out[name] = {"value": value, "unit": reader.UNIT}
    return out


def report_checks(checks) -> dict:
    """Each number compared beside its limit, last on standard error."""
    for name, value, limit in checks:
        print(f"check {name} = {value!r} (limit {limit!r})", file=sys.stderr)
    # JSON has no NaN or infinity: such a reading is written as text
    shown = lambda v: v if math.isfinite(v) else repr(v)
    return {name: {"value": shown(value), "limit": limit} for name, value, limit in checks}


def execute(cell, args, devices) -> dict:
    """Everything of a run after the look for the chips: the result line."""
    enable_compile_cache()
    driver = catalog.load_driver(cell.driver)
    trace_dir = None
    if args.trace:
        trace_dir = OUT_DIR / "trace" / f"{cell.name}-{args.seed}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = Tracer(trace_dir)
    compiles = CompileLog()
    result = driver.run(cell, seed=args.seed, seconds=args.seconds, tracer=tracer,
                        t0=T0, memory_peak=lambda: memory_peak(devices))
    print(f"window_compiles {compiles.inside(*result['window'])}", file=sys.stderr)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": all(abs(v) <= lim for _, v, lim in result["checks"]),
            "attempted": result["attempted"], "failed": result["failed"]}
    if args.trace:
        reduced = trace_mod.reduce(trace_mod.load(trace_dir))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["metrics"] = per_layer(cell, result, reduced, dev.device_kind)
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
        with open(OUT_DIR / f"{cell.name}-{args.seed}.reduced.json", "w") as f:
            json.dump(dict(reduced, counts=result["counts"]), f, indent=1)
    else:
        line["metrics"] = {k: {"value": v, "unit": u}
                           for k, (v, u) in result["metrics"].items()}
    line["device"] = device
    line["checks"] = report_checks(result["checks"])
    return line


def main(argv=None) -> int:
    args = parse(argv)
    cell = catalog.load_cell(args.workload)
    devices = check_devices(cell.chips)
    print(json.dumps(execute(cell, args, devices), allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
