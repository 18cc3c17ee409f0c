"""repro.core — the paper's contribution: a detailed TPU workload simulator.

Facade:

    sim = Simulator()                         # TPU v5e by default
    cap = sim.capture(step_fn, *abstract_args, mesh=mesh, ...)
    rep = sim.performance(cap)                # detailed timeline (SimReport)
    out = sim.functional(step_fn, *real_args) # bit-exact execution
    sim.analysis(rep)                         # phase analysis (repro.analysis)
    sim.vision(rep)                           # legacy single-file vision view
    sim.power(rep)                            # GPUWattch-style breakdown
    sim.correlate(cap)                        # Fig. 6/7 correlation table
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

from repro.core.capture import Captured, capture, capture_bundle
from repro.core.collectives import collective_time
from repro.core.correlate import CorrelationReport, correlate
from repro.core.debug import Divergence, compare_implementations, first_divergence
from repro.core.engine import Engine, SimReport, SimulationCache
from repro.core.functional import FunctionalResult, run_functional
from repro.core.hlo_ir import SimModule, parse_hlo_module, summarize_collectives
from repro.core.hw import CHIPS, V5E, V5P, HardwareSpec, chip_for_device_kind
from repro.core.power import PowerReport, analyze_power
from repro.core.sim_checkpoint import CheckpointedSim, simulate_from_checkpoint
from repro.core.trace import to_chrome_trace, to_csv
from repro.core.vision import VisionReport, analyze as vision_analyze


class Simulator:
    """One-stop facade over capture/engine/vision/power/correlate."""

    def __init__(self, hw: HardwareSpec = V5E, overlap_collectives: bool = True,
                 num_compute_streams: int = 1, memory_model: bool = True,
                 topology_model: bool = True, scheduler: str = "batched"):
        self.hw = hw
        self.engine = Engine(hw, overlap_collectives, num_compute_streams,
                             memory_model=memory_model,
                             topology_model=topology_model,
                             scheduler=scheduler)

    def capture(self, fn, *abstract_args, **kw) -> Captured:
        return capture(fn, *abstract_args, **kw)

    def capture_bundle(self, bundle, name="step", mesh=None) -> Captured:
        return capture_bundle(bundle, name=name, mesh=mesh)

    def performance(self, captured: Captured,
                    window: Optional[Tuple[int, int]] = None) -> SimReport:
        return self.engine.simulate(captured.module, window=window)

    def functional(self, fn, *args, steps: int = 1) -> FunctionalResult:
        return run_functional(fn, *args, steps=steps)

    def analysis(self, report: SimReport, num_buckets: int = 120):
        """Phase analysis: intervals + labeled phases + HBM channel model."""
        from repro.analysis import analyze
        return analyze(report, num_buckets=num_buckets, hw=self.hw)

    def vision(self, report: SimReport, num_buckets: int = 200) -> VisionReport:
        return vision_analyze(report, self.hw, num_buckets)

    def power(self, report: SimReport) -> PowerReport:
        return analyze_power(report, self.hw)

    def correlate(self, captured: Captured, reference=None) -> CorrelationReport:
        return correlate(captured, self.hw, reference)


__all__ = [
    "Simulator", "Captured", "capture", "capture_bundle", "Engine", "SimReport",
    "SimulationCache",
    "SimModule", "parse_hlo_module", "summarize_collectives", "HardwareSpec",
    "V5E", "V5P", "CHIPS", "chip_for_device_kind", "collective_time", "correlate", "CorrelationReport",
    "first_divergence", "compare_implementations", "Divergence",
    "run_functional", "FunctionalResult", "analyze_power", "PowerReport",
    "vision_analyze", "VisionReport", "simulate_from_checkpoint",
    "CheckpointedSim", "to_chrome_trace", "to_csv",
]
