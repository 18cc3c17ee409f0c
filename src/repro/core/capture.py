"""Capture layer: any JAX callable -> compiled artifact -> simulator IR.

The paper's §III-A adapted to XLA: instead of cuobjdump-extracting PTX from
libcudnn.so, we lower/compile the workload (which embeds *all* its "library"
computation in one HLO module) and parse that module into the SimOp IR.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from repro.core.hlo_ir import SimModule, parse_hlo_module, summarize_collectives


@dataclass
class Captured:
    """One captured workload: compiled executable + parsed IR + metadata."""
    name: str
    lowered: Any
    compiled: Any
    module: SimModule
    cost_analysis: Dict[str, float]
    memory_analysis: Any
    capture_seconds: float
    hlo_text_len: int

    @property
    def xla_flops(self) -> float:
        return float(self.cost_analysis.get("flops", 0.0))

    @property
    def xla_bytes(self) -> float:
        return float(self.cost_analysis.get("bytes accessed", 0.0))

    def collectives(self) -> Dict[str, Any]:
        return summarize_collectives(self.module)


def capture(fn: Callable, *abstract_args, name: str = "workload",
            mesh: Optional[Any] = None, in_shardings: Any = None,
            out_shardings: Any = None, donate_argnums: Tuple[int, ...] = (),
            ) -> Captured:
    """Lower + compile ``fn`` on abstract inputs and parse the HLO."""
    t0 = time.time()
    kw: Dict[str, Any] = {"donate_argnums": donate_argnums}
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
        kw["out_shardings"] = out_shardings
    jitted = jax.jit(fn, **kw)
    if mesh is not None:
        with mesh:
            lowered = jitted.lower(*abstract_args)
            compiled = lowered.compile()
    else:
        lowered = jitted.lower(*abstract_args)
        compiled = lowered.compile()
    text = compiled.as_text()
    module = parse_hlo_module(text)
    return Captured(
        name=name,
        lowered=lowered,
        compiled=compiled,
        module=module,
        cost_analysis=dict(compiled.cost_analysis() or {}),
        memory_analysis=compiled.memory_analysis(),
        capture_seconds=time.time() - t0,
        hlo_text_len=len(text),
    )


def capture_bundle(bundle, name: str = "step", mesh=None) -> Captured:
    """Capture a repro.runtime StepBundle."""
    return capture(bundle.fn, *bundle.abstract_inputs, name=name, mesh=mesh,
                   in_shardings=bundle.in_shardings,
                   out_shardings=bundle.out_shardings,
                   donate_argnums=bundle.donate_argnums)
