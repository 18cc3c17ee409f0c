"""Hardware specifications for the performance model.

All timing/energy constants live here (one dataclass per chip) so the
simulator retargets by swapping the spec — the GPGPU-Sim analogue of the
gpgpusim.config file describing the GTX1080Ti/GTX1050 in the paper.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, Tuple


@dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu-v5e"

    # --- compute ---
    peak_bf16_flops: float = 197e12       # per chip
    peak_f32_flops: float = 49e12         # MXU fp32 ~= 1/4 bf16
    vpu_flops: float = 4e12               # vector unit (elementwise) FLOP/s
    transcendental_flops: float = 1e12    # exp/tanh/... throughput
    mxu_tile: Tuple[int, int] = (128, 128)
    vpu_lanes: Tuple[int, int] = (8, 128)

    # --- memory ---
    hbm_bytes: int = 16 * 2**30
    hbm_bw: float = 819e9                 # B/s
    hbm_channels: int = 16                # channel model for "bank camping"
    hbm_interleave_bytes: int = 512       # address-interleave stripe width
    vmem_bytes: int = 128 * 2**20         # on-chip working-set capacity
    vmem_bw: float = 10e12                # ~VMEM bandwidth

    # --- interconnect ---
    ici_link_bw: float = 50e9             # B/s per link per direction
    ici_links_per_axis: int = 2           # bidirectional torus ring per axis
    ici_latency_s: float = 1e-6           # per-hop launch latency
    dcn_bw: float = 12.5e9                # inter-pod (DCN) per host share
    #: fabric shape for repro.topology ("ring" | "ring:N" | "torus:AxB[xC]"
    #: | "fc[:N]").  The unsized default builds a per-collective-group ring,
    #: which reproduces the flat analytic model's totals exactly; a sized
    #: spec pins collectives onto one shared fabric so different replica
    #: groups contend for (or provably avoid) the same physical links.
    ici_topology: str = "ring"

    # --- overheads ---
    op_launch_overhead_s: float = 0.5e-6  # per-HLO-op issue cost

    # --- energy model (first-order; W = pJ/op * op/s) ---
    pj_per_mxu_flop: float = 0.25
    pj_per_vpu_flop: float = 1.5
    pj_per_hbm_byte: float = 7.0
    pj_per_vmem_byte: float = 0.4
    pj_per_ici_byte: float = 10.0
    static_watts: float = 60.0            # idle/static per chip

    @property
    def hbm_channel_bw(self) -> float:
        """Per-channel HBM bandwidth (the paper's per-partition bandwidth).

        An evenly interleaved transfer sees ``hbm_bw`` in aggregate; a
        transfer camping on one channel sees only this.
        """
        if self.hbm_channels <= 0:
            return self.hbm_bw
        return self.hbm_bw / self.hbm_channels

    def matmul_efficiency(self, m: int, n: int, k: int) -> float:
        """MXU systolic occupancy: padding waste for non-128-aligned dims.

        The TPU analogue of the paper's warp-occupancy concerns: a (m,n,k)
        matmul runs at peak only when every dim fills the 128x128 array.
        """
        tm, tn = self.mxu_tile

        def frac(dim, tile):
            if dim <= 0:
                return 1.0
            full = (dim + tile - 1) // tile
            return dim / (full * tile)

        return frac(m, tm) * frac(n, tn) * frac(k, 8)   # k packed by 8


def _cached_spec_hash(self: "HardwareSpec") -> int:
    """Memoized field-tuple hash (same value as the dataclass-generated
    one).  Specs key every hot cache in the stack — engine maps, simulation
    caches, lowering plans — and the 25-field tuple hash is measurable in
    the cluster loop, so it is computed once per instance."""
    try:
        return self._hash            # type: ignore[attr-defined]
    except AttributeError:
        h = hash(tuple(getattr(self, f.name)
                       for f in dataclasses.fields(self)))
        object.__setattr__(self, "_hash", h)
        return h


HardwareSpec.__hash__ = _cached_spec_hash      # type: ignore[assignment]

V5E = HardwareSpec()

V5P = HardwareSpec(
    name="tpu-v5p", peak_bf16_flops=459e12, peak_f32_flops=115e12,
    vpu_flops=8e12, hbm_bytes=95 * 2**30, hbm_bw=2765e9, hbm_channels=32,
    ici_link_bw=100e9, ici_links_per_axis=2, vmem_bytes=128 * 2**20,
)

CHIPS: Dict[str, HardwareSpec] = {"tpu-v5e": V5E, "tpu-v5p": V5P}

#: ``jax.Device.device_kind`` -> ``CHIPS`` key.  Code that prices a run on
#: real hardware looks its chip up here; a kind that is missing is an error.
DEVICE_KINDS: Dict[str, str] = {"TPU v5 lite": "tpu-v5e", "TPU v5": "tpu-v5p"}


def chip_for_device_kind(kind: str) -> HardwareSpec:
    """The :class:`HardwareSpec` of the chip JAX reports as ``kind``."""
    if kind not in DEVICE_KINDS:
        raise KeyError(f"no HardwareSpec for device kind {kind!r}; "
                       f"known: {sorted(DEVICE_KINDS)}")
    return CHIPS[DEVICE_KINDS[kind]]
