"""Simulator-core tests: HLO parser (trip counts!), engine invariants,
collective model, vision/power reports."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Engine, Simulator, V5E, capture, collective_time, parse_hlo_module,
    summarize_collectives,
)


def _capture_scan(length):
    def f(x, w):
        def body(c, wl):
            return jax.nn.relu(c @ wl), None
        y, _ = jax.lax.scan(body, x, w)
        return y
    return capture(f, jax.ShapeDtypeStruct((64, 64), jnp.float32),
                    jax.ShapeDtypeStruct((length, 64, 64), jnp.float32))


def test_chip_for_device_kind():
    from repro.core import V5P, chip_for_device_kind
    assert chip_for_device_kind("TPU v5 lite") is V5E
    assert chip_for_device_kind("TPU v5") is V5P
    with pytest.raises(KeyError, match="no HardwareSpec"):
        chip_for_device_kind("TPU v6 lite")


def test_trip_count_scaling():
    """The IR walker must scale while bodies by trip count (XLA's own
    cost_analysis does not — the reason this parser exists)."""
    cap5 = _capture_scan(5)
    cap10 = _capture_scan(10)
    t5 = cap5.module.totals()
    t10 = cap10.module.totals()
    assert t5["mxu_flops"] > 0
    ratio = t10["mxu_flops"] / t5["mxu_flops"]
    assert 1.8 < ratio < 2.2, f"trip scaling broken: ratio={ratio}"
    # and confirm XLA's cost model indeed does NOT scale (documented behavior)
    assert abs(cap10.xla_flops - cap5.xla_flops) / max(cap5.xla_flops, 1) < 0.2


def test_dot_flops_exact():
    cap = capture(lambda a, b: a @ b,
                  jax.ShapeDtypeStruct((128, 256), jnp.float32),
                  jax.ShapeDtypeStruct((256, 64), jnp.float32))
    t = cap.module.totals()
    expected = 2 * 128 * 256 * 64
    assert abs(t["mxu_flops"] - expected) / expected < 0.05


def test_engine_report_invariants():
    cap = _capture_scan(8)
    rep = Engine().simulate(cap.module)
    assert rep.total_seconds > 0
    assert rep.total_flops > 0
    assert 0 <= rep.mfu <= 1.0
    assert rep.compute_seconds <= rep.total_seconds + 1e-12
    assert rep.exposed_ici_seconds >= 0
    # dataflow-scheduler invariants: exposure never exceeds the busy time,
    # and the makespan never exceeds the serial-chain bound
    for unit, s in rep.exposed_seconds.items():
        assert 0 <= s <= rep.unit_seconds.get(unit, 0.0) + 1e-12
    assert rep.total_seconds <= rep.compute_seconds + rep.ici_seconds + 1e-12
    assert sum(rep.critical_path_seconds.values()) <= rep.total_seconds + 1e-9
    # window-simulation (op-level checkpoint) must not change totals much
    rep_w = Engine().simulate(cap.module, window=(0, 3))
    assert abs(rep_w.total_flops - rep.total_flops) / rep.total_flops < 1e-6
    assert abs(rep_w.launch_overhead_seconds - rep.launch_overhead_seconds) \
        <= 1e-12 + 1e-6 * rep.launch_overhead_seconds


def test_collective_model_monotone():
    t1 = collective_time("all-reduce", 1e9, 16, V5E)
    t2 = collective_time("all-reduce", 2e9, 16, V5E)
    assert t2.seconds > t1.seconds
    ag = collective_time("all-gather", 1e9, 16, V5E)
    ar = collective_time("all-reduce", 1e9, 16, V5E)
    assert ar.seconds > ag.seconds            # AR = RS + AG
    assert collective_time("all-reduce", 1e9, 1, V5E).seconds == 0.0


def test_collective_census_from_spmd(tmp_path):
    """A psum under jit must show up as all-reduce bytes in the census."""
    import os
    if jax.device_count() < 2:
        pytest.skip("needs >1 device (run via dryrun path)")


def test_vision_and_power_reports():
    sim = Simulator()
    cap = _capture_scan(6)
    rep = sim.performance(cap)
    vr = sim.vision(rep, num_buckets=50)
    assert len(vr.buckets) == 50
    assert vr.camping_index >= 1.0
    assert vr.phases, "phase segmentation empty"
    csv = vr.to_csv()
    assert csv.count("\n") == 50
    heat = vr.ascii_heatmap()
    assert "mxu" in heat and "hbm" in heat
    pw = sim.power(rep)
    assert abs(sum(pw.shares.values()) - 1.0) < 1e-6
    assert pw.total_j > 0


def test_correlation_report():
    sim = Simulator()
    cap = _capture_scan(6)
    cr = sim.correlate(cap)
    assert cr.sim_total > 0 and cr.ref_total > 0
    assert -1.0 <= cr.correlation <= 1.0
    assert "TOTAL" in cr.table()


def test_functional_mode():
    sim = Simulator()
    f = lambda x: (x * 2, None)
    res = sim.functional(f, jnp.ones((4,)), steps=3)
    assert res.steps == 3
    # carry threads through: 1 -> 2 -> 4 -> 8
    np.testing.assert_allclose(np.asarray(res.outputs[0]), 8 * np.ones(4))


def test_matmul_efficiency_model():
    assert V5E.matmul_efficiency(128, 128, 128) == 1.0
    assert V5E.matmul_efficiency(129, 128, 128) < 0.6
    assert V5E.matmul_efficiency(1, 128, 128) < 0.01
