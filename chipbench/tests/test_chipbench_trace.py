"""The trace reduction on a small two-chip trace, against numbers worked
out by hand (nanoseconds; the window is the bench.window span, 1000-11000).

Chip 0, clipped to the window: copy.1 1000-1200, fusion.1 1500-3500,
all-gather.2 3000-4500, while.1 5000-9000 holding fusion.2 5500-6500,
reduce-scatter.1 9500-11000.  Busy 200 + 3000 + 4000 + 1500 = 8700.
Collectives 1500 + 1500 = 3000, of which 1000 + 1500 = 2500 with no other
op.  Idle gaps 1200-1500 (in bench.step_dispatch), 4500-5000, 9000-9500.

Chip 1: fusion.1 1000-6000, all-reduce.1 6000-7000, and on its async line
all-gather-start.4 7000-8000.  Busy 6000 (async ops are not busy);
collectives 2000, all of it exposed; idle gap 7000-11000.  Names given as
whole HLO instructions are cut to the op's name.
"""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from chipbench import trace

DATA = Path(__file__).parent / "data" / "two_chip_trace.textproto"


@pytest.fixture(scope="module")
def reduced():
    profile = ProfileData.from_text_proto(DATA.read_text())
    return trace.reduce(trace.load_profile(profile))


def test_window_and_busy(reduced):
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(10000e-9)
    assert reduced["busy_s"] == pytest.approx((8700 + 6000) / 2 * 1e-9)


def test_exposed_collectives(reduced):
    assert reduced["collective_s"] == pytest.approx((3000 + 2000) / 2 * 1e-9)
    assert reduced["exposed_collective_s"] == pytest.approx((2500 + 2000) / 2 * 1e-9)


def test_top_ops_are_self_times_averaged_over_chips(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["fusion.1"] == pytest.approx((2000 + 5000) / 2 * 1e-9)
    assert ops["while.1"] == pytest.approx(3000 / 2 * 1e-9)
    assert ops["fusion.2"] == pytest.approx(1000 / 2 * 1e-9)
    assert ops["copy.1"] == pytest.approx(200 / 2 * 1e-9)
    assert "jit_train_step" not in ops and "all-gather-start.4" not in ops
    assert reduced["device_ops"][0][0] == "fusion.1"


def test_idle_gaps_named_by_host_span(reduced):
    gaps = [(name, round(s * 1e9)) for name, s in reduced["idle_gaps"]]
    assert gaps[0] == ("bench.window", 4000)
    assert sorted(gaps[1:3]) == [("bench.window", 500), ("bench.window", 500)]
    assert gaps[3] == ("bench.step_dispatch", 300)
    assert len(gaps) == 4


def test_interval_helpers():
    u = trace.union([(5, 7), (0, 2), (1, 3)])
    assert u == [(0, 3), (5, 7)]
    assert trace.subtract(u, [(2, 6)]) == [(0, 2), (6, 7)]
    assert trace.measure(trace.clip(u, 1, 6)) == 3


def test_a_trace_without_device_ops_is_refused():
    profile = ProfileData.from_text_proto(
        'planes { id: 1 name: "/host:CPU" }')
    with pytest.raises(ValueError, match="XLA Ops"):
        trace.load_profile(profile)
