"""The arithmetic from readings to a run's metrics, on synthetic series."""

import statistics

import pytest

from benchmarks import stats
from benchmarks.stats import Reading


def back_to_back(t0, n, dur, items, stall_at=None, stall_s=0.0):
    out, t = [], t0
    for i in range(n):
        d = dur + (stall_s if i == stall_at else 0.0)
        out.append(Reading(t, t + d, items))
        t += d
    return out


def test_slice_rate_is_not_quantised_to_whole_readings():
    # 5,000 items every 16.8 ms: a slice holds 59.5 flushes
    readings = back_to_back(100.0, 3000, 0.0168, 5000)
    m = stats.slice_rate_median(readings, 100.0, 145.0)
    assert m.samples == 45
    assert m.value == pytest.approx(5000 / 0.0168, rel=1e-6)


def test_a_stall_costs_the_rate_its_full_weight_and_the_slice_median_nothing():
    steady = 5000 / 0.0168
    readings = back_to_back(0.0, 3000, 0.0168, 5000, stall_at=600, stall_s=2.0)
    rate = stats.work_over_wall(readings, 0.0, 45.0)
    assert rate.value == pytest.approx(steady * 43.0 / 45.0, rel=1e-3)  # loses the stall's 4.4 %
    m = stats.slice_rate_median(readings, 0.0, 45.0)
    assert m.value == pytest.approx(steady, rel=1e-3)  # the diagnostic beside it does not


def test_work_over_wall_prorates_the_readings_that_straddle_an_edge():
    # 1,000 items every 1.23 s from t = -0.5: a window never holds whole cycles only
    readings = back_to_back(-0.5, 50, 1.23, 1000)
    rate = stats.work_over_wall(readings, 0.0, 45.0)
    assert rate.value == pytest.approx(1000 / 1.23, rel=1e-9)
    assert rate.samples == 37  # whole cycles alone would read 35,000 / 45 = 777.8
    assert stats.work_over_wall(readings, 100.0, 145.0) is None


def test_slices_conserve_items():
    readings = back_to_back(0.0, 40, 1.23, 1000)
    rates = stats.slice_rates(readings, 0.0, 45.0)
    assert sum(rates) == pytest.approx(45.0 / 1.23 * 1000, rel=1e-9)
    assert statistics.median(rates) == pytest.approx(1000 / 1.23, rel=1e-6)


def test_close_median_takes_closes_inside_the_window_only():
    readings = back_to_back(-1.0, 15, 3.5, 5000, stall_at=4, stall_s=6.0)
    m = stats.duration_median(readings, 0.0, 45.0)
    inside = [r for r in readings if r.start >= 0.0 and r.end <= 45.0]
    assert m.samples == len(inside) == 10
    assert m.value == pytest.approx(3.5)
    assert statistics.mean(r.end - r.start for r in inside) > 4.0


def test_tail_percentile_needs_ten_samples_beyond():
    assert stats.tail_percentile(list(range(20))) is None
    pct, v = stats.tail_percentile(list(range(70)))
    assert v == 59 and pct == pytest.approx(100 * 60 / 70)


def test_spread_is_the_drivers():
    vals = [100, 101, 102, 103, 104, 105]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx((q[2] - q[0]) / 102.5)


def test_windows_of_a_long_series():
    readings = back_to_back(0.0, 120, 1.0, 1000)
    w = stats.windows_of(readings, {"reduce": "duration_median"}, 30.0)
    assert len(w) == 91 and set(w) == {1.0}
    w = stats.windows_of(readings, {"reduce": "work_over_wall"}, 30.0)
    assert len(w) == 91 and w == pytest.approx([1000.0] * 91)


def test_stall_share_and_flush_level_tell_a_stall_from_a_slower_process():
    from benchmarks.layers import flush_p50_ms, flush_stall_share_pct

    def run_of(readings):
        return {"readings": stats.in_window(readings, 0.0, 45.0), "window": (0.0, 45.0)}

    stalled = run_of(back_to_back(0.0, 3000, 0.0168, 5000, stall_at=600, stall_s=0.9))
    assert flush_p50_ms.read(stalled) == pytest.approx(16.8)
    assert flush_stall_share_pct.read(stalled) == pytest.approx(2.0)  # 0.9 s of 45
    slower = run_of(back_to_back(0.0, 3000, 0.0175, 5000))
    assert flush_p50_ms.read(slower) == pytest.approx(17.5)
    assert flush_stall_share_pct.read(slower) == 0.0
