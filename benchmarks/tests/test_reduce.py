"""The trace reduction on a small trace recorded on the chip
(``data/sample.xplane.pb.gz``: a traced 1 s window of ``pay5000.sigflush``,
seed 1006, PR 23; ``data/sample.json`` holds that run's window and spans)."""

import json
import os

import pytest

from benchmarks import reduce as R

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def sample():
    trace = R.load(os.path.join(DATA, "sample.xplane.pb.gz"))
    meta = json.load(open(os.path.join(DATA, "sample.json")))
    return trace, meta


def test_short_names():
    assert (
        R.short_name("%verify_kernel_pallas.1 = s32[1,4096]{1,0:T(1,128)} custom-call(%x)")
        == "verify_kernel_pallas.1 s32[1,4096]"
    )
    assert R.short_name("%fusion = (u8[32,1024]{1,0}, u8[32,1024]{1,0}) fusion(%p)") == "fusion u8[32,1024]"
    assert R.short_name("jit_packed_pallas(123)") == "jit_packed_pallas(123)"


def test_the_trace_has_one_chip_and_clock_markers(sample):
    trace, _ = sample
    assert list(trace.chips) == ["/device:TPU:0"]
    assert trace.sync_markers >= 2 and trace.offset_ns is not None


def test_busy_and_idle_add_up_to_the_window(sample):
    trace, meta = sample
    w0 = meta["t_open"] * 1e9 + trace.offset_ns
    w1 = meta["t_end"] * 1e9 + trace.offset_ns
    busy = R.busy_seconds(trace, w0, w1)
    spans = [(n, s * 1e9 + trace.offset_ns, e * 1e9 + trace.offset_ns) for n, s, e in meta["spans"]]
    gaps = R.idle_gaps(trace, spans, w0, w1)
    window = (w1 - w0) / 1e9
    assert 0.5 * window < busy < window
    assert busy + sum(gaps.values()) == pytest.approx(window, rel=1e-6)
    # the harness's span covers every flush, so next to nothing is unattributed
    assert gaps.get(R.NO_SPAN, 0.0) < 0.05 * window


def test_kernel_time_per_item(sample):
    trace, meta = sample
    w0 = meta["t_open"] * 1e9 + trace.offset_ns
    w1 = meta["t_end"] * 1e9 + trace.offset_ns
    ops = R.op_seconds(trace, w0, w1)
    kernel = sum(v for k, v in ops.items() if "verify_kernel_pallas" in k)
    flushes = R.op_count(trace, w0, w1, r"verify_kernel_pallas.* s32\[1,4096\]")
    assert flushes == meta["flushes"]
    assert R.op_count(trace, w0, w1, r"verify_kernel_pallas.* s32\[1,1024\]") == flushes
    # 2.55 us an item (ledger, PR 22; my chip runs, PR 23)
    assert kernel / (flushes * 5000) * 1e6 == pytest.approx(2.55, rel=0.02)
    assert R.top(ops)[0][0].startswith("verify_kernel_pallas")


def test_union_merges_overlaps():
    ops = [R.Op("a", 0, 10), R.Op("b", 5, 20), R.Op("c", 30, 40)]
    assert R.busy_intervals(ops, 0, 100) == [(0, 20), (30, 40)]
    assert R.busy_intervals(ops, 8, 35) == [(8, 20), (30, 35)]


def test_idle_gaps_go_to_the_innermost_open_span():
    trace = R.Trace({"/device:TPU:0": [R.Op("k", 10, 20), R.Op("k", 60, 70)]}, 0.0, 1)
    spans = [("outer", 0, 100), ("inner", 30, 50)]
    gaps = R.idle_gaps(trace, spans, 0, 100)
    assert gaps == {"outer": pytest.approx((10 + 10 + 10 + 30) / 1e9), "inner": pytest.approx(20 / 1e9)}
    assert R.idle_gaps(trace, [], 0, 100) == {R.NO_SPAN: pytest.approx(80 / 1e9)}
