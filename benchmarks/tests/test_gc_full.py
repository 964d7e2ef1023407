"""`gc_full_ms_per_ledger` (PR 50): the collector's full passes of the
window spread over its readings — a known answer, a window whose passes
all fall in one cycle, 0 for a window without a pass and None without a
reading."""

import json
import os

import pytest
from test_layers_inside import CLOSE_READINGS, CLOSES, CYCLE_READINGS, CYCLES, MAIN, OLD_SPANS, reader, run_of

from benchmarks.spans import S
from benchmarks.stats import Reading

NAME = "gc_full_ms_per_ledger"

EIGHT = [Reading(float(i), i + 0.9, 1000) for i in range(8)]
PASSES = [
    # every fourth ledger, inside its close, each longer than the last
    S("gc.full", 0.50, 0.54, MAIN, None),
    S("gc.full", 4.50, 4.60, MAIN, None),
    # one the overlay's tick asked for, between two cycles
    S("gc.full", 6.92, 6.98, 7, None),
]


def test_three_passes_over_eight_readings_read_their_sum_over_eight():
    assert reader(NAME)(run_of(PASSES, EIGHT)) == pytest.approx((40.0 + 100.0 + 60.0) / 8)


def test_a_mean_where_the_median_cycle_holds_no_pass():
    # of the three cycles one holds a pass: a median over cycles reads 0
    one = CYCLES + [S("gc.full", 10.7, 10.79, MAIN, None)]
    assert reader(NAME)(run_of(one, CYCLE_READINGS)) == pytest.approx(30.0)


def test_other_spans_do_not_count():
    assert reader(NAME)(run_of(CLOSES + PASSES[:1], CLOSE_READINGS)) == pytest.approx(40.0 / 3)


def test_a_window_without_a_pass_reads_zero_and_one_without_a_reading_nothing():
    # a rehearsal's few ledgers: the collector cost them nothing
    assert reader(NAME)(run_of(CYCLES, CYCLE_READINGS)) == 0.0
    assert reader(NAME)(run_of(OLD_SPANS, CLOSE_READINGS)) == 0.0
    assert reader(NAME)(run_of(readings=EIGHT)) == 0.0
    assert reader(NAME)(run_of(PASSES)) is None
    assert reader(NAME)(run_of()) is None


def test_entry_and_file():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert m == {
        "name": NAME, "unit": "ms", "better": "lower", "source": "program_span", "layer": "collector",
        "moves": "applied_tx_per_s", "workloads": ["pay1000.frontdoor", "zipf1000.frontdoor"],
    }
    assert os.path.exists(os.path.join(root, "benchmarks", "layers", NAME + ".py"))
    cells = {w["name"] for w in bench["workloads"]}
    assert set(m["workloads"]) <= cells
