"""`txset_validate_ms_per_ledger` (PR 48) over the ledger cycles the other
layer tests use: every ``txset.validate`` span of a cycle counts, inside the
trigger or after it, whoever asked; a known answer, and None where no cycle
holds one."""

import pytest
from test_layers_inside import CLOSE_READINGS, CLOSES, CYCLE_READINGS, CYCLES, MAIN, OLD_SPANS, reader, run_of

from benchmarks.spans import S

NAME = "txset_validate_ms_per_ledger"


def test_reads_the_known_answer():
    # 0.1 + 0.05 + 0.04 = 0.19 / 0.1; the third cycle has no validation and is left out
    assert reader(NAME)(run_of(CYCLES, CYCLE_READINGS)) == pytest.approx(145.0)


def test_a_memo_hit_is_a_span_like_any_other():
    # a cycle of one full pass and seven hits of 0.1 ms: the sum, not the count
    hits = [S("txset.validate", 14.05 + i * 0.01, 14.0501 + i * 0.01, MAIN, {"memo": 1}) for i in range(7)]
    full = [S("txset.validate", 14.01, 14.03, MAIN, None)]
    assert reader(NAME)(run_of(CYCLES + full + hits, CYCLE_READINGS)) == pytest.approx(100.0)
    assert reader(NAME)(run_of(full + hits, CYCLE_READINGS)) == pytest.approx(20.7)


def test_a_validation_outside_every_reading_is_left_out():
    late = CYCLES + [S("txset.validate", 20.0, 25.0, MAIN, None)]
    assert reader(NAME)(run_of(late, CYCLE_READINGS)) == pytest.approx(145.0)


def test_finds_nothing_to_read():
    # OLD_SPANS' one validation lies outside every reading
    assert reader(NAME)(run_of(OLD_SPANS, CLOSE_READINGS)) is None
    assert reader(NAME)(run_of(CLOSES, CLOSE_READINGS)) is None
    assert reader(NAME)(run_of(readings=CYCLE_READINGS)) is None


def test_entry_and_file():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    twin = next(m for m in bench["per_layer"] if m["name"] == "herder_trigger_ms_per_ledger")
    # the front door's cell, unit, source and end-to-end metric; the layer is the txset's
    assert m == {**twin, "name": NAME, "layer": "txset validate + sig flush"}
    assert m["layer"] in {x["layer"] for x in bench["per_layer"] if x["name"] == "sig_flush_ms_per_close"}
