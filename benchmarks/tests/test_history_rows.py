"""`history_rows_ms_per_close` (PR 35) over the closes the other layer
tests use, each given its two history inserts: a known answer, and None
where the program records neither span."""

import pytest
from test_layers_inside import CLOSE_READINGS, CLOSES, MAIN, OLD_SPANS, reader, run_of

from benchmarks.spans import S

NAME = "history_rows_ms_per_close"


def inserts(t0, fees, rows):
    """``fees.rows`` at the end of the close's fee pass and ``apply.rows``
    after its apply loop, both before the commit (at ``t0`` + 0.5)."""
    return [
        S("close.fees", t0 + 0.05, t0 + 0.1 + fees, MAIN, None),
        S("fees.rows", t0 + 0.1, t0 + 0.1 + fees, MAIN, None),
        S("apply.serial", t0 + 0.2, t0 + 0.4, MAIN, None),
        S("apply.rows", t0 + 0.4, t0 + 0.4 + rows, MAIN, None),
    ]


# a close: 0.012 + 0.010, 0.040 + 0.030, 0.020 + 0.015 s
WITH_INSERTS = CLOSES + inserts(0.0, 0.012, 0.010) + inserts(2.0, 0.040, 0.030) + inserts(4.0, 0.020, 0.015)


def test_reads_the_known_answer():
    assert reader(NAME)(run_of(WITH_INSERTS, CLOSE_READINGS)) == pytest.approx(35.0)


def test_one_insert_alone_is_read():
    only_fees = [s for s in WITH_INSERTS if s.name != "apply.rows"]
    assert reader(NAME)(run_of(only_fees, CLOSE_READINGS)) == pytest.approx(20.0)


def test_a_close_outside_every_reading_is_left_out():
    late = WITH_INSERTS + inserts(8.0, 0.5, 0.5)
    assert reader(NAME)(run_of(late, CLOSE_READINGS)) == pytest.approx(35.0)


def test_finds_nothing_to_read():
    assert reader(NAME)(run_of(OLD_SPANS, CLOSE_READINGS)) is None
    assert reader(NAME)(run_of(CLOSES, CLOSE_READINGS)) is None
    assert reader(NAME)(run_of(readings=CLOSE_READINGS)) is None


def test_entry_and_file():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    (m,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    twin = next(m for m in bench["per_layer"] if m["name"] == "commit_sql_ms_per_close")
    assert m == {**twin, "name": NAME}  # same layer, unit, source, cells: the four close cells
    assert bench["per_layer"][-1] == m  # appended, nothing moved
