"""The reader of PR 38 over a synthetic ``run``: ``programs_stored`` of the
``first_dispatch`` block as the counters stood at the window's opening; a
block without the count (the parent's, PR 37) and a run without the block
give None, so the parent prints every metric it had and not this one."""

import json
import os

import pytest
from test_first_dispatch import BLOCK, with_block
from test_layers_inside import OLD_COUNTERS, reader, run_of

NAME = "programs_stored.setup"
MODULE = "programs_stored_setup"
CELLS = ["pay5000.sigflush", "pay5000.close", "multisig5000.close", "scp4096.envelopes"]


def block(stored, exported=0, traced=0):
    return {**BLOCK, "programs_stored": stored, "programs_exported": exported, "programs_traced": traced}


@pytest.mark.parametrize(
    "counts, want",
    [((2, 0, 0), 2), ((1, 0, 0), 1), ((0, 2, 0), 0), ((1, 0, 1), 1), ((0, 0, 0), 0)],
    ids=["warm-two-buckets", "warm-one-bucket", "a-checkouts-first-run", "one-fell-back", "never-dispatched"],
)
def test_count_present_gives_the_count(counts, want):
    assert reader(MODULE)(with_block(block(*counts))) == want


def test_the_opening_is_read_not_the_window():
    run = with_block(block(2))
    run["counters"]["after"]["sig_backend"]["first_dispatch"]["programs_stored"] = 9
    assert reader(MODULE)(run) == 2


@pytest.mark.parametrize(
    "run",
    [
        with_block(BLOCK),  # PR 37's block: the sums, none of the counts
        run_of(before=OLD_COUNTERS, after=OLD_COUNTERS),
        run_of(before={"sig_backend": {"backend": "cpu"}}),
        run_of(),
    ],
    ids=["parent-block", "older-counters", "cpu-backend", "no-counters"],
)
def test_count_absent_gives_none(run):
    assert reader(MODULE)(run) is None


def test_entry_and_file():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries[NAME] == {
        "name": NAME, "unit": "1", "better": "higher", "source": "program_counter",
        "layer": "verify pipeline", "moves": "setup_s", "workloads": CELLS,
    }
    assert os.path.exists(os.path.join(root, "benchmarks", "layers", MODULE + ".py"))
    # appended after PR 37's four, which stay as they were
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "first_dispatch_s", "first_dispatch_trace_lower_s", "first_dispatch_compile_s",
        "compile_cache_misses.setup", NAME,
    ]
