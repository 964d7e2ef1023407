"""The four readers of PR 37 over a synthetic ``run``: the ``first_dispatch``
block of ``sig_backend`` as the counters stood at the window's opening gives
the four values; a program without the block (the parent) gives None."""

import json
import os

import pytest
from test_layers_inside import OLD_COUNTERS, reader, run_of

CELLS = ["pay5000.sigflush", "pay5000.close", "multisig5000.close", "scp4096.envelopes"]
NAMES = {
    "first_dispatch_s": "s",
    "first_dispatch_trace_lower_s": "s",
    "first_dispatch_compile_s": "s",
    "compile_cache_misses.setup": "1",
}

# two buckets first dispatched one after the other, the first a cache miss
BLOCK = {
    "buckets": {
        4096: {"bucket": 4096, "start": 100.0, "end": 170.0, "trace_s": 9.0, "lower_s": 14.0, "compile_s": 45.0,
               "cache_retrieval_s": 0.0, "cache": "miss", "cache_hits": 0, "cache_misses": 1, "rest_s": 2.0,
               "caller": "close", "thread": "ThreadPoolExecutor-0_0"},
        1024: {"bucket": 1024, "start": 170.5, "end": 200.5, "trace_s": 8.0, "lower_s": 13.0, "compile_s": 7.5,
               "cache_retrieval_s": 7.0, "cache": "hit", "cache_hits": 1, "cache_misses": 0, "rest_s": 1.5,
               "compile_time_saved_s": 30.0, "caller": "close", "thread": "ThreadPoolExecutor-0_0"},
    },
    "wall_s": 100.0, "trace_s": 17.0, "lower_s": 27.0, "compile_s": 52.5, "cache_retrieval_s": 7.0,
    "cache_hits": 1, "cache_misses": 1,
    "unattributed": {"events": 12, "seconds": 0.8},
    "recompiles": {"events": 0, "seconds": 0.0, "bucket": None},
}
WANT = {
    "first_dispatch_s": 100.0,
    "first_dispatch_trace_lower_s": 44.0,
    "first_dispatch_compile_s": 52.5,
    "compile_cache_misses.setup": 1,
}


def module(name):
    return name.replace(".", "_")


def with_block(block):
    before = {"sig_backend": {"items": 10000, "lanes": 10240, "first_dispatch": block}}
    # the window adds nothing to the account: the readers take the opening's
    after = {"sig_backend": {"items": 60000, "lanes": 61440, "first_dispatch": {**block, "wall_s": 999.0}}}
    return run_of(before=before, after=after)


@pytest.mark.parametrize("name", NAMES)
def test_block_present_gives_the_value(name):
    assert reader(module(name))(with_block(BLOCK)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", NAMES)
def test_a_cell_that_never_dispatched_reads_zero(name):
    empty = {**BLOCK, "buckets": {}, "wall_s": 0.0, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
             "cache_retrieval_s": 0.0, "cache_hits": 0, "cache_misses": 0}
    assert reader(module(name))(with_block(empty)) == 0


@pytest.mark.parametrize("name", NAMES)
def test_block_absent_gives_none(name):
    # the parent's counters, a backend that is not the tpu one, no counters
    assert reader(module(name))(run_of(before=OLD_COUNTERS, after=OLD_COUNTERS)) is None
    assert reader(module(name))(run_of(before={"sig_backend": {"backend": "cpu"}})) is None
    assert reader(module(name))(run_of()) is None


def test_entries_and_files():
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in NAMES.items():
        assert entries[name] == {
            "name": name, "unit": unit, "better": "lower", "source": "program_counter",
            "layer": "verify pipeline", "moves": "setup_s", "workloads": CELLS,
        }
        assert os.path.exists(os.path.join(root, "benchmarks", "layers", module(name) + ".py"))
    # appended after what was there, in the issue's order
    assert [m["name"] for m in bench["per_layer"][-4:]] == list(NAMES)
    by_cell = {w["name"]: w for w in bench["workloads"]}
    assert all(c in by_cell for c in CELLS)
