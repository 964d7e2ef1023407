"""The program's account of a bucket's first dispatch (PR 37).

``BatchVerifier.stats()["first_dispatch"]`` says, per bucket, what JAX
reported on the dispatching thread while the bucket's program was traced,
lowered and compiled (``stellar_tpu/ops/__init__.py`` ``CompileEvents``);
the same numbers ride the bucket's first ``ed25519.device_dispatch`` span,
and ``sig.device_flush`` carries ``cold`` on the flush that paid.  On the
CPU the kernel is the XLA lowering at buckets of 16 and 32 lanes: four
first dispatches in the whole file, ~20 s each, two of them at once.
"""

from __future__ import annotations

import importlib
import json
import threading

import pytest

from stellar_tpu.crypto.keys import SecretKey
from stellar_tpu.crypto.sigbackend import (
    CALLER_CLOSE,
    CALLER_OVERLAY,
    TpuSigBackend,
)
from stellar_tpu.trace.tracer import Tracer

STAGES = ("trace_s", "lower_s", "compile_s")
SUMMED = STAGES + ("cache_retrieval_s", "cache_hits", "cache_misses")
PROGRAMS = ("stored", "exported", "traced")
COUNTED = tuple("programs_" + p for p in PROGRAMS)


def triples(n: int, salt: int = 0):
    out = []
    for i in range(n):
        sk = SecretKey.pseudo_random_for_testing(1000 * salt + i)
        msg = b"first dispatch %d %d" % (salt, i)
        out.append((sk.public_raw, msg, sk.sign(msg)))
    return out


def check_record(rec: dict, bucket: int) -> None:
    assert rec["bucket"] == bucket
    assert rec["end"] > rec["start"]
    wall = rec["end"] - rec["start"]
    assert all(rec[k] >= 0 for k in STAGES)
    # JAX's nested reports are counted once: the stages fit the wall time
    assert sum(rec[k] for k in STAGES) <= wall
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["compile_s"] > 0
    # PR 38: where the program came from, and what finding it cost; the
    # stages and the load lie one beside the other inside the wall time
    assert rec["program"] in PROGRAMS
    assert ("program_error" in rec) == (rec["program"] == "traced")
    assert rec["program_load_s"] >= 0
    assert sum(rec[k] for k in STAGES) + rec["program_load_s"] <= wall
    assert rec["rest_s"] == pytest.approx(wall - sum(rec[k] for k in STAGES) - rec["program_load_s"])
    assert rec["cache"] in ("hit", "miss", "off")
    assert rec["cache_hits"] + rec["cache_misses"] <= 1  # one program, asked once
    assert ("compile_time_saved_s" in rec) == (rec["cache"] == "hit")
    assert rec["cache_retrieval_s"] <= rec["compile_s"]
    assert isinstance(rec["thread"], str) and rec["thread"]


@pytest.fixture(scope="module")
def plain():
    """A verifier of its own, tracer off: 16, then 16 again, then 32."""
    from stellar_tpu.ops.verifier import BatchVerifier

    bv = BatchVerifier(max_batch=32, min_device_batch=16)
    seen = {"empty": bv.stats()["first_dispatch"]}
    assert all(bv.verify(triples(10)))
    seen["first"] = bv.stats()["first_dispatch"]
    assert all(bv.verify(triples(12, salt=1)))
    seen["second"] = bv.stats()["first_dispatch"]
    assert all(bv.verify(triples(20, salt=2)))
    seen["wider"] = bv.stats()["first_dispatch"]
    return bv, seen


@pytest.fixture(scope="module")
def backend():
    """A backend with the tracer on whose first two flushes come from two
    threads, one bucket each, under two caller classes; then a third."""
    tracer = Tracer()
    be = TpuSigBackend(max_batch=32, cpu_cutover=0, tracer=tracer)
    jobs = [(triples(10, salt=3), CALLER_OVERLAY), (triples(20, salt=4), CALLER_CLOSE)]
    out: dict = {}

    def flush(items, caller):
        with tracer.span("test.cause", req=caller):
            out[caller] = be.verify_batch(items, caller=caller)

    threads = [threading.Thread(target=flush, args=j) for j in jobs]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(out[CALLER_OVERLAY]) and all(out[CALLER_CLOSE])
    after_two = be.stats()["first_dispatch"]
    assert all(be.verify_batch(triples(30, salt=5), caller=CALLER_CLOSE))
    return be, tracer.spans(), after_two


def test_nothing_dispatched_nothing_recorded(plain):
    _, seen = plain
    fd = seen["empty"]
    assert fd["buckets"] == {} and fd["wall_s"] == 0
    assert all(fd[k] == 0 for k in SUMMED + COUNTED)
    assert fd["recompiles"] == {"events": 0, "seconds": 0.0, "bucket": None}
    assert set(fd["unattributed"]) == {"events", "seconds"}


def test_first_verify_leaves_one_record_a_bucket(plain):
    _, seen = plain
    fd = seen["first"]
    assert list(fd["buckets"]) == [16]
    rec = fd["buckets"][16]
    check_record(rec, 16)
    # called with no backend above it: no caller class, the caller's thread
    assert rec["caller"] is None and rec["thread"] == threading.current_thread().name
    assert fd["wall_s"] == pytest.approx(rec["end"] - rec["start"])
    for k in SUMMED:
        assert fd[k] == rec[k]
    assert {k: fd[k] for k in COUNTED} == {k: int(k == "programs_" + rec["program"]) for k in COUNTED}
    json.dumps(fd)  # /info serializes it


def test_second_verify_of_a_bucket_changes_nothing(plain):
    _, seen = plain
    keep = lambda fd: {k: v for k, v in fd.items() if k != "unattributed"}  # noqa: E731
    assert keep(seen["second"]) == keep(seen["first"])
    assert seen["second"]["recompiles"]["events"] == 0


def test_wider_batch_adds_exactly_the_new_bucket(plain):
    _, seen = plain
    fd = seen["wider"]
    assert sorted(fd["buckets"]) == [16, 32]
    assert fd["buckets"][16] == seen["first"]["buckets"][16]
    check_record(fd["buckets"][32], 32)
    # one after the other on one thread: the union is the sum
    walls = [r["end"] - r["start"] for r in fd["buckets"].values()]
    assert fd["wall_s"] == pytest.approx(sum(walls))
    for k in SUMMED:
        assert fd[k] == pytest.approx(sum(r[k] for r in fd["buckets"].values()))
    assert sum(fd[k] for k in COUNTED) == 2
    assert fd["recompiles"]["events"] == 0


def test_account_counts_with_the_tracer_off(plain):
    bv, seen = plain
    assert not bv._tracer.enabled
    assert seen["wider"]["trace_s"] > 0


def test_stats_and_info_carry_the_block(plain):
    bv, seen = plain
    assert "verify_seconds" not in bv.stats()
    assert bv.stats()["first_dispatch"]["buckets"] == seen["wider"]["buckets"]


def test_two_buckets_from_two_threads(backend):
    _, _, fd = backend
    assert sorted(fd["buckets"]) == [16, 32]
    r16, r32 = fd["buckets"][16], fd["buckets"][32]
    check_record(r16, 16)
    check_record(r32, 32)
    # the caller class the backend was called under, on its worker's thread
    assert (r16["caller"], r32["caller"]) == (CALLER_OVERLAY, CALLER_CLOSE)
    assert r16["thread"] == r32["thread"] == "tpu-verify"
    walls = [r["end"] - r["start"] for r in (r16, r32)]
    assert max(walls) <= fd["wall_s"] <= sum(walls)
    # they ran at once, so the union is shorter than the sum
    assert max(r16["start"], r32["start"]) < min(r16["end"], r32["end"])
    assert fd["wall_s"] < sum(walls)
    assert fd["recompiles"]["events"] == 0


def test_two_verifiers_share_one_listener(plain, backend):
    from jax._src import monitoring

    import stellar_tpu.ops as ops

    events = ops.compile_events

    def ours():
        return [
            sum(1 for f in listeners() if getattr(f, "__self__", None) is events)
            for listeners in (monitoring.get_event_duration_listeners, monitoring.get_event_listeners)
        ]

    assert ours() == [1, 1]
    # a second import of the package registers nothing and keeps the accounts
    importlib.reload(ops)
    assert ops.compile_events is events and ours() == [1, 1]
    # each verifier holds its own buckets; what nobody dispatched is one tally
    bv, _ = plain
    be, _, _ = backend
    a, b = bv.stats()["first_dispatch"], be.stats()["first_dispatch"]
    assert a["buckets"][16] != b["buckets"][16]
    assert a["unattributed"] == b["unattributed"]


def dispatches(spans):
    return sorted((s for s in spans if s.name == "ed25519.device_dispatch"), key=lambda s: s.start)


def test_first_dispatch_span_carries_the_account(backend):
    be, spans, _ = backend
    recs = be.stats()["first_dispatch"]["buckets"]
    got = dispatches(spans)
    assert [s.attrs["bucket"] for s in got] == [16, 32, 32] or [s.attrs["bucket"] for s in got] == [32, 16, 32]
    for s in got[:2]:
        rec = recs[s.attrs["bucket"]]
        assert s.attrs["first"] is True and s.attrs["backend"] == "xla"
        for k in STAGES + ("cache_retrieval_s", "cache", "rest_s", "caller", "program"):
            assert s.attrs[k] == rec[k]
        assert ("compile_time_saved_s" in s.attrs) == (rec["cache"] == "hit")
        # the record lies inside its span, on the tracer's clock
        assert s.start <= rec["start"] < rec["end"] <= s.end
    # a later dispatch of a warm bucket carries the four it always has: the
    # bucket, the lowering, and (PR 45) what was uploaded, in how many pieces
    assert got[2].attrs == {"bucket": 32, "backend": "xla", "shards": 1, "upload_bytes": 128 * 32}


def test_device_flush_is_marked_cold_only_when_it_was(backend):
    _, spans, _ = backend
    flushes = sorted((s for s in spans if s.name == "sig.device_flush"), key=lambda s: s.start)
    assert [s.attrs.get("cold") for s in flushes] == [1, 1, None]
    assert "cold" not in flushes[2].attrs
    # the flush that paid names its cause: the span open where it was asked for
    causes = {s.sid: s for s in spans if s.name == "test.cause"}
    assert {causes[s.parent].req for s in flushes[:2]} == {CALLER_OVERLAY, CALLER_CLOSE}
    assert all(s.attrs["items"] in (10, 20, 30) and s.attrs["chunks"] == 1 for s in flushes)


def test_span_attributes_only_with_the_tracer_on(plain, backend):
    bv, _ = plain
    assert bv._tracer.spans() == []  # the disabled tracer recorded nothing
    _, spans, _ = backend
    assert sum(1 for s in dispatches(spans) if s.attrs.get("first")) == 2


def test_a_compile_after_warm_up_is_named(plain):
    """A stage event on a thread that is dispatching a warm bucket is a
    recompile of that bucket; one on an unmarked thread is nobody's."""
    import stellar_tpu.ops as ops

    bv, _ = plain
    events = ops.compile_events
    trace, lower, compile_ = ops.STAGES
    loose = events.unattributed.stats()
    events.charge(bv._programs._recompiles, 32)
    try:
        # a jit inside a jit: the inner trace is reported first and lies
        # inside the outer one, which takes its seconds back
        events._on_duration(trace, 0.25)
        events._on_duration(trace, 1.0)
    finally:
        events.charge(None)
    rc = bv.stats()["first_dispatch"]["recompiles"]
    assert rc == {"events": 2, "seconds": pytest.approx(1.0), "bucket": 32}
    assert events.unattributed.stats()["events"] == loose["events"]
    events._on_duration(lower, 0.5)
    events._on_event("/jax/compilation_cache/cache_hits")
    events._on_duration("/jax/core/some_other_duration", 9.0)
    now = events.unattributed.stats()
    assert now["events"] == loose["events"] + 1
    assert now["seconds"] == pytest.approx(loose["seconds"] + 0.5)
    assert bv.stats()["first_dispatch"]["recompiles"]["events"] == 2
    assert list(bv.stats()["first_dispatch"]["buckets"]) == [16, 32]


def test_info_returns_the_block():
    from stellar_tpu.main.application import Application
    from stellar_tpu.tx import testutils as T
    from stellar_tpu.util.clock import VIRTUAL_TIME, VirtualClock

    clock = VirtualClock(VIRTUAL_TIME)
    cfg = T.get_test_config(86, backend="tpu")
    cfg.HTTP_PORT = 0
    cfg.TRACE_ENABLED = False
    a = Application.create(clock, cfg, new_db=True)
    try:
        sb = a.command_handler.handle_info({})["info"]["sig_backend"]
        json.dumps(sb)
        fd = sb["first_dispatch"]
        assert fd["buckets"] == {} and fd["wall_s"] == 0
        assert set(fd) == {"buckets", "wall_s", *SUMMED, *COUNTED, "unattributed", "recompiles"}
    finally:
        a.graceful_stop()
        clock.shutdown()
