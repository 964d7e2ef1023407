"""stellar_tpu/analysis — the project-contract static analyzer.

Three layers:

1. per-rule positive/negative fixture snippets (tests/analysis_fixtures/):
   every rule must flag its positive fixture and pass its negative one —
   the fixtures are the executable spec of each contract;
2. engine semantics: suppression-rationale enforcement, locked-by
   registration, parse-error exit code 2, CLI modes;
3. the tier-1 gate: ``test_analysis_clean`` runs the analyzer over the
   LIVE package and asserts zero unsuppressed violations — a contract
   change lands with a fix, a rule update, or a written rationale
   (ROADMAP standing policy).

Plus targeted regressions for the violations the first run surfaced
(direct entry-field writes bypassing mut(), nondeterministic peer/archive
picks).
"""

import json
import os
import re
import subprocess
import sys
import types

import pytest

import stellar_tpu
from stellar_tpu.analysis import analyze_paths, analyze_source, rule_ids
from stellar_tpu.analysis.core import Report, attr_chain
from stellar_tpu.analysis.crules import scan_gil_regions, strip_c_noise

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")
PKG_DIR = os.path.dirname(os.path.abspath(stellar_tpu.__file__))


def run_fixture(name: str) -> Report:
    path = os.path.join(FIXTURES, name)
    with open(path) as f:
        text = f.read()
    m = re.search(r"analysis-fixture-path:\s*(\S+)", text)
    assert m, f"{name} is missing its analysis-fixture-path header"
    return analyze_source(text, m.group(1), path=path)


def rules_hit(report: Report):
    return {v.rule for v in report.violations}


# -- per-rule positive/negative fixtures ------------------------------------

RULE_FIXTURES = [
    ("cow-mutation", "cow_mutation_pos.py", "cow_mutation_neg.py", 7),
    ("trusted-getfield", "trusted_getfield_pos.py", "trusted_getfield_neg.py", 3),
    ("cache-latch", "cache_latch_pos.py", "cache_latch_neg.py", 4),
    ("locked-field", "locked_field_pos.py", "locked_field_neg.py", 3),
    ("determinism", "determinism_pos.py", "determinism_neg.py", 6),
    ("metrics-fast-lane", "metrics_fast_lane_pos.py", "metrics_fast_lane_neg.py", 5),
    ("send-path", "send_path_pos.py", "send_path_neg.py", 3),
    ("durable-write", "durable_write_pos.py", "durable_write_neg.py", 5),
    ("gil-region", "gil_region_pos.c", "gil_region_neg.c", 2),
]


@pytest.mark.parametrize(
    "rule,pos,neg,n_pos", RULE_FIXTURES, ids=[r[0] for r in RULE_FIXTURES]
)
def test_rule_fixtures(rule, pos, neg, n_pos):
    rp = run_fixture(pos)
    hits = [v for v in rp.violations if v.rule == rule]
    assert len(hits) >= n_pos, (
        f"{rule}: expected >= {n_pos} hits in {pos}, got"
        f" {[v.render() for v in rp.violations]}"
    )
    # the positive fixture must not trip OTHER rules (one contract per file)
    assert rules_hit(rp) == {rule}

    rn = run_fixture(neg)
    assert not [v for v in rn.violations if v.rule == rule], (
        f"{rule}: negative fixture flagged:"
        f" {[v.render() for v in rn.violations]}"
    )
    assert not rn.parse_errors


def test_determinism_rule_covers_chaos_plane():
    """r12: the determinism rule's scope includes simulation/ and
    scenarios/ — the chaos plane's replay contract (same topology + seed
    + fault program ⇒ same run) requires seeded rolls and clock-routed
    time in the harness itself, not just in the consensus planes."""
    for path in ("scenarios/faults_fixture.py", "simulation/lg_fixture.py"):
        rp = analyze_source(
            "import time\n\ndef t():\n    return time.time()\n", path
        )
        assert [v.rule for v in rp.violations] == ["determinism"], path
    # seeded construction stays legal (the fix the rule prescribes)
    rp = analyze_source(
        "import random\n_rng = random.Random(7)\n",
        "scenarios/seeded_fixture.py",
    )
    assert not rp.violations


def test_fixture_inventory_covers_every_rule():
    """Every registered rule (meta aside) carries fixture coverage — a new
    rule without an executable spec fails here, and >=6 rules are active
    (the ISSUE acceptance floor)."""
    covered = {r[0] for r in RULE_FIXTURES}
    registered = set(rule_ids())
    assert covered | {"suppression-rationale"} == registered
    assert len(registered) >= 6


# -- suppression semantics ---------------------------------------------------


def test_bare_and_unknown_suppressions_are_violations():
    rp = run_fixture("suppression_pos.py")
    rules = [v.rule for v in rp.violations]
    # the bare suppression reports itself AND fails to silence the hit
    assert rules.count("suppression-rationale") == 2  # bare + unknown rule
    assert "determinism" in rules
    assert not rp.suppressed


def test_rationale_suppression_silences_and_records():
    rn = run_fixture("suppression_neg.py")
    assert not rn.violations
    assert len(rn.suppressed) == 2  # own-line and trailing placements
    assert all(s.rule == "determinism" and s.rationale for s in rn.suppressed)


def test_unused_suppression_is_a_violation():
    """A stale suppression (its violation no longer fires) must fail the
    gate — it would silently pre-suppress a future regression and drift
    the SWEEP.md inventory (the unused-noqa pattern)."""
    rp = analyze_source(
        "def f(app):\n"
        "    # analysis: off determinism -- stale: the wall-clock read below was removed last round\n"
        "    return app.clock.now()\n",
        "scp/stale_fixture.py",
    )
    assert [v.rule for v in rp.violations] == ["suppression-rationale"]
    assert "unused suppression" in rp.violations[0].message
    assert not rp.suppressed


def test_own_line_suppression_skips_comment_continuations():
    """An own-line suppression followed by further comment lines (a
    wrapped rationale) must attach to the next CODE line, not the
    comment."""
    rp = analyze_source(
        "import time\n"
        "\n"
        "def f():\n"
        "    # analysis: off determinism -- harness stopwatch around the\n"
        "    # crank loop; never feeds a consensus decision\n"
        "    return time.time()\n",
        "scp/wrapped_fixture.py",
    )
    assert not rp.violations, [v.render() for v in rp.violations]
    assert len(rp.suppressed) == 1


def test_locked_by_comment_must_sit_on_declaration():
    rp = analyze_source(
        "import threading\n"
        "# analysis: locked-by _lock\n"
        "x = 1\n",
        "crypto/misregistered_fixture.py",
    )
    assert [v.rule for v in rp.violations] == ["suppression-rationale"]


def test_suppression_cannot_silence_the_meta_rule():
    rp = analyze_source(
        "import time\n"
        "# analysis: off suppression-rationale -- nice try\n"
        "t = time.time()  # analysis: off determinism\n",
        "scp/meta_fixture.py",
    )
    assert "suppression-rationale" in {v.rule for v in rp.violations}
    assert "determinism" in {v.rule for v in rp.violations}


# -- engine mechanics --------------------------------------------------------


def test_attr_chain_shapes():
    import ast

    def chain_of(src):
        node = ast.parse(src).body[0].value
        return attr_chain(node)

    assert chain_of("self.entry.data.value") == ["self", "entry", "data", "value"]
    assert chain_of("f.mut().balance") == ["f", "mut()", "balance"]
    assert chain_of("verify_cache().put") == ["verify_cache()", "put"]
    assert chain_of("a[0].b") is None  # subscripts end the walk


def test_parse_error_reported_not_swallowed():
    rp = analyze_source("def broken(:\n", "ledger/broken_fixture.py")
    assert rp.parse_errors and rp.exit_code() == 2


def test_parse_error_beats_clean_files(tmp_path):
    """CLI exit 2 when ANY audited module fails to parse, even if every
    parsed file is clean — a broken parse must never report a clean tree."""
    d = tmp_path / "stellar_tpu" / "ledger"
    d.mkdir(parents=True)
    (d / "ok.py").write_text("x = 1\n")
    (d / "broken.py").write_text("def broken(:\n")
    p = subprocess.run(
        [sys.executable, "-m", "stellar_tpu.analysis", str(tmp_path)],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(PKG_DIR),
    )
    assert p.returncode == 2, p.stdout + p.stderr
    assert "PARSE ERROR" in p.stdout


def test_cli_exit_codes_and_json(tmp_path):
    d = tmp_path / "stellar_tpu" / "scp"
    d.mkdir(parents=True)
    f = d / "clean.py"
    f.write_text("def f(app):\n    return app.clock.now()\n")
    base = [sys.executable, "-m", "stellar_tpu.analysis"]
    cwd = os.path.dirname(PKG_DIR)
    p = subprocess.run(
        base + [str(tmp_path), "--json"], capture_output=True, text=True, cwd=cwd
    )
    assert p.returncode == 0, p.stdout + p.stderr
    doc = json.loads(p.stdout)
    assert doc["clean"] and doc["files_scanned"] == 1

    f.write_text("import time\n\ndef f():\n    return time.time()\n")
    p = subprocess.run(
        base + [str(tmp_path), "--json"], capture_output=True, text=True, cwd=cwd
    )
    assert p.returncode == 1
    doc = json.loads(p.stdout)
    assert [v["rule"] for v in doc["violations"]] == ["determinism"]


def test_cli_rules_listing():
    p = subprocess.run(
        [sys.executable, "-m", "stellar_tpu.analysis", "--rules"],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(PKG_DIR),
    )
    assert p.returncode == 0
    for rid in rule_ids():
        assert rid in p.stdout


def test_c_scanner_string_and_comment_immunity():
    lines = [
        "Py_BEGIN_ALLOW_THREADS",
        '    s = "PyErr_SetString inside a string";',
        "    /* Py_INCREF(comment) */",
        "    // PyLong_AsLong(line comment)",
        "    real_work();",
        "Py_END_ALLOW_THREADS",
        "PyErr_SetString(exc, msg);  /* outside: fine */",
    ]
    assert list(scan_gil_regions(lines)) == []
    stripped = strip_c_noise(['x = "a\\"b" + c; // tail'])
    assert stripped == ["x =   + c; "]


# -- the tier-1 gate ---------------------------------------------------------


def test_analysis_clean():
    """The live package carries zero unsuppressed violations, with >=6
    rules active over the full module + native-C surface.  When this
    fails: fix the regression, or suppress WITH a rationale and record it
    in SWEEP.md (ROADMAP standing policy)."""
    report = analyze_paths([PKG_DIR])
    assert not report.parse_errors, report.parse_errors
    assert not report.violations, "\n".join(
        v.render() for v in report.violations
    )
    assert len(report.rules) >= 6
    assert report.files_scanned > 100  # the whole package, not a subdir
    # every suppression in the live tree carries its reviewed rationale
    assert all(s.rationale for s in report.suppressed)


# -- regressions for the violations the first live run surfaced -------------


def test_make_auth_only_routes_through_mut():
    """accountframe.make_auth_only wrote f.account.balance directly; the
    frame is freshly constructed (never sealed) so behavior is identical,
    but the discipline write must hold even if construction changes."""
    from stellar_tpu.crypto import SecretKey
    from stellar_tpu.ledger.accountframe import AccountFrame

    pk = SecretKey.pseudo_random_for_testing(7).get_public_key()
    f = AccountFrame.make_auth_only(pk)
    assert f.account.balance == -0x8000000000000000
    assert not f._sealed


def test_replace_body_respects_seal(tmp_path):
    """ManageOffer's update path swapped .entry.data.value directly; on a
    SEALED frame that mutates the snapshot shared with the delta/cache.
    replace_body must CoW first: the sealed snapshot stays bit-identical."""
    from stellar_tpu.xdr.base import xdr_copy
    from stellar_tpu.xdr.entries import (
        Asset,
        LedgerEntry,
        LedgerEntryData,
        LedgerEntryType,
        OfferEntry,
        Price,
    )
    from stellar_tpu.xdr.xtypes import PublicKey
    from stellar_tpu.ledger.offerframe import OfferFrame

    seller = PublicKey.from_ed25519(b"\x11" * 32)
    body = OfferEntry(
        sellerID=seller,
        offerID=7,
        selling=Asset.native(),
        buying=Asset.native(),
        amount=100,
        price=Price(1, 2),
        flags=0,
        ext=0,
    )
    frame = OfferFrame(
        LedgerEntry(1, LedgerEntryData(LedgerEntryType.OFFER, body), 0)
    )
    # seal the frame the way a store does: its entry becomes THE shared
    # snapshot (delta/cache/store-buffer all alias it)
    shared = frame.entry
    shared_before = shared.to_xdr()
    frame._sealed = True

    new_body = xdr_copy(body)
    new_body.amount = 1
    frame.replace_body(new_body)

    assert shared.to_xdr() == shared_before  # the snapshot never moved
    assert frame.entry is not shared  # CoW paid
    assert frame.offer is new_body  # typed alias re-bound
    assert not frame._sealed


def _fake_app():
    from stellar_tpu.util.clock import VirtualClock

    return types.SimpleNamespace(clock=VirtualClock(), overlay_manager=None)


def test_itemfetcher_peer_pick_is_deterministic():
    """Tracker used module-level random.choice: two identical runs asked
    different peers.  The pick now rides an item-hash-seeded generator."""
    from stellar_tpu.overlay.itemfetcher import Tracker

    h = bytes(range(32))
    t1 = Tracker(_fake_app(), h, ask_peer=lambda p, ih: None)
    t2 = Tracker(_fake_app(), h, ask_peer=lambda p, ih: None)
    peers = list(range(17))
    assert [t1._rng.choice(peers) for _ in range(20)] == [
        t2._rng.choice(peers) for _ in range(20)
    ]
    # distinct items still spread load across peers
    t3 = Tracker(_fake_app(), bytes(reversed(h)), ask_peer=lambda p, ih: None)
    assert [t1._rng.choice(peers) for _ in range(20)] != [
        t3._rng.choice(peers) for _ in range(20)
    ]


def test_catchup_archive_pick_is_deterministic(tmp_path):
    """CatchupStateMachine picked its archive with module-level
    random.choice; the pick now rides node-identity XOR a construction
    nonce — same construction order replays the same archive walk
    run-to-run, while successive catchup sessions rotate instead of
    pinning one archive forever."""
    from stellar_tpu.crypto import SecretKey
    from stellar_tpu.history.catchupsm import CatchupStateMachine
    from stellar_tpu.util.clock import VirtualClock

    def make_sm():
        app = types.SimpleNamespace(
            clock=VirtualClock(),
            config=types.SimpleNamespace(
                NODE_SEED=SecretKey.pseudo_random_for_testing(3)
            ),
            tmp_dirs=types.SimpleNamespace(
                tmp_dir=lambda name: types.SimpleNamespace(
                    get_name=lambda: str(tmp_path)
                )
            ),
        )
        return CatchupStateMachine(app, "complete", done=lambda ok, h: None)

    archives = ["a", "b", "c", "d"]
    nonce0 = CatchupStateMachine._nonce
    try:
        seq = lambda sm: [sm._rng.choice(archives) for _ in range(10)]  # noqa: E731
        CatchupStateMachine._nonce = nonce0  # "a fresh process"
        run1 = [seq(make_sm()), seq(make_sm())]
        CatchupStateMachine._nonce = nonce0
        run2 = [seq(make_sm()), seq(make_sm())]
        assert run1 == run2  # same construction order replays exactly
        assert run1[0] != run1[1]  # successive sessions rotate the walk
    finally:
        CatchupStateMachine._nonce = nonce0


def test_loopback_fault_rolls_are_seeded():
    """LoopbackPeer's fault-injection generator was unseeded; a chaos run
    that found a bug could not be replayed.  Behavioral contract on REAL
    peers: same construction ORDER => identical roll sequences
    (replayable run-to-run), while distinct peers — pair halves AND
    sibling pairs — roll uncorrelated sequences."""
    import stellar_tpu.tx.testutils as T
    from stellar_tpu.main.application import Application
    from stellar_tpu.overlay.loopback import LoopbackPeer
    from stellar_tpu.overlay.peer import PeerRole
    from stellar_tpu.util.clock import VirtualClock

    app = Application.create(VirtualClock(), T.get_test_config(77), new_db=True)
    seq = lambda p: [p._rng.random() for _ in range(8)]  # noqa: E731
    nonce0 = LoopbackPeer._ctor_nonce
    try:
        def build_run():
            LoopbackPeer._ctor_nonce = nonce0  # "a fresh process"
            return [
                LoopbackPeer(app, PeerRole.WE_CALLED_REMOTE),
                LoopbackPeer(app, PeerRole.REMOTE_CALLED_US),
                LoopbackPeer(app, PeerRole.WE_CALLED_REMOTE),  # sibling pair
            ]
        run1 = [seq(p) for p in build_run()]
        run2 = [seq(p) for p in build_run()]
        assert run1 == run2  # same construction order replays exactly
        a1, b1, a2 = run1
        assert a1 != b1  # pair halves uncorrelated
        assert a1 != a2  # sibling pairs of the SAME role uncorrelated
    finally:
        LoopbackPeer._ctor_nonce = nonce0
        app.graceful_stop()


def test_parse_error_on_nul_bytes_is_reported():
    """ast.parse raises bare ValueError (not SyntaxError) for NUL bytes —
    still a parse error, never a crash or a clean pass."""
    rp = analyze_source("x = 1\x00\n", "ledger/nul_fixture.py")
    assert rp.parse_errors and rp.exit_code() == 2


def test_analyzer_never_rides_the_runtime(tmp_path):
    """Build/test-time only: importing the application planes must not pull
    stellar_tpu.analysis (profile_close --assert-budget pins the same
    contract in-process)."""
    p = subprocess.run(
        [
            sys.executable,
            "-c",
            "import sys\n"
            "import stellar_tpu.main.application\n"
            "import stellar_tpu.ledger.manager\n"
            "import stellar_tpu.crypto.sigbackend\n"
            "assert not any(m.startswith('stellar_tpu.analysis')"
            " for m in sys.modules), 'analysis leaked into the runtime'\n"
            "print('RUNTIME_CLEAN')\n",
        ],
        capture_output=True,
        text=True,
        cwd=os.path.dirname(PKG_DIR),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert p.returncode == 0, p.stdout + p.stderr
    assert "RUNTIME_CLEAN" in p.stdout
