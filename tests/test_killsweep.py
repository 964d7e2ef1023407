"""Kill-sweep harness (scenarios/killsweep.py, ISSUE r18): real
subprocess hard-kills at registered durable-write kill-points, restart,
and bit-exact recovery vs an unkilled control.

The tier-1 leg sweeps a representative point per plane (SQL commit,
bucket staging incl. the torn-write modes, publish commit) — ~12 child
processes.  The FULL sweep (every point × mode, ~80 children, ~60 s)
runs behind ``-m slow``.
"""

from __future__ import annotations

import pytest

from stellar_tpu.scenarios.killsweep import run_kill_sweep

TIER1_POINTS = [
    "close.pre-commit",      # every durable close artifact staged, no COMMIT
    "bucket.fresh:write",    # + truncate/torn modes on the staged file
    "publish.commit-json:staged",  # mid-publish, post-fsync pre-rename
]


def _assert_green(report, expect_points):
    assert not report.get("error"), report
    assert report["ok"], [
        v for v in report["verdicts"] if not v["ok"]
    ]
    swept_points = {v["point"] for v in report["verdicts"]}
    assert swept_points == set(expect_points)
    # every kill child actually died at its point and every resume
    # landed bit-exact on the control trajectory (report["ok"] covers
    # it; re-assert the per-verdict floor for a readable failure)
    for v in report["verdicts"]:
        assert v["ok"], v
        assert v["selfcheck"] in ("ok", "repaired"), v
        assert v["resumed_lcl"] == report["target_ledger"], v


def test_kill_sweep_representative_points(tmp_path):
    report = run_kill_sweep(
        points=TIER1_POINTS, base_dir=str(tmp_path), log=lambda s: None
    )
    _assert_green(report, TIER1_POINTS)
    # the corruptible :write stage fans out into all three fault modes
    modes = {
        (v["point"], v["mode"]) for v in report["verdicts"]
    }
    assert ("bucket.fresh:write", "truncate") in modes
    assert ("bucket.fresh:write", "torn") in modes
    # a filtered run must report what it actually killed — only the
    # tier-1 points — separately from the window's coverage
    assert report["points_swept"] == sorted(TIER1_POINTS)
    # the control window exercises (nearly) the whole registered
    # inventory — the acceptance's >= 25 distinct points.  The C merge
    # engine's point is host-dependent (toolchain-less hosts fall back
    # to the Python engine, whose points are swept instead).
    assert len(report["points_hit"]) >= 25, report["points_hit"]
    assert set(report["points_unexercised"]) <= {
        "bucket.native-merge:staged"
    }, report["points_unexercised"]


def test_kill_sweep_cli_rejects_unknown_point():
    from stellar_tpu.scenarios.__main__ import main

    assert main(["--kill-sweep", "--points", "not.a.point"]) == 2


@pytest.mark.slow
def test_kill_sweep_full(tmp_path):
    """Every registered point the window crosses, every applicable
    fault mode."""
    report = run_kill_sweep(base_dir=str(tmp_path), log=lambda s: None)
    assert not report.get("error"), report
    assert report["ok"], [v for v in report["verdicts"] if not v["ok"]]
    assert len(report["points_hit"]) >= 25
    # unfiltered: everything the window crossed was killed
    assert report["points_swept"] == report["points_hit"]
    assert set(report["points_unexercised"]) <= {
        "bucket.native-merge:staged"
    }
    assert report["recovered"] == report["swept"] >= 30
