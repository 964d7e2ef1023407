#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the validator still starts on the chip.

Deployment driven: "a new validator with a chip joins by replaying one
checkpoint of history", through ``python -m stellar_tpu.main.cli`` and the
admin HTTP routes only.

  Phase A  the network's history, on the host.  One standalone 1-of-1
           validator (SIGNATURE_BACKEND="cpu", JAX_PLATFORMS=cpu, disk
           sqlite) is the plain reference: /generateload creates the
           accounts and fills three ledgers with 5000 payments each (the
           BASELINE.json primary-metric width), and the checkpoint at
           ledger 7 is published to a file archive.
  Phase B  the node under test, on the chip.  A fresh SIGNATURE_BACKEND="tpu"
           node, every verify knob at its default, replays that history
           with /catchup?mode=complete — the signatures of the ledgers ahead
           prefetched through the close pipeline in SIG_BATCH_MAX-lane
           batches filled across ledger boundaries — must land on
           the producer's anchor hash, then closes one ledger of its own
           from /tx submissions.  What did the work is read back from
           /info, /trace and /invariants and asserted: a Mosaic-compiled
           Pallas kernel on a TPU, every replayed signature accounted for
           between device lanes and the small-batch cutover, and not one
           batch finished on the host by the dispatch watchdog.
  Phase B' the same replay from a fresh DB with the compile cache warm
           (not in the rehearsal).
  Kernel   each shipped Pallas program once on one granule, in a child of
           its own: verify, the DEVICE_HASH fused program, sha256_pallas —
           bit-exact against libsodium and hashlib.

This parent never initializes a JAX backend (a chip belongs to one process
at a time); every child is started with ``cwd=<checkout>`` and stopped
before the next one that needs the chip.  Without a TPU the script fails —
``--rehearse-cpu`` runs the same flow at a tiny size on the CPU and labels
itself a rehearsal.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``; any failed phase exits non-zero without
it.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import shutil
import signal
import socket
import sqlite3
import statistics
import subprocess
import sys
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
ARCHIVE = "smoke"
DEADLINE_S = 1150  # the contract allows 1200 s, compilation included
SYNCED = "Synced!"
LOADGEN_FIRST_ACCOUNT = 5000  # simulation/loadgen.py: pseudo_random(5000 + i)
SMALL_LEDGER_TXS = 20  # ledger 2: below any cutover

# the deployment's size; ``--rehearse-cpu`` swaps in REHEARSAL
REAL = {
    "ledger_txs": 5000,  # BASELINE.json primary metric
    "accounts": 5001,
    "payment_ledgers": 3,
    "sig_batch_max": 4096,  # Config default
    "cpu_cutover": 1024,  # Config default
    "kernel_lanes": 512,  # one Pallas granule (ops/ed25519_pallas.NT)
}
# two buckets above the cutover per ledger, like 4096 + 1024 (both are
# buckets the tier-1 suite compiles anyway, so the persistent cache is shared)
REHEARSAL = {
    "ledger_txs": 80,
    "accounts": 120,
    "payment_ledgers": 3,
    "sig_batch_max": 64,
    "cpu_cutover": 32,
    "kernel_lanes": 64,
}
CHECKPOINT_FREQUENCY = 8  # the reference's accelerated-time value (BASELINE.md)


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - T0:6.1f}s] {msg}", flush=True)


T0 = time.monotonic()


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - T0)


def wait_for(what: str, fn, timeout: float, interval: float = 0.5):
    """Poll ``fn`` until it returns something truthy; SmokeFailure after
    ``timeout`` seconds (or at the script's own deadline)."""
    end = time.monotonic() + min(timeout, max(1.0, remaining()))
    while True:
        got = fn()
        if got:
            return got
        if time.monotonic() > end:
            raise SmokeFailure(f"timed out after {timeout:.0f}s waiting for {what}")
        time.sleep(interval)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


class Node:
    """One ``python -m stellar_tpu.main.cli --conf <cfg>`` child and its
    admin HTTP port."""

    def __init__(self, name: str, conf: str, port: int, env: dict, logdir: str):
        self.name, self.conf, self.port, self.env = name, conf, port, env
        self.log_path = os.path.join(logdir, f"{name}.log")
        self.proc = None

    def cli(self, *flags: str, timeout: float = 300.0) -> None:
        """A run-to-completion CLI mode (--newdb, --newhist, --forcescp)."""
        with open(self.log_path, "a") as lf:
            lf.write(f"--- cli {' '.join(flags)}\n")
            lf.flush()
            r = subprocess.run(
                [sys.executable, "-m", "stellar_tpu.main.cli",
                 "--conf", self.conf, *flags],
                cwd=HERE, env=self.env, stdout=lf, stderr=subprocess.STDOUT,
                timeout=min(timeout, max(1.0, remaining())),
            )
        check(
            r.returncode == 0,
            f"{self.name}: cli {' '.join(flags)} exited {r.returncode}\n"
            + tail(self.log_path),
        )

    def start(self) -> None:
        lf = open(self.log_path, "a")
        lf.write("--- run\n")
        lf.flush()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "stellar_tpu.main.cli", "--conf", self.conf],
            cwd=HERE, env=self.env, stdout=lf, stderr=subprocess.STDOUT,
            start_new_session=True,  # its cp/gzip helpers die with it
        )
        lf.close()

    def get(self, path: str, timeout: float = 30.0) -> dict:
        url = f"http://127.0.0.1:{self.port}/{path}"
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return json.loads(resp.read())

    def poll(self, path: str, timeout: float = 30.0):
        """``get`` that answers None while the node cannot be reached — not
        listening yet, or inside one long crank (a 5000-tx close, a whole
        replay) that serves no HTTP meanwhile.  A dead node is a failure."""
        if self.proc.poll() is not None:
            raise SmokeFailure(
                f"{self.name} exited {self.proc.returncode} early\n"
                + tail(self.log_path)
            )
        try:
            return self.get(path, timeout)
        except (urllib.error.URLError, ConnectionError, TimeoutError, OSError):
            return None

    def info(self, timeout: float = 30.0):
        got = self.poll("info", timeout)
        return got["info"] if got else None

    def wait_info(self, what: str, pred, timeout: float) -> dict:
        def probe():
            i = self.info(timeout=min(timeout, 600.0))
            return i if i and pred(i) else None

        return wait_for(f"{self.name}: {what}", probe, timeout)

    def stop(self) -> None:
        """SIGTERM and expect a clean exit."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeFailure(f"{self.name} ignored SIGTERM for 60s")
        check(rc == 0, f"{self.name} exited {rc} on SIGTERM\n" + tail(self.log_path))

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()


def node_config(
    path: str, workdir: str, name: str, port: int, seed_strkey: str,
    pub_strkey: str, passphrase: str, archive_dir: str, extra: dict,
    writable_archive: bool,
) -> None:
    """A standalone 1-of-1 validator's TOML, with every path absolute and
    under the work directory (the relative defaults would litter the
    checkout)."""
    spec = {"get": f"cp {archive_dir}/{{0}} {{1}}"}
    if writable_archive:
        spec["put"] = f"cp {{0}} {archive_dir}/{{1}}"
        spec["mkdir"] = f"mkdir -p {archive_dir}/{{0}}"
    top = {
        "HTTP_PORT": port,
        "PEER_PORT": free_port(),
        "RUN_STANDALONE": True,
        "NODE_IS_VALIDATOR": True,
        "NETWORK_PASSPHRASE": passphrase,
        "NODE_SEED": seed_strkey,
        "DATABASE": f"sqlite3://{workdir}/{name}.db",
        "BUCKET_DIR_PATH": f"{workdir}/{name}-buckets",
        "TMP_DIR_PATH": f"{workdir}/{name}-tmp",
        "CHECKPOINT_FREQUENCY": CHECKPOINT_FREQUENCY,
        **extra,
    }
    lines = [f"{k} = {json.dumps(v)}" for k, v in top.items()]
    lines += ["[QUORUM_SET]", "THRESHOLD = 1", f"VALIDATORS = [{json.dumps(pub_strkey)}]"]
    lines += [f"[HISTORY.{ARCHIVE}]"] + [f"{k} = {json.dumps(v)}" for k, v in spec.items()]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


def db_rows(db_path: str, sql: str, args=()) -> list:
    con = sqlite3.connect(f"file:{db_path}?mode=ro", uri=True)
    try:
        return con.execute(sql, args).fetchall()
    finally:
        con.close()


def cache_entries(path: str) -> int:
    try:
        # JAX's own entries: the program store's subdirectory
        # (stellar_tpu/ops/programs.py) is not one
        return sum(
            1
            for n in os.listdir(path)
            if not n.startswith(".") and os.path.isfile(os.path.join(path, n))
        )
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# Phase A — the network's history, on the host
# ---------------------------------------------------------------------------


def phase_a(ctx: dict) -> dict:
    size, node = ctx["size"], ctx["producer"]
    width = size["ledger_txs"]
    node.cli("--newdb")
    node.cli("--newhist", ARCHIVE)
    node.cli("--forcescp")
    node.start()
    node.wait_info("synced", lambda i: i["state"] == SYNCED, 120)

    def close() -> int:
        """MANUAL_CLOSE cadence: one ledger per call, once its load is in."""
        lcl = node.get("info", 120)["info"]["ledger"]["num"]
        node.get("manualclose", 120)
        node.wait_info(
            f"ledger {lcl + 1}", lambda i: i["ledger"]["num"] == lcl + 1, 300
        )
        return lcl + 1

    def load(accounts: int = 0, txs: int = 0) -> None:
        """/generateload, then wait until the herder holds every tx."""
        want = node.get("ingest", 120)["admitted"] + accounts + txs
        node.get(
            f"generateload?accounts={accounts}&txs={txs}&txrate=20000", 120
        )
        wait_for(
            f"producer: {accounts + txs} txs admitted",
            lambda: (node.poll("ingest", 300) or {}).get("admitted", 0) >= want,
            600,
        )

    # ledger 2 carries the MAX_TX_SET_SIZE upgrade (genesis allows 100) and
    # a few creates: the one flush of the replay that stays under the
    # cutover.  Ledgers 3 and 4 create the rest of the accounts.
    rest = size["accounts"] - SMALL_LEDGER_TXS
    for n in (SMALL_LEDGER_TXS, rest - rest // 2, rest // 2):
        load(accounts=n)
        close()
    for _ in range(size["payment_ledgers"]):
        load(txs=width)
        anchor = close()
    check(
        (anchor + 1) % CHECKPOINT_FREQUENCY == 0,
        f"ledger {anchor} is not a checkpoint boundary",
    )
    has_path = os.path.join(ctx["archive"], ".well-known", "stellar-history.json")

    def published():
        try:
            with open(has_path) as f:
                return json.load(f).get("currentLedger") == anchor
        except (OSError, ValueError):
            return False

    wait_for(f"checkpoint {anchor} published", published, 120)
    lcl = node.get("info", 60)["info"]["ledger"]
    check(lcl["num"] == anchor, f"producer moved past the anchor: {lcl}")
    inv = node.get("invariants", 60)
    check(inv["total_violations"] == 0, f"producer invariants: {inv}")
    node.stop()

    per_ledger = dict(
        db_rows(
            ctx["producer_db"],
            "SELECT ledgerseq, COUNT(*) FROM txhistory GROUP BY ledgerseq",
        )
    )
    check(
        all(per_ledger.get(s) == width
            for s in range(anchor - size["payment_ledgers"] + 1, anchor + 1)),
        f"payment ledgers did not fill to {width} txs: {per_ledger}",
    )
    return {
        "anchor": anchor,
        "anchor_hash": lcl["hash"],
        "txs_per_ledger": {str(k): v for k, v in sorted(per_ledger.items())},
        "accounts": db_rows(ctx["producer_db"], "SELECT COUNT(*) FROM accounts")[0][0],
        "txhistory_rows": sum(per_ledger.values()),
    }


# ---------------------------------------------------------------------------
# Phase B — the node under test, on the chip
# ---------------------------------------------------------------------------


def check_backend(ctx: dict, sb: dict, hist: dict) -> None:
    """What did the replay's work, from /info's sig_backend block.  The
    replay prefetches through the close pipeline, across ledger boundaries
    (``ledger/closepipeline.py``), so which flush carried which ledger is
    the scheduler's business; what has to hold is that every signature of
    the history was verified once, in a batch — none by the eager
    one-at-a-time path, none by the watchdog's — that the device took at
    least the payment ledgers, and that it took them in SIG_BATCH_MAX
    chunks."""
    size = ctx["size"]
    want_platform = "cpu" if ctx["rehearsal"] else "tpu"
    check(sb.get("platform") == want_platform, f"node runs jax on {sb.get('platform')!r}")
    check(sb.get("device_kind") and sb.get("device_count", 0) >= 1, f"no device identity: {sb}")
    if ctx["rehearsal"]:
        check(sb["kernel"] == "xla", f"rehearsal kernel is {sb['kernel']!r}")
    else:
        check(
            sb["kernel"] == "pallas" and sb["interpret"] is False,
            f"kernel lowering is {sb['kernel']!r} interpret={sb['interpret']!r}",
        )
    check(sb["native_host_stage"] is True, "the native host stage is not live")
    signatures = sum(hist["txs_per_ledger"].values())
    check(
        sb["items"] + sb["cpu_cutover_items"] == signatures,
        f"device verified {sb['items']} items and the cutover {sb['cpu_cutover_items']};"
        f" history holds {signatures} signatures",
    )
    check(
        sb["items"] >= size["payment_ledgers"] * size["sig_batch_max"],
        f"the device got only {sb['items']} of the history's signatures",
    )
    check(
        sb["device_calls"] >= -(-sb["items"] // size["sig_batch_max"]) and sb["lanes"] >= sb["items"],
        f"{sb['items']} items in {sb['device_calls']} dispatches of {sb['lanes']} lanes",
    )
    check(
        sb.get("eager_host_verifies", 0) == 0,
        f"{sb.get('eager_host_verifies')} signature checks at apply missed the prefetch",
    )
    check(sb["gate_rejects"] == 0 and sb["host_assist_items"] == 0, f"{sb}")
    check(
        sb["wedge_fallback_items"] == 0 and sb["wedge_latch_flips"] == {},
        f"the dispatch watchdog abandoned the device: {sb}",
    )


def compile_split(trace: dict) -> dict:
    """Per bucket, the first device dispatch (Python trace + lower + XLA
    compile or cache load, then enqueue) apart from the later ones."""
    by_bucket: dict = {}
    for ev in trace["traceEvents"]:
        if ev["name"] == "ed25519.device_dispatch":
            by_bucket.setdefault(ev["args"]["bucket"], []).append((ev["ts"], ev["dur"]))
    out = {}
    for bucket, evs in sorted(by_bucket.items()):
        durs = [d for _, d in sorted(evs)]
        out[str(bucket)] = {
            "first_dispatch_s": round(durs[0] / 1e6, 3),
            "later_dispatch_median_ms": (
                round(statistics.median(durs[1:]) / 1e3, 3) if durs[1:] else None
            ),
            "dispatches": len(durs),
        }
    return out


def replay(ctx: dict, node: Node, hist: dict) -> dict:
    """--newdb, start, /catchup?mode=complete, wait for the anchor."""
    t0 = time.monotonic()
    node.cli("--newdb")
    node.start()
    node.wait_info("admin http up", lambda i: True, 300)
    t_up = time.monotonic()
    got = node.get("catchup?mode=complete", 120)
    check(got.get("mode") == "complete", f"/catchup answered {got}")
    anchor = hist["anchor"]
    info = node.wait_info(
        f"replay to ledger {anchor}",
        lambda i: i["ledger"]["num"] == anchor and i["state"] == SYNCED,
        900,
    )
    t_done = time.monotonic()
    check(
        info["ledger"]["hash"] == hist["anchor_hash"],
        f"anchor hash {info['ledger']['hash']} != producer's {hist['anchor_hash']}",
    )
    trace = node.get("trace", 120)
    reasons = sorted(
        {
            ev.get("args", {}).get("reason")
            for ev in trace["traceEvents"]
            if ev["name"] in ("sig.host_verify", "sig.host_torsion")
        }
    )
    check(
        set(reasons) <= {"cutover"},
        f"host-verify spans carry reasons {reasons}",
    )
    check(trace["dropped_spans"] == 0, "the span ring overflowed")
    return {
        "boot_s": round(t_up - t0, 1),
        "catchup_s": round(t_done - t_up, 1),
        "host_verify_reasons": reasons,
        "buckets": compile_split(trace),
    }


def fresh_payments(passphrase: str, seq: int, count: int) -> list:
    """Root-signed payments after sequence number ``seq``, built here, for /tx."""
    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.tx import testutils as T
    from types import SimpleNamespace

    network_id = hashlib.sha256(passphrase.encode()).digest()
    root = SecretKey.from_seed(network_id)  # the genesis master key
    dest = SecretKey.pseudo_random_for_testing(LOADGEN_FIRST_ACCOUNT)
    shim = SimpleNamespace(network_id=network_id)
    return [
        T.tx_from_ops(shim, root, seq + 1 + i, [T.payment_op(dest, 1000 + i)], fee=100)
        for i in range(count)
    ]


def phase_b(ctx: dict, hist: dict) -> dict:
    node = ctx["replayer"]
    anchor = hist["anchor"]
    out = replay(ctx, node, hist)
    sb = node.get("info", 60)["info"]["sig_backend"]
    check_backend(ctx, sb, hist)
    inv = node.get("invariants", 60)
    check(
        inv["total_violations"] == 0 and inv["closes_checked"] == anchor - 1,
        f"invariants after the replay: {inv}",
    )
    node.stop()
    out.update(
        sig_backend=sb,
        invariants={k: inv[k] for k in ("closes_checked", "total_violations")},
    )

    # A caught-up node only follows; in a one-node network nobody leads.
    # The operator's step is the reference's: --forcescp, start again —
    # the node joins SCP from its own LCL and closes on its own cadence.
    t0 = time.monotonic()
    node.cli("--forcescp")
    node.start()
    info = node.wait_info("synced after the restart", lambda i: i["state"] == SYNCED, 300)
    check(
        info["ledger"]["num"] > anchor or info["ledger"]["hash"] == hist["anchor_hash"],
        f"restarted on {info['ledger']}, not on the anchor",
    )
    restart_s = round(time.monotonic() - t0, 1)
    seq0 = node.get("testacc?name=root", 60)["seqnum"]
    txs = fresh_payments(ctx["passphrase"], seq0, 3)
    for tx in txs:
        got = node.get("tx?blob=" + tx.envelope.to_xdr().hex(), 60)
        check(got.get("status") == "PENDING", f"/tx answered {got}")
    wait_for(
        "the node's own ledger with the /tx payments",
        lambda: (node.poll("testacc?name=root", 60) or {}).get("seqnum") == seq0 + len(txs),
        120,
    )
    info = node.get("info", 60)["info"]
    check(info["ledger"]["num"] > anchor, f"no ledger closed past the anchor: {info}")
    inv = node.get("invariants", 60)
    check(
        inv["total_violations"] == 0 and inv["closes_checked"] >= 1,
        f"invariants on the node's own ledgers: {inv}",
    )
    # three batches of one at admission, then all cache hits at the close
    sb = info["sig_backend"]
    check(
        sb["cpu_cutover_items"] == len(txs) and sb["items"] == 0
        and sb["wedge_fallback_items"] == 0 and sb["wedge_latch_flips"] == {},
        f"after the restart: {sb}",
    )
    node.stop()

    db = ctx["replayer_db"]
    accounts = db_rows(db, "SELECT COUNT(*) FROM accounts")[0][0]
    rows = db_rows(db, "SELECT COUNT(*) FROM txhistory WHERE ledgerseq <= ?", (anchor,))[0][0]
    check(
        accounts == hist["accounts"] and rows == hist["txhistory_rows"],
        f"replayed state differs: {accounts} accounts / {rows} txhistory rows,"
        f" producer has {hist['accounts']} / {hist['txhistory_rows']}",
    )
    anchor_hash = db_rows(
        db, "SELECT ledgerhash FROM ledgerheaders WHERE ledgerseq = ?", (anchor,)
    )[0][0]
    check(anchor_hash == hist["anchor_hash"], f"anchor hash in DB {anchor_hash}")
    own = {
        r[0] for r in db_rows(db, "SELECT txid FROM txhistory WHERE ledgerseq > ?", (anchor,))
    }
    check(
        own == {tx.get_contents_hash().hex() for tx in txs},
        f"the node's own ledger(s) hold {len(own)} txs, not the {len(txs)} submitted",
    )
    out.update(
        anchor_hash=anchor_hash,
        accounts=accounts,
        txhistory_rows=rows,
        restart_to_synced_s=restart_s,
        own_ledger=info["ledger"]["num"],
        own_ledger_txs=len(own),
        own_ledger_invariants={k: inv[k] for k in ("closes_checked", "total_violations")},
    )
    return out


def phase_b_warm(ctx: dict, hist: dict) -> dict:
    """The same replay into a fresh DB, compile cache warm."""
    node = ctx["replayer_warm"]
    out = replay(ctx, node, hist)
    sb = node.get("info", 60)["info"]["sig_backend"]
    check_backend(ctx, sb, hist)
    node.stop()
    out["sig_backend"] = {
        k: sb[k] for k in ("items", "device_calls", "wedge_fallback_items", "wedge_latch_flips")
    }
    return out


# ---------------------------------------------------------------------------
# Kernel leg — a child of its own (this function touches JAX)
# ---------------------------------------------------------------------------

# message lengths around the SHA-512 single-block boundary of R‖A‖M (47 ->
# 111 bytes on the device, 48 -> 112 bytes on the host residual path)
_DH_MSG_LENS = (0, 1, 31, 32, 46, 47, 48, 49, 64, 200)
# SHA-256 padding boundaries, one to three blocks
_SHA256_LENS = (0, 1, 55, 56, 63, 64, 65, 111, 112, 119, 120, 200)


def _boundary_lane_items(n: int):
    """__graft_entry__._mixed_lane_items' four lane classes (valid,
    corrupted R, corrupted s, undecompressable A) over messages whose
    lengths straddle the device-hash block boundary; verdicts from
    libsodium."""
    import numpy as np

    import __graft_entry__ as graft
    from stellar_tpu.crypto import SecretKey, sodium

    bad_a = graft._bad_point_bytes()
    items, want = [], np.zeros(n, dtype=bool)
    for i in range(n):
        sk = SecretKey.pseudo_random_for_testing(70_000 + i)
        mlen = _DH_MSG_LENS[(i // 4) % len(_DH_MSG_LENS)]
        msg = hashlib.sha512(b"smoke %d" % i).digest() * 4
        msg = msg[:mlen]
        pk, sig = sk.public_raw, bytearray(sk.sign(msg))
        if i % 4 == 1:
            sig[i % 32] ^= 1 << (i % 8)
        elif i % 4 == 2:
            sig[32] ^= 1 << (i % 8)
        elif i % 4 == 3:
            pk = bad_a
        sig = bytes(sig)
        want[i] = sodium.verify_detached(sig, msg, pk)
        items.append((pk, msg, sig))
    return items, want


def _timed_twice(fn):
    """(result, first call seconds, second call seconds): the first call
    compiles, the second only runs."""
    t0 = time.perf_counter()
    first = fn()
    t1 = time.perf_counter()
    second = fn()
    t2 = time.perf_counter()
    check(first == second, "a kernel's second run disagrees with its first")
    return first, round(t1 - t0, 3), round(t2 - t1, 4)


def _device_or_fail(rehearsal: bool) -> dict:
    import jax

    dev = jax.devices()[0]
    check(
        dev.platform == "tpu" or rehearsal,
        f"JAX found no TPU (platform {dev.platform!r})",
    )
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def kernel_leg(rehearsal: bool, lanes: int) -> dict:
    sys.path.insert(0, HERE)
    import jax
    import jax.numpy as jnp
    import numpy as np

    import __graft_entry__ as graft
    from stellar_tpu.ops import sha256 as dsha256
    from stellar_tpu.ops import sha512 as dsha512
    from stellar_tpu.ops.verifier import BatchVerifier

    out = {"device": _device_or_fail(rehearsal), "lanes": lanes, "programs": {}}
    pallas = not rehearsal
    hash_lowering = {"kernel": "pallas" if pallas else "xla", "interpret": False}

    def lowering_ok(bv):
        if pallas:
            check(
                bv.backend == "pallas" and bv.interpret is False,
                f"verifier lowered to {bv.backend!r} interpret={bv.interpret!r}",
            )
        return {"kernel": bv.backend, "interpret": bv.interpret}

    # 1. verify, host-hash layout, against libsodium's mask
    items, want = graft._mixed_lane_items(lanes)
    bv = BatchVerifier(max_batch=lanes)
    got, first_s, run_s = _timed_twice(lambda: bv.verify(items))
    check(got == want.tolist(), "verify kernel disagrees with libsodium")
    check(bv.n_device_calls == 2 and want.any() and not want.all(), "verify leg did not dispatch")
    out["programs"]["verify"] = {**lowering_ok(bv), "first_s": first_s, "run_s": run_s}

    # 2. the DEVICE_HASH fused program (sha512 ahead of verify, one jit)
    items, want = _boundary_lane_items(lanes)
    dh = BatchVerifier(max_batch=lanes, device_hash=True)
    got, first_s, run_s = _timed_twice(lambda: dh.verify(items))
    check(got == want.tolist(), "device-hash fused program disagrees with libsodium")
    check(dh.n_device_calls == 2, "device-hash leg did not dispatch")
    out["programs"]["device_hash_verify"] = {
        **lowering_ok(dh), "first_s": first_s, "run_s": run_s,
    }
    # ... and its hash stage alone, against hashlib: every lane's h row,
    # device-hashed (flag 1) or host residual passed through (flag 0)
    staged = dh._stage_chunk(items, 0, lanes)
    packed = jnp.asarray(staged.packed)
    if pallas:
        sha = jax.jit(lambda p: dsha512.sha512_pallas(p, interpret=False))
    else:
        sha = jax.jit(dsha512.h_rows_from_packed)
    rows, first_s, run_s = _timed_twice(
        lambda: np.asarray(sha(packed)).astype(np.uint8).T.tobytes()
    )
    flags = staged.packed[dsha512.ROW_FLAG, :lanes]
    check(flags.any() and not flags.all(), "hash leg needs both device and host lanes")
    for j, (pk, msg, sig) in enumerate(items):
        h = dsha512.reduce_digest(hashlib.sha512(sig[:32] + pk + msg).digest())
        check(rows[32 * j : 32 * j + 32] == h, f"sha512 lane {j} (mlen {len(msg)}) != hashlib")
    out["programs"]["sha512"] = {
        **hash_lowering, "first_s": first_s, "run_s": run_s,
        "device_lanes": int(flags.sum()), "host_residual_lanes": int((flags == 0).sum()),
    }

    # 3. sha256 (the bucket-hash program) against hashlib
    msgs = [
        (hashlib.sha512(b"frame %d" % i).digest() * 4)[: _SHA256_LENS[i % len(_SHA256_LENS)]]
        for i in range(lanes)
    ]
    digests, first_s, run_s = _timed_twice(
        lambda: dsha256.sha256_batch(msgs, pallas=pallas, interpret=False)
    )
    for m, d in zip(msgs, digests):
        check(d == hashlib.sha256(m).digest(), f"sha256 of a {len(m)}-byte frame != hashlib")
    out["programs"]["sha256"] = {**hash_lowering, "first_s": first_s, "run_s": run_s}
    return out


def mesh_leg(n_chips: int) -> dict:
    """One verify over an n-chip mesh through make_sharded_verifier, in the
    host-hash and the DEVICE_HASH layout: each chip holds its own shard,
    verdicts bit-exact against libsodium."""
    sys.path.insert(0, HERE)
    import jax

    import __graft_entry__ as graft
    from stellar_tpu.ops.ed25519_pallas import NT
    from stellar_tpu.parallel.mesh import make_mesh, make_sharded_verifier

    out = {"device": _device_or_fail(False), "mesh_devices": n_chips, "programs": {}}
    devices = jax.devices()
    check(len(devices) >= n_chips, f"need {n_chips} chips, JAX sees {len(devices)}")
    mesh = make_mesh(devices[:n_chips])
    lanes = out["lanes"] = NT * n_chips
    for name, device_hash, make_items in (
        ("verify", False, graft._mixed_lane_items),
        ("device_hash_verify", True, _boundary_lane_items),
    ):
        bv = make_sharded_verifier(mesh=mesh, max_batch=lanes, device_hash=device_hash)
        check(bv.backend == "pallas" and bv.interpret is False, f"{bv.backend} {bv.interpret}")
        check(bv.stats()["mesh_devices"] == n_chips, f"{bv.stats()}")
        items, want = make_items(lanes)
        arr = bv._upload_sharded(bv._stage_chunk(items, 0, lanes).packed)
        shards = arr.addressable_shards
        check(
            len(shards) == n_chips
            and {s.device for s in shards} == set(devices[:n_chips])
            and all(s.data.shape == (bv._rows, NT) for s in shards),
            f"shards are not one per chip: {[(s.device, s.data.shape) for s in shards]}",
        )
        got, first_s, run_s = _timed_twice(lambda: bv.verify(items))
        check(got == want.tolist(), f"sharded {name} disagrees with libsodium")
        check(want.any() and not want.all(), f"sharded {name}: one-sided lanes")
        out["programs"][name] = {
            "first_s": first_s, "run_s": run_s, "shard_shape": [bv._rows, NT],
            "shard_devices": [str(s.device) for s in shards],
        }
    return out


def run_child_leg(fn) -> int:
    """A JAX-touching leg's process: one ``LEG <json>`` line, rc 0 or 1."""
    try:
        print("LEG " + json.dumps(fn()), flush=True)
        return 0
    except SmokeFailure as e:
        print(f"chip_smoke leg FAILED: {e}", file=sys.stderr, flush=True)
        return 1


def spawn_leg(ctx: dict, name: str, *flags: str, timeout: float = 600.0) -> dict:
    log_path = os.path.join(ctx["logs"], f"{name}.log")
    with open(log_path, "w") as lf:
        r = subprocess.run(
            [sys.executable, os.path.join(HERE, "chip_smoke.py"), *flags],
            cwd=HERE, env=ctx["chip_env"], stdout=subprocess.PIPE, stderr=lf,
            text=True, timeout=min(timeout, max(1.0, remaining())),
        )
    for line in r.stdout.splitlines():
        if r.returncode == 0 and line.startswith("LEG "):
            return json.loads(line[4:])
    raise SmokeFailure(f"{name} exited {r.returncode}\n{r.stdout[-2000:]}\n" + tail(log_path))


# ---------------------------------------------------------------------------
# the drive
# ---------------------------------------------------------------------------


def drive(args) -> dict:
    check(
        os.path.isdir(os.path.join(HERE, "stellar_tpu")),
        f"{HERE} holds chip_smoke.py but not the stellar_tpu checkout",
    )
    rehearsal = args.rehearse_cpu
    size = dict(REHEARSAL if rehearsal else REAL)
    # children inherit the caller's environment; this parent pins itself
    # to the CPU so nothing it imports can ever take the chip
    chip_env = dict(os.environ)
    host_env = dict(os.environ, JAX_PLATFORMS="cpu")
    if rehearsal:
        chip_env = dict(host_env)
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, HERE)

    out_dir = os.path.abspath(args.out)
    work = os.path.join(out_dir, "work")
    logs = os.path.join(out_dir, "logs")
    shutil.rmtree(work, ignore_errors=True)
    shutil.rmtree(logs, ignore_errors=True)
    archive = os.path.join(work, "archive")
    for d in (work, logs, archive):
        os.makedirs(d)
    ctx = {
        "rehearsal": rehearsal, "size": size, "work": work, "logs": logs,
        "archive": archive, "chip_env": chip_env,
        "passphrase": f"chip_smoke network, seed {args.seed}",
        "producer_db": os.path.join(work, "producer.db"),
        "replayer_db": os.path.join(work, "replayer.db"),
    }
    summary = {
        "rehearsal": rehearsal, "seed": args.seed, "size": size,
        "reduced": [
            f"history: one checkpoint of {CHECKPOINT_FREQUENCY} ledgers "
            "(CHECKPOINT_FREQUENCY, the reference's accelerated-time value; 64 in production)",
            f"{size['payment_ledgers']} replayed payment ledgers at the full "
            f"{REAL['ledger_txs']}-tx width (the fewest the check asks for)",
        ],
    }
    if rehearsal:
        summary["reduced"].append(
            f"REHEARSAL on the CPU: {size['ledger_txs']}-tx ledgers, SIG_BATCH_MAX="
            f"{size['sig_batch_max']}, TPU_CPU_CUTOVER={size['cpu_cutover']}, XLA kernels;"
            " no warm-cache replay (phase B' repeats phase B's code path to time the"
            " chip's warm start)"
        )

    # the device, from a child that exits before anything else needs it
    log("probing for the device")
    t = time.monotonic()
    device = spawn_leg(ctx, "probe", "--leg", "probe", *(["--rehearse-cpu"] if rehearsal else []))
    summary["device"] = device
    summary["probe_s"] = round(time.monotonic() - t, 1)
    log(f"device: {device}" + ("  [REHEARSAL platform=cpu]" if rehearsal else ""))

    # all five C extensions, built from the committed sources: the
    # toolchain-less pure-Python paths are a silent slow path here
    from stellar_tpu import native

    ext = native.loaded()
    check(all(ext.values()), f"native extensions did not all load: {ext}")
    summary["native_extensions"] = ext

    from stellar_tpu.crypto.keys import SecretKey
    from stellar_tpu.ops import DEFAULT_CACHE_DIR

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    summary["compile_cache"] = {
        "dir": cache_dir,
        "from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "entries_at_start": cache_entries(cache_dir),
    }
    default_entries_at_start = cache_entries(DEFAULT_CACHE_DIR)

    def make_node(name: str, env: dict, extra: dict, writable: bool) -> Node:
        sk = SecretKey.from_seed(hashlib.sha256(f"{args.seed} {name}".encode()).digest())
        conf = os.path.join(work, f"{name}.cfg")
        port = free_port()
        node_config(
            conf, work, name, port, sk.get_strkey_seed(), sk.get_strkey_public(),
            ctx["passphrase"], archive, extra, writable,
        )
        return Node(name, conf, port, env, logs)

    replay_knobs = {"SIGNATURE_BACKEND": "tpu", "CATCHUP_COMPLETE": True}
    if rehearsal:
        replay_knobs.update(
            SIG_BATCH_MAX=size["sig_batch_max"], TPU_CPU_CUTOVER=size["cpu_cutover"]
        )
    ctx["producer"] = make_node(
        "producer", host_env,
        {"SIGNATURE_BACKEND": "cpu", "MANUAL_CLOSE": True,
         "DESIRED_MAX_TX_PER_LEDGER": size["ledger_txs"]},
        True,
    )
    ctx["replayer"] = make_node("replayer", chip_env, replay_knobs, False)
    ctx["replayer_warm"] = make_node("replayer_warm", chip_env, replay_knobs, False)
    nodes = [ctx["producer"], ctx["replayer"], ctx["replayer_warm"]]
    try:
        log("phase A: producing history on the host (cpu backend)")
        t = time.monotonic()
        hist = phase_a(ctx)
        summary["phase_a"] = {"wall_s": round(time.monotonic() - t, 1), **hist}
        log(f"phase A done: anchor {hist['anchor']} {hist['anchor_hash'][:16]}… "
            f"txs/ledger {hist['txs_per_ledger']}")

        log("phase B: replaying on the device (cold compile cache)")
        t = time.monotonic()
        summary["phase_b"] = phase_b(ctx, hist)
        summary["phase_b"]["wall_s"] = round(time.monotonic() - t, 1)
        summary["compile_cache"]["entries_after_cold"] = cache_entries(cache_dir)
        log(f"phase B done: catchup {summary['phase_b']['catchup_s']} s, "
            f"buckets {summary['phase_b']['buckets']}")

        if not rehearsal:  # on the CPU it would only repeat phase B's path
            log("phase B': the same replay, compile cache warm")
            t = time.monotonic()
            summary["phase_b_warm"] = phase_b_warm(ctx, hist)
            summary["phase_b_warm"]["wall_s"] = round(time.monotonic() - t, 1)
            log(f"phase B' done: catchup {summary['phase_b_warm']['catchup_s']} s")
    finally:
        for n in nodes:
            n.kill()

    log("kernel leg: each shipped program once, in a child of its own")
    t = time.monotonic()
    summary["kernel_leg"] = spawn_leg(
        ctx, "kernel_leg", "--leg", "kernel", "--lanes", str(size["kernel_lanes"]),
        *(["--rehearse-cpu"] if rehearsal else []),
    )
    summary["kernel_leg"]["wall_s"] = round(time.monotonic() - t, 1)
    summary["compile_cache"]["entries_at_end"] = cache_entries(cache_dir)
    cc = summary["compile_cache"]
    if not rehearsal and cc["entries_at_start"] == 0:
        # (a cache that outlives the call starts warm and need not grow)
        check(
            cc["entries_after_cold"] > 0,
            f"no compile-cache entry appeared in {cache_dir}: {cc}",
        )
    if cc["from_env"] and os.path.abspath(cache_dir) != DEFAULT_CACHE_DIR:
        check(
            cache_entries(DEFAULT_CACHE_DIR) == default_entries_at_start,
            f"JAX_COMPILATION_CACHE_DIR is set, yet {DEFAULT_CACHE_DIR} grew",
        )
    check(
        summary["kernel_leg"]["device"] == device
        and summary["phase_b"]["sig_backend"]["device_kind"] == device["kind"]
        and summary["phase_b"]["sig_backend"]["device_count"] == device["count"],
        "the probe, the node and the kernel leg saw different devices",
    )
    summary["wall_s"] = round(time.monotonic() - T0, 1)
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same flow at a tiny size on the CPU, labelled a rehearsal",
    )
    ap.add_argument(
        "--out", default=os.path.join(HERE, "chiprun_out", "chip_smoke"),
        help="output directory (logs, summary.json)",
    )
    ap.add_argument("--leg", choices=("probe", "kernel", "mesh"), help=argparse.SUPPRESS)
    ap.add_argument("--lanes", type=int, default=REAL["kernel_lanes"], help=argparse.SUPPRESS)
    ap.add_argument("--chips", type=int, default=4, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args.leg == "probe":
        return run_child_leg(lambda: _device_or_fail(args.rehearse_cpu))
    if args.leg == "kernel":
        return run_child_leg(lambda: kernel_leg(args.rehearse_cpu, args.lanes))
    if args.leg == "mesh":
        return run_child_leg(lambda: mesh_leg(args.chips))

    def on_alarm(_sig, _frame):
        raise SmokeFailure(f"the smoke did not finish inside {DEADLINE_S}s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(DEADLINE_S)
    out_dir = os.path.abspath(args.out)
    try:
        summary = drive(args)
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke FAILED after {time.monotonic() - T0:.0f}s: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        signal.alarm(0)
        # DBs, buckets and the archive go; logs and summary.json stay
        shutil.rmtree(os.path.join(out_dir, "work"), ignore_errors=True)
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary, indent=1), flush=True)
    if args.rehearse_cpu:
        print("REHEARSAL platform=cpu: control flow and counts only, no device number")
    last = {"ok": True, "device": summary["device"]}
    if args.rehearse_cpu:
        last["rehearsal"] = True
    print(json.dumps(last), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
