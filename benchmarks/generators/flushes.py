"""``flushes``: back-to-back synchronous all-miss signature flushes.

A reading is one call of ``TpuSigBackend.verify_batch`` — the entry that
transaction-set validation and catchup replay call — over one full
transaction set's worth of (public key, message, signature) triples, built
as the node builds them: the signatures of signed native payments over their
contents hashes.  The backend is constructed from the configuration as
``Application`` constructs it, without the verify cache in front, so every
flush is all misses.  A pool of sets made from the seed is cycled.  The
harness adds no pipelining: the next flush starts when the last returned.

After the window a seeded adversarial batch (valid lanes among corrupted R,
corrupted s, wrong key, non-canonical S, small-order A and R, a y that is
on no curve point; messages of lengths across the 111/112-byte SHA-512
block boundary of R|A|M) goes through the same entry and every verdict is
compared with libsodium's; so is every triple of the pool.

Parameters: ``pool_sets`` — sets in the pool.
"""

from __future__ import annotations

import hashlib
import time

from benchmarks import node as N
from benchmarks.stats import Reading

L = 2**252 + 27742317777372353535851937790883648493
SMALL_ORDER = [  # encodings of points of order 1, 2, 4, 8
    bytes([1]) + bytes(31),
    bytes.fromhex("ecffffffffffffffffffffffffffffffffffffffffffffffffffffffffffff7f"),
    bytes(32),
    bytes(31) + bytes([0x80]),
    bytes.fromhex("26e8958fc2b227b045c3f489f2ef98f0d5dfac05d3c63339b13802886d53fc05"),
    bytes.fromhex("c7176a703d4dd84fba3c0b760d10670f2a2053fa2c39ccc64ec7fd7792ac037a"),
]
OFF_CURVE_Y = (2).to_bytes(32, "little")  # y = 2 decompresses to no point
MSG_LENS = (0, 1, 31, 32, 46, 47, 48, 49, 64, 200)


def adversarial(seed: int, n: int) -> list:
    keys = N.keys_from_seed(seed, n, b"adversary")
    items = []
    for i, sk in enumerate(keys):
        msg = (hashlib.sha512(b"adversarial %d %d" % (seed, i)).digest() * 4)[: MSG_LENS[(i // 8) % len(MSG_LENS)]]
        pk, sig = sk.public_raw, bytearray(sk.sign(msg))
        kind = i % 8
        if kind == 1:
            sig[i % 32] ^= 1 << (i % 8)
        elif kind == 2:
            sig[32] ^= 1 << (i % 8)
        elif kind == 3:
            pk = keys[(i + 1) % n].public_raw
        elif kind == 4:
            s = int.from_bytes(sig[32:], "little") + L
            if s < 2**256:
                sig[32:] = s.to_bytes(32, "little")
        elif kind == 5:
            pk = SMALL_ORDER[(i // 8) % len(SMALL_ORDER)]
        elif kind == 6:
            sig[:32] = SMALL_ORDER[(i // 8) % len(SMALL_ORDER)]
        elif kind == 7:
            pk = OFF_CURVE_Y
        items.append((pk, msg, bytes(sig)))
    return items


class Workload:
    def __init__(self, ctx):
        from stellar_tpu.crypto import sha256
        from stellar_tpu.crypto.sigbackend import TpuSigBackend
        from stellar_tpu.trace import Tracer

        self.ctx = ctx
        p = ctx.traffic["params"]
        self.width = N.width_of(ctx.config, ctx.rehearsal)
        cfg = N.make_config(ctx.config, ctx.work, ctx.rehearsal, ctx.traffic.get("node"))
        if cfg.SIGNATURE_BACKEND != "tpu":
            raise SystemExit("ledger-flushes needs SIGNATURE_BACKEND tpu")
        self.tracer = Tracer(enabled=cfg.TRACE_ENABLED, ring_size=cfg.TRACE_RING_SIZE)
        self.backend = TpuSigBackend(
            max_batch=cfg.SIG_BATCH_MAX,
            sig_mesh=cfg.SIG_MESH,
            device_hash=bool(cfg.DEVICE_HASH),
            cpu_cutover=cfg.TPU_CPU_CUTOVER,
            streams=cfg.SIG_VERIFY_STREAMS,
            tracer=self.tracer,
        )
        network_id = sha256(cfg.NETWORK_PASSPHRASE.encode())
        n_sets = int(p["rehearsal_pool_sets"] if ctx.rehearsal else p["pool_sets"])
        keys = N.keys_from_seed(ctx.seed, 2 * self.width)
        self.pool = []
        for k in range(n_sets):
            order = N.permutation(ctx.seed, len(keys), k)
            triples = []
            for j in range(self.width):
                src, dst = keys[order[j]], keys[order[self.width + j]]
                frame = N.tx_frame(network_id, 100, src, (3 << 32) + 1 + k, [N.payment_op(dst, 1000)])
                triples.append(
                    (src.public_raw, frame.get_contents_hash(), frame.envelope.signatures[0].signature)
                )
            self.pool.append(triples)
        self.turn = 0
        self.verdicts = 0
        self.refused_valid = 0

    def step(self, in_window: bool) -> Reading:
        items = self.pool[self.turn % len(self.pool)]
        self.turn += 1
        t0 = time.monotonic()
        out = self.backend.verify_batch(items)
        t1 = time.monotonic()
        self.ctx.span("bench.verify_batch", t0, t1, items=len(items))
        if in_window:
            self.verdicts += len(out)
            self.refused_valid += len(out) - sum(out)
        return Reading(t0, t1, len(items))

    def counters(self) -> dict:
        return {"sig_backend": self.backend.stats()}

    def drain_spans(self) -> list:
        spans, _, dropped = self.tracer.snapshot(clear=True)
        if dropped:
            raise RuntimeError(f"the span ring dropped {dropped} spans")
        return spans

    def finish(self) -> None:
        pass

    def notes(self) -> dict:
        return {"pool_sets": len(self.pool), "flushes": self.turn}

    def close(self) -> None:
        pass

    def check(self, check) -> tuple:
        from benchmarks.reference import sodium_verdicts

        pool_bad = sum(1 for t in self.pool for ok in sodium_verdicts(t) if not ok)
        check.compare("pool_triples_libsodium_refuses", pool_bad, 0)
        check.compare("window_verdicts_false", self.refused_valid, 0, f"of {self.verdicts}")
        adv = adversarial(self.ctx.seed, self.width)
        want = sodium_verdicts(adv)
        got = self.backend.verify_batch(adv)
        accepted = sum(1 for g, w in zip(got, want) if g and not w)
        refused = sum(1 for g, w in zip(got, want) if w and not g)
        check.compare("invalid_lanes_accepted", accepted, 0, f"of {len(adv) - sum(want)} invalid")
        check.compare("valid_lanes_refused", refused, 0, f"of {sum(want)} valid")
        return self.verdicts + len(adv), self.refused_valid + accepted + refused
