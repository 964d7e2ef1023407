"""A signature flush sharded over a four-device mesh (ISSUE 45, ``pay5000x4``),
at the rehearsal's sizes on the CPU: four of the conftest's eight virtual
devices, the XLA lowering of the verify kernel, ``SIG_BATCH_MAX`` 32.

The flush under test is 39 triples: one 32-lane chunk with every shard full
(8 lanes a device) and a 16-lane tail chunk for the last 7 — a full shard, a
partly filled one and two dead ones, the shape a 5,000-triple flush has on
the 2x2 host (4,096 + 2,048 lanes: 512, 392, 0, 0 live in the tail).  Every
live shard holds lanes libsodium refuses.  Beside the verdicts: the
``ed25519.upload`` span and the ``mesh`` block of ``stats()`` for that
flush, the sharded bucket's stored program loaded by a second
``BucketPrograms``, and a node booted with ``SIG_MESH = 4`` from the
benchmark's configuration closing the rehearsal's ledgers to the plain
``cpu`` node's hashes.

The four device-compute tests share two compiled shapes (buckets 32 and 16
on the four-device mesh) and the unsharded side its two.
"""

import copy
import os

import pytest

jax = pytest.importorskip("jax")

from benchmarks.generators import closes  # noqa: E402
from benchmarks.measure import Ctx, load_json  # noqa: E402
from benchmarks.reference import Check  # noqa: E402
from stellar_tpu.crypto import SecretKey, sodium  # noqa: E402
from stellar_tpu.crypto.sigbackend import TpuSigBackend  # noqa: E402
from stellar_tpu.ops import programs  # noqa: E402
from stellar_tpu.parallel.mesh import make_mesh  # noqa: E402
from stellar_tpu.trace import Tracer  # noqa: E402

pytestmark = pytest.mark.tpu_kernel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_BATCH, FLUSH = 32, 39
# the live lanes each device is handed by one flush: 8 of the full chunk,
# then 4, 3, 0, 0 of the tail's 7
LANES_PER_DEVICE = [12, 11, 8, 8]
SMALL_ORDER_A = bytes([1]) + bytes(31)


def hostile_items(n: int, seed: int = 4500):
    """Triples of which three in four are refused, each for another reason:
    a flipped bit in R (the device's to refuse), a wrong key (the device's),
    a small-order A (the host gate's); -> (items, libsodium's verdicts)."""
    items = []
    for i in range(n):
        sk = SecretKey.pseudo_random_for_testing(seed + i)
        msg = b"mesh flush %d" % i
        pk, sig = sk.public_raw, bytearray(sk.sign(msg))
        if i % 4 == 1:
            sig[i % 32] ^= 1 << (i % 8)
        elif i % 4 == 2:
            pk = SecretKey.pseudo_random_for_testing(seed + n + i).public_raw
        elif i % 4 == 3:
            pk = SMALL_ORDER_A
        items.append((pk, msg, bytes(sig)))
    return items, [sodium.verify_detached(sig, msg, pk) for pk, msg, sig in items]


@pytest.fixture(scope="module")
def mesh4():
    devs = jax.devices()
    assert len(devs) >= 4, "conftest must provide the virtual CPU devices"
    return make_mesh(devs[:4])


def sharded_backend(mesh, tracer=None) -> TpuSigBackend:
    return TpuSigBackend(max_batch=MAX_BATCH, mesh=mesh, cpu_cutover=0, tracer=tracer)


def test_tail_chunk_with_dead_shards_equals_unsharded_and_libsodium(mesh4):
    items, want = hostile_items(FLUSH)
    assert 0 < sum(want) < len(want)
    sharded = sharded_backend(mesh4)
    bv = sharded._verifier
    assert [bv._bucket(n) for _, n in bv._chunks(FLUSH)] == [32, 16]
    unsharded = TpuSigBackend(max_batch=MAX_BATCH, cpu_cutover=0)
    got = sharded.verify_batch(items)
    assert got == want
    assert got == unsharded.verify_batch(items)
    stats = sharded.stats()
    assert stats["mesh_devices"] == 4
    assert stats["device_calls"] == 2 and stats["lanes"] == 48
    assert unsharded.stats()["mesh"] == {
        "devices": 0, "shard_uploads": 0, "dead_shards": 0, "lanes_per_device": [],
    }
    assert unsharded.stats()["mesh_devices"] == 0


def test_upload_span_and_mesh_counts_of_one_flush(mesh4):
    tracer = Tracer(enabled=True)
    backend = sharded_backend(mesh4, tracer)
    items, want = hostile_items(FLUSH, seed=4600)
    assert backend.verify_batch(items) == want
    assert backend.stats()["mesh"] == {
        "devices": 4,
        "shard_uploads": 8,  # two chunks, one copy a shard
        "dead_shards": 2,    # the tail's third and fourth
        "lanes_per_device": LANES_PER_DEVICE,
    }
    spans, _, _ = tracer.snapshot()
    dispatches = {s.sid: s for s in spans if s.name == "ed25519.device_dispatch"}
    uploads = [s for s in spans if s.name == "ed25519.upload"]
    assert len(dispatches) == len(uploads) == 2
    for up in uploads:
        parent = dispatches[up.parent]
        assert parent.start <= up.start <= up.end <= parent.end
    by_bucket = {s.attrs["bucket"]: s.attrs for s in dispatches.values()}
    assert sorted(by_bucket) == [16, 32]
    for bucket, attrs in by_bucket.items():
        assert attrs["shards"] == 4
        assert attrs["upload_bytes"] == 128 * bucket

    # the one-chip path parts upload and call the same way
    tracer = Tracer(enabled=True)
    one = TpuSigBackend(max_batch=MAX_BATCH, cpu_cutover=0, tracer=tracer)
    assert one.verify_batch(items) == want
    spans, _, _ = tracer.snapshot()
    assert sum(1 for s in spans if s.name == "ed25519.upload") == 2
    assert {s.attrs["shards"] for s in spans if s.name == "ed25519.device_dispatch"} == {1}


def test_stored_sharded_program_is_loaded_by_a_second_verifier(mesh4, tmp_path, monkeypatch):
    store = tmp_path / "programs"
    store.mkdir()
    monkeypatch.setattr(programs, "store_dir", lambda: str(store))
    items, want = hostile_items(FLUSH, seed=4700)
    first = sharded_backend(mesh4)
    assert first.verify_batch(items) == want
    fd = first.stats()["first_dispatch"]
    assert (fd["programs_exported"], fd["programs_traced"]) == (2, 0)
    assert len(list(store.iterdir())) == 2
    second = sharded_backend(mesh4)
    assert second.verify_batch(items) == want
    fd = second.stats()["first_dispatch"]
    assert (fd["programs_stored"], fd["programs_exported"], fd["programs_traced"]) == (2, 0, 0)
    assert fd["recompiles"]["events"] == 0


def test_node_with_sig_mesh_4_closes_to_the_cpu_nodes_hashes(tmp_path):
    config = copy.deepcopy(load_json(os.path.join(ROOT, "benchmarks", "configs", "pay5000x4.json")))
    assert config["node"]["SIG_MESH"] == "auto"
    config["node"]["SIG_MESH"] = 4  # "auto" would take all eight of the conftest's
    traffic = load_json(os.path.join(ROOT, "benchmarks", "traffic", "full-ledgers.json"))
    ctx = Ctx(
        seed=45, config=config, traffic=traffic, cell=None, work=str(tmp_path),
        rehearsal=True, root=ROOT, seconds=1.0,
    )
    wl = closes.Workload(ctx)
    try:
        before = wl.counters()["sig_backend"]
        for _ in range(3):
            wl.step(True)
        wl.finish()
        after = wl.counters()["sig_backend"]
        assert after["mesh_devices"] == 4
        assert after["items"] - before["items"] == 3 * wl.width
        assert after["cpu_cutover_items"] == before["cpu_cutover_items"]
        assert all(n > 0 for n in after["mesh"]["lanes_per_device"])
        check = Check()
        attempted, failed = wl.check(check)
        check.print()
        assert check.ok and failed == 0 and attempted == 3 * wl.width
        assert "ledger_hashes_differing" in {r["name"] for r in check.rows}
    finally:
        wl.close()
