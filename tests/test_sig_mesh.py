"""Multi-chip sharded verify as the production dispatch path (ISSUE r13).

Three layers:
1. wiring — Config.SIG_MESH validation, parallel/mesh.mesh_from_spec
   semantics (off / "auto" / explicit count over ADDRESSABLE devices),
   and the TpuSigBackend plumb-through (no device compute involved);
2. contracts — SigFlushFuture quarantine (pending AND completed) and the
   per-caller wedge latch must hold unchanged when the backend dispatches
   over a mesh (the close pipeline / overlay / byzantine-flood planes all
   inherit the sharded path through this surface);
3. an end-to-end Application boot with SIG_MESH="auto" on the conftest
   8-device CPU mesh, proving a validator config turns on sharded
   dispatch without code.

Device-compute tests reuse the 8-device bucket-64 shape the existing
sharded-verifier differential compiles, so this module adds no new XLA
compile shapes to tier-1.
"""

import threading

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from stellar_tpu.crypto import SecretKey, sodium  # noqa: E402
from stellar_tpu.crypto.sigbackend import (  # noqa: E402
    CALLER_CLOSE,
    CALLER_PIPELINE,
    CachingSigBackend,
    TpuSigBackend,
    make_backend,
)
from stellar_tpu.crypto.sigcache import VerifySigCache  # noqa: E402
from stellar_tpu.main.config import Config  # noqa: E402
from stellar_tpu.parallel.mesh import make_mesh, mesh_from_spec  # noqa: E402

pytestmark = pytest.mark.tpu_kernel


def _valid_items(n, seed=3000):
    items = []
    for i in range(n):
        sk = SecretKey.pseudo_random_for_testing(seed + i)
        msg = b"mesh backend %d" % i
        items.append((sk.public_raw, msg, sk.sign(msg)))
    return items


class TestConfigKnob:
    def test_default_off_and_valid_values(self):
        cfg = Config()
        assert cfg.SIG_MESH == 0
        cfg.validate()
        for good in (0, False, "auto", 1, 8):
            cfg.SIG_MESH = good
            cfg.validate()

    @pytest.mark.parametrize("bad", [True, -1, "8", "all", 1.5, [8]])
    def test_rejects_malformed(self, bad):
        cfg = Config()
        cfg.SIG_MESH = bad
        with pytest.raises(ValueError, match="SIG_MESH"):
            cfg.validate()

    def test_from_dict_plumbs(self):
        cfg = Config.from_dict({"SIG_MESH": "auto"})
        assert cfg.SIG_MESH == "auto"


class TestMeshFromSpec:
    def test_off(self):
        assert mesh_from_spec(0) is None
        assert mesh_from_spec(None) is None
        assert mesh_from_spec(False) is None

    def test_auto_takes_all_addressable(self):
        mesh = mesh_from_spec("auto")
        assert mesh is not None
        assert len(mesh.devices.flat) == len(jax.local_devices())

    def test_auto_single_device_stays_unsharded(self, monkeypatch):
        # one chip: the unsharded path IS the 1-device configuration
        monkeypatch.setattr(
            jax, "local_devices", lambda: jax.devices()[:1]
        )
        assert mesh_from_spec("auto") is None

    def test_explicit_count(self):
        mesh = mesh_from_spec(3)
        assert len(mesh.devices.flat) == 3
        assert mesh.axis_names == ("batch",)

    def test_explicit_one_normalizes_to_unsharded(self):
        # a 1-device mesh would drop the lane-tree batched inversion for
        # sharding machinery with nothing to parallelize
        assert mesh_from_spec(1) is None

    def test_explicit_count_too_large_raises(self):
        with pytest.raises(ValueError, match="addressable"):
            mesh_from_spec(len(jax.local_devices()) + 1)

    def test_make_mesh_defaults_to_local_devices(self, monkeypatch):
        # a multi-host process group must never mesh devices it cannot
        # feed: the no-argument default is local_devices, not devices
        seen = []

        def fake_local():
            seen.append(True)
            return jax.devices()[:2]

        monkeypatch.setattr(jax, "local_devices", fake_local)
        mesh = make_mesh()
        assert seen and len(mesh.devices.flat) == 2


class TestBackendWiring:
    def test_sig_mesh_builds_the_verifier_mesh(self):
        be = TpuSigBackend(max_batch=16, sig_mesh=8)
        assert be._verifier.mesh is not None
        assert len(be._verifier.mesh.devices.flat) == 8
        assert be.stats()["mesh_devices"] == 8

    def test_sig_mesh_off_stays_unsharded(self):
        be = TpuSigBackend(max_batch=16)
        assert be._verifier.mesh is None
        assert be.stats()["mesh_devices"] == 0

    def test_explicit_mesh_wins_over_spec(self):
        mesh = make_mesh(jax.devices()[:2])
        be = TpuSigBackend(max_batch=16, mesh=mesh, sig_mesh=8)
        assert be._verifier.mesh is mesh
        assert be.stats()["mesh_devices"] == 2

    def test_make_backend_passthrough(self):
        be = make_backend(
            "tpu", cache=VerifySigCache(), max_batch=16, sig_mesh=4
        )
        assert be.stats()["mesh_devices"] == 4

    def test_bucket_splits_evenly_over_any_mesh_width(self):
        # non-pow2 mesh widths: every bucket must stay a whole multiple
        # of the device count (the per-shard staging buffers are fixed
        # equal slices) — no kernel dispatch, pure bucketing arithmetic
        from stellar_tpu.ops.verifier import BatchVerifier

        for width in (2, 3, 5, 8):
            bv = BatchVerifier(
                max_batch=100, mesh=make_mesh(jax.devices()[:width])
            )
            assert bv.max_batch % width == 0
            for n in (1, width - 1, width + 1, 50, 100, 1000):
                assert bv._bucket(n) % width == 0


class TestMeshApplication:
    def test_auto_mesh_via_config_boot(self):
        """A validator config flips on sharded dispatch without code:
        SIGNATURE_BACKEND="tpu" + SIG_MESH="auto" on the 8-device test
        mesh must boot an Application whose sig backend is 8-wide."""
        from stellar_tpu.main.application import Application
        from stellar_tpu.tx import testutils as T
        from stellar_tpu.util.clock import VirtualClock

        cfg = T.get_test_config(59, backend="tpu")
        cfg.SIG_MESH = "auto"
        cfg.validate()
        clock = VirtualClock()
        app = Application(clock, cfg, new_db=True)
        try:
            assert app.sig_backend.stats()["mesh_devices"] == 8
        finally:
            # None-safe superset of database.close(): harmless on this
            # bare (create()-less) app, correct if it ever grows a herder
            app.graceful_stop()


@pytest.fixture(scope="module")
def mesh_backend():
    """One shared 8-device mesh TpuSigBackend for the contract tests —
    bucket 64, the shape the sharded differential already compiles (all
    device-path calls below use 33..64 items so no other bucket shape is
    ever compiled).  The warm call also clears the first-dispatch state
    so the wedge test's shrunk budget is the one that applies."""
    mesh = make_mesh(jax.devices()[:8])
    be = TpuSigBackend(max_batch=64, mesh=mesh, cpu_cutover=0)
    assert all(be.verify_batch(_valid_items(40, seed=4900)))
    assert be._verifier.n_device_calls >= 1
    return be


class TestQuarantineUnderMesh:
    """SigFlushFuture quarantine semantics must hold unchanged when the
    in-flight flush dispatched over the mesh (ISSUE r13: the chaos
    plane's byzantine-flood oracle rides exactly this contract)."""

    def test_inflight_sharded_prewarm_quarantine_keeps_cache_clean(
        self, mesh_backend
    ):
        cache = VerifySigCache()
        be = CachingSigBackend(mesh_backend, cache)
        items = _valid_items(40, seed=4000)
        real = mesh_backend._verifier.verify
        done_compute = threading.Event()
        release = threading.Event()

        def gated_verify(batch):
            out = real(batch)  # the genuine sharded device round-trip
            done_compute.set()
            assert release.wait(60), "test gate never released"
            return out

        mesh_backend._verifier.verify = gated_verify
        try:
            fut = be.verify_batch_async(items, caller=CALLER_PIPELINE)
            assert done_compute.wait(120), "sharded dispatch never ran"
            # quarantine while the future is still pending: the latch
            # must be blocked, not raced
            fut.quarantine()
            release.set()
            assert fut._done.wait(60)
        finally:
            mesh_backend._verifier.verify = real
        with pytest.raises(RuntimeError, match="quarantined"):
            fut.result(timeout=5)
        assert len(cache) == 0, "quarantined flush left cache entries"

    def test_completed_sharded_flush_quarantine_evicts(self, mesh_backend):
        cache = VerifySigCache()
        be = CachingSigBackend(mesh_backend, cache)
        items = _valid_items(40, seed=4200)
        fut = be.verify_batch_async(items, caller=CALLER_PIPELINE)
        assert fut.result(timeout=120) == [True] * len(items)
        assert len(cache) == len(items)  # valid verdicts latched
        fut.quarantine()  # post-completion: drop_many must evict them all
        assert len(cache) == 0


class TestWedgeLatchUnderMesh:
    def test_per_caller_latch_scopes_survive_mesh_dispatch(
        self, mesh_backend
    ):
        """A stalled sharded pipeline prewarm latches ONLY the pipeline
        caller class onto host; the synchronous close path keeps probing
        the (healthy) mesh — the r10 per-caller contract, re-pinned on
        the sharded backend."""
        be = mesh_backend
        items = _valid_items(40, seed=4400)
        want = [
            sodium.verify_detached(s, m, p) for p, m, s in items
        ]
        real = be._verifier.verify
        prev_timeout = be.DEVICE_TIMEOUT
        be.DEVICE_TIMEOUT = 0.2  # instance override; class default kept

        def stalled(batch):
            import time as _t

            _t.sleep(1.0)  # beyond the shrunk budget -> host fallback
            return real(batch)

        be._verifier.verify = stalled
        try:
            out = be.verify_batch(items, caller=CALLER_PIPELINE)
            assert out == want  # host fallback is still correct
            assert be.n_latch_flips.get(CALLER_PIPELINE) == 1
            assert CALLER_CLOSE not in be.n_latch_flips
        finally:
            be._verifier.verify = real
            be.DEVICE_TIMEOUT = prev_timeout
        # the close caller class must still ride the mesh device path
        with be._wedge_lock:
            wedged_pipeline = dict(be._wedged_until)
        assert list(wedged_pipeline) == [CALLER_PIPELINE]
        calls_before = be._verifier.n_device_calls
        out = be.verify_batch(items, caller=CALLER_CLOSE)
        assert out == want
        assert be._verifier.n_device_calls == calls_before + 1
        assert be.stats()["wedge_latch_flips"] == {CALLER_PIPELINE: 1}
        with be._wedge_lock:  # don't leave the shared fixture latched
            be._wedged_until.clear()
