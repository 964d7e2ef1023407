"""The program store (PR 38): a bucket's lowered verify program kept beside
JAX's compilation cache (``stellar_tpu/ops/programs.py``), loaded by every
process after the one that lowered it.

On the CPU the kernel is the XLA lowering at the smallest bucket, 16 lanes.
Every test here works on a store directory of its own: nothing reads or
leaves a program in the checkout's ``.jax_cache/programs``.
"""

from __future__ import annotations

import errno
import hashlib
import os
import random
import threading
import types

import jax
import jax.numpy as jnp
import pytest

from stellar_tpu.crypto import SecretKey, sodium
from stellar_tpu.ops import ed25519 as ed
from stellar_tpu.ops import programs
from stellar_tpu.ops import ref25519 as ref
from stellar_tpu.ops.verifier import BatchVerifier

BUCKET = 16
KINDS = ("stored", "exported", "traced")


def verifier(**kw) -> BatchVerifier:
    """One bucket whatever the batch: a longer batch is more chunks."""
    return BatchVerifier(max_batch=BUCKET, min_device_batch=BUCKET, **kw)


def signed(n: int, salt: int = 0, forge_every: int = 0):
    out = []
    for i in range(n):
        sk = SecretKey.pseudo_random_for_testing(7000 + 100 * salt + i)
        msg = b"program store %d %d" % (salt, i)
        sig = sk.sign(msg)
        if forge_every and i % forge_every == 1:
            sig = sig[:7] + bytes([sig[7] ^ 4]) + sig[8:]
        out.append((sk.public_raw, msg, sig))
    return out


def record(bv: BatchVerifier, bucket: int = BUCKET) -> dict:
    return bv.stats()["first_dispatch"]["buckets"][bucket]


def counts(bv: BatchVerifier) -> dict:
    fd = bv.stats()["first_dispatch"]
    return {k: fd["programs_" + k] for k in KINDS}


def files(directory) -> list:
    return sorted(os.listdir(directory))


def raiser(*_a, **_kw):
    raise AssertionError("the kernel's Python body ran")


# ---------------------------------------------------------------------------
# the three ways a bucket gets its program, once a module
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ways(tmp_path_factory):
    """Two fresh verifiers on one empty directory, then one with no
    directory at all: ``exported``, ``stored``, ``traced``."""
    directory = tmp_path_factory.mktemp("cache") / "programs"
    directory.mkdir()
    items = signed(12, forge_every=4)
    want = [i % 4 != 1 for i in range(12)]
    out = {"dir": directory, "items": items, "want": want}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(programs, "store_dir", lambda: str(directory))
        first = verifier()
        assert first.verify(items) == want
        out["exported"] = first
        out["files_after_export"] = files(directory)
        # a second process: nothing of the first but the directory.  The
        # Python body of the kernel must not run for it
        with pytest.MonkeyPatch.context() as body:
            body.setattr(ed, "verify_kernel", raiser)
            body.setattr(ed, "_verify_packed", raiser)
            body.setattr(ed, "_verify_packed_device_hash", raiser)
            second = verifier()
            assert second.verify(items) == want
        out["stored"] = second
        out["files_after_load"] = files(directory)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(programs, "store_dir", lambda: None)
        third = verifier()
        assert third.verify(items) == want
        out["traced"] = third
    return out


@pytest.fixture
def store(tmp_path, monkeypatch):
    """An empty store of the test's own."""
    directory = tmp_path / "programs"
    directory.mkdir()
    monkeypatch.setattr(programs, "store_dir", lambda: str(directory))
    return directory


@pytest.fixture
def shared_kernel(ways, monkeypatch):
    """Every verifier made in the test runs the traced verifier's jit: the
    fallback costs no second trace and compile of the same program."""
    kernel = ways["traced"]._programs.kernel
    monkeypatch.setattr(BatchVerifier, "_make_kernel", lambda self, batch_inv: kernel)
    return kernel


# -- (1) miss, then hit ------------------------------------------------------


def test_miss_exports_and_stores_one_file(ways):
    rec = record(ways["exported"])
    assert rec["program"] == "exported" and "program_error" not in rec
    assert counts(ways["exported"]) == {"stored": 0, "exported": 1, "traced": 0}
    # the one trace and lowering the machine pays for the bucket
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0
    (name,) = ways["files_after_export"]
    assert name.endswith(".jaxexport") and not name.startswith(".")
    assert name[: -len(".jaxexport")] == programs.key(ways["exported"]._programs.fields(BUCKET))


def test_hit_loads_and_never_runs_the_python_body(ways):
    rec = record(ways["stored"])
    assert rec["program"] == "stored" and "program_error" not in rec
    assert counts(ways["stored"]) == {"stored": 1, "exported": 0, "traced": 0}
    assert ways["files_after_load"] == ways["files_after_export"]
    # what is left of the trace is the wrapper's
    assert rec["trace_s"] < record(ways["exported"])["trace_s"]
    assert rec["program_load_s"] > 0


def test_the_patch_that_proves_it_bites_on_the_traced_path(monkeypatch):
    monkeypatch.setattr(programs, "store_dir", lambda: None)
    monkeypatch.setattr(ed, "verify_kernel", raiser)
    with pytest.raises(AssertionError, match="Python body ran"):
        verifier().verify(signed(3, salt=9))


def test_no_store_means_the_traced_kernel(ways):
    bv = ways["traced"]
    rec = record(bv)
    assert rec["program"] == "traced" and rec["program_error"] == "FileNotFoundError"
    assert counts(bv) == {"stored": 0, "exported": 0, "traced": 1}
    with bv._programs._lock:
        assert bv._programs._calls[BUCKET] is bv._programs.kernel


def test_one_callable_a_bucket_kept_across_dispatches(ways):
    bv = ways["stored"]
    with bv._programs._lock:
        before = dict(bv._programs._calls)
    assert list(before) == [BUCKET] and before[BUCKET] is not bv._programs.kernel
    assert bv.verify(signed(40, salt=1)) == [True] * 40  # three chunks
    with bv._programs._lock:
        assert bv._programs._calls == before
    fd = bv.stats()["first_dispatch"]
    assert fd["recompiles"]["events"] == 0 and list(fd["buckets"]) == [BUCKET]


# -- (2) the same verdicts whichever way -------------------------------------


def rfc8032():
    cases = [
        ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60", b""),
        ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb", b"\x72"),
        ("c5aa8df43f9f837bedb7442f31dcb7b166d38535076f094b85ce3a2e0b4458f7", b"\xaf\x82"),
    ]
    items = []
    for seed_hex, msg in cases:
        sk = SecretKey.from_seed(bytes.fromhex(seed_hex))
        items.append((sk.public_raw, msg, sk.sign(msg)))
    return items


def mutations():
    rng = random.Random(1234)
    items = []
    for i in range(48):
        sk = SecretKey.pseudo_random_for_testing(i)
        msg = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 100)))
        sig = bytearray(sk.sign(msg))
        if i % 2:
            sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
        items.append((sk.public_raw, msg, bytes(sig)))
    return items


def adversarial():
    sk = SecretKey.pseudo_random_for_testing(0)
    msg = b"m"
    sig = sk.sign(msg)
    adv = []
    for e in ref.small_order_blacklist():
        adv.append((e, msg, sig))
        adv.append((sk.public_raw, msg, e + sig[32:]))
    bad_s = (int.from_bytes(sig[32:], "little") + ref.L).to_bytes(32, "little")
    adv.append((sk.public_raw, msg, sig[:32] + bad_s))
    adv.append(((2**255 - 5).to_bytes(32, "little"), msg, sig))
    adv.append((sk.public_raw, msg, b"\x00" * 64))
    adv.append((sk.public_raw, msg, sig[:40]))
    return adv


CORPORA = {"rfc8032": rfc8032, "mutations": mutations, "adversarial": adversarial}


@pytest.mark.parametrize("corpus", CORPORA)
def test_verdicts_equal_the_traced_paths_and_the_reference(ways, corpus):
    items = CORPORA[corpus]()
    want = [len(s) == 64 and len(p) == 32 and ref.verify(p, m, s) for p, m, s in items]
    assert want == [len(s) == 64 and sodium.verify_detached(s, m, p) for p, m, s in items]
    if corpus == "rfc8032":
        assert want == [True, True, True]
    for way in KINDS:
        assert ways[way].verify(items) == want, way


def test_torsion_proofs_ride_the_stored_program(ways):
    B = ref.base_point()
    encs = [ref.compress(ref.scalar_mult(k, B)) for k in (1, 2, 7, 7919)]
    encs.append(ref.compress(ref.IDENT))
    tors = [bytes(e) for e in ref.small_order_blacklist()]
    encs += tors
    for e in tors:
        pt = ref.decompress(e)
        if pt is not None and not ref.point_equal(pt, ref.IDENT):
            encs.append(ref.compress(ref.point_add(ref.scalar_mult(3, B), pt)))
    encs += [b"", b"short", b"\xff" * 32]
    want = []
    for e in encs:
        pt = ref.decompress(e) if len(e) == 32 and ref.fe_is_canonical(e) else None
        want.append(pt is not None and ref.is_torsion_free(pt))
    assert True in want and False in want
    for way in KINDS:
        assert ways[way].verify_torsion(encs) == want, way
        # the same program: no bucket but the one it had
        assert list(ways[way].stats()["first_dispatch"]["buckets"]) == [BUCKET]


# -- (3) the key --------------------------------------------------------------


def fake_devices(**changed):
    real = jax.devices()[0]
    client = types.SimpleNamespace(
        platform_version=changed.pop("platform_version", real.client.platform_version)
    )
    dev = types.SimpleNamespace(
        platform=changed.pop("platform", real.platform),
        device_kind=changed.pop("device_kind", real.device_kind),
        client=client,
    )
    n = changed.pop("device_count", len(jax.devices()))
    assert not changed
    return lambda *a, **kw: [dev] * n


def _source(name):
    def change(mp, bv, tmp_path):
        root = tmp_path / "ops"
        root.mkdir()
        here = os.path.dirname(programs.__file__)
        for f in programs.SOURCE_FILES:
            data = open(os.path.join(here, f), "rb").read()
            (root / f).write_bytes(data + b"\n# one byte more\n" if f == name else data)
        altered = programs.source_digests(str(root))
        assert dict(altered).keys() == dict(programs.source_digests()).keys()
        mp.setattr(programs, "source_digests", lambda: altered)

    return change


def _devices(**changed):
    return lambda mp, bv, tmp_path: mp.setattr(programs.jax, "devices", fake_devices(**changed))


def _attr(obj_of, name, value):
    return lambda mp, bv, tmp_path: mp.setattr(obj_of(bv), name, value)


def _mesh(shape, names):
    def change(mp, bv, tmp_path):
        devices = types.SimpleNamespace(shape=shape)
        mp.setattr(bv._programs, "mesh", types.SimpleNamespace(axis_names=names, devices=devices))

    return change


def _pallas():
    from stellar_tpu.ops import ed25519_pallas

    return ed25519_pallas


XLA_CHANGES = {
    **{"source:" + f: _source(f) for f in programs.SOURCE_FILES},
    "jax.__version__": _attr(lambda bv: jax, "__version__", "0.0.1"),
    "jaxlib.__version__": _attr(lambda bv: __import__("jaxlib"), "__version__", "0.0.1"),
    "platform_version": _devices(platform_version="another build of the backend"),
    "platform": _devices(platform="tpu"),
    "device_kind": _devices(device_kind="TPU v5 lite"),
    "device_count": _devices(device_count=4),
    "rows": _attr(lambda bv: bv._programs, "rows", 160),
    "device_hash": _attr(lambda bv: bv._programs, "device_hash", True),
    "backend": _attr(lambda bv: bv._programs, "backend", "other"),
    "interpret": _attr(lambda bv: bv._programs, "interpret", True),
    "batch_inv": lambda mp, bv, tmp_path: mp.setitem(bv._programs.lowering, "batch_inv", False),
    "x64": lambda mp, bv, tmp_path: mp.setattr(
        programs.jax, "config", types.SimpleNamespace(jax_enable_x64=True)
    ),
    "mesh:none->2x2": _mesh((2, 2), ("batch", "model")),
}



def _flag(name):
    return lambda mp, bv, tmp_path: mp.setattr(_pallas(), name, not getattr(_pallas(), name))


PALLAS_CHANGES = {
    "NT": _attr(lambda bv: _pallas(), "NT", 256),
    "_BATCH_INV": _flag("_BATCH_INV"),
    "_SIGNED_WIN": _flag("_SIGNED_WIN"),
}


@pytest.mark.parametrize("what", XLA_CHANGES)
def test_key_changes_with_each_keyed_thing(ways, tmp_path, what):
    bv = ways["exported"]
    old = bv._programs.fields(BUCKET)
    with pytest.MonkeyPatch.context() as mp:
        XLA_CHANGES[what](mp, bv, tmp_path)
        new = bv._programs.fields(BUCKET)
    assert bv._programs.fields(BUCKET) == old  # the change is undone
    assert new != old and programs.key(new) != programs.key(old)
    # and the program stored under the old key is not what the new one finds
    directory = str(ways["dir"])
    assert programs.load(programs.path_of(directory, old)) is not None
    assert programs.load(programs.path_of(directory, new)) is None


@pytest.mark.parametrize("what", PALLAS_CHANGES)
def test_key_changes_with_each_pallas_flag(monkeypatch, tmp_path, what):
    bv = verifier(backend="pallas")  # interpreted here; nothing is dispatched
    bucket = _pallas().NT
    assert bv.interpret and bv._granule == bucket
    old = bv._programs.fields(bucket)
    assert (old["NT"], old["batch_inv"], old["signed_win"]) == (
        _pallas().NT, _pallas()._BATCH_INV, _pallas()._SIGNED_WIN,
    )
    # a verifier hands the lowering's constants over once, when it is built
    PALLAS_CHANGES[what](monkeypatch, bv, tmp_path)
    new = verifier(backend="pallas")._programs.fields(bucket)
    assert new != old and programs.key(new) != programs.key(old)


def test_key_changes_with_bucket_and_mesh_shape_and_the_xla_batch_inv():
    import numpy as np
    from jax.sharding import Mesh

    bv = verifier()
    base = bv._programs.fields(BUCKET)
    assert base["batch_inv"] is True and base["mesh"] is None
    keys = {programs.key(base), programs.key(bv._programs.fields(2 * BUCKET))}
    chips = np.array(jax.devices()[:4])
    for shape, names in (((4,), ("batch",)), ((2, 2), ("batch", "model")), ((4,), ("lanes",))):
        fields = verifier(mesh=Mesh(chips.reshape(shape), names))._programs.fields(BUCKET)
        # under a mesh the XLA path drops the lane-tree inversion
        assert fields["batch_inv"] is False and fields["mesh"] == [list(names), list(shape)]
        keys.add(programs.key(fields))
    assert len(keys) == 5


@pytest.mark.parametrize(
    "edited, rekeys",
    [("verifier.py", False), ("programs.py", False), ("__init__.py", False), ("ed25519.py", True)],
)
def test_an_edit_to_the_host_pipeline_keeps_every_stored_program(ways, tmp_path, monkeypatch, edited, rekeys):
    """A copy of ``ops/`` with one byte more in one file: only a file the
    kernel's body is traced through changes a bucket's key."""
    here = os.path.dirname(programs.__file__)
    root = tmp_path / "ops"
    root.mkdir()
    for name in os.listdir(here):
        if name.endswith(".py"):
            data = open(os.path.join(here, name), "rb").read()
            (root / name).write_bytes(data + b"#" if name == edited else data)
    bv = ways["exported"]
    old = programs.key(bv._programs.fields(BUCKET))
    altered = programs.source_digests(str(root))
    monkeypatch.setattr(programs, "source_digests", lambda: altered)
    assert (programs.key(bv._programs.fields(BUCKET)) != old) == rekeys
    assert (edited in programs.SOURCE_FILES) == rekeys


def test_key_names_no_path_host_or_stack(ways):
    fields = ways["exported"]._programs.fields(BUCKET)
    flat = repr(fields)
    assert os.path.dirname(programs.__file__) not in flat and os.getcwd() not in flat
    # the same fields from another thread and call depth: the same name
    got = []
    t = threading.Thread(target=lambda: got.append((lambda: ways["stored"]._programs.fields(BUCKET))()))
    t.start()
    t.join(30)
    assert got == [fields]


def test_a_program_stored_under_the_old_key_is_never_loaded(ways, monkeypatch, shared_kernel):
    """End to end: the directory holds the bucket's program, one source
    file's bytes change, and the next verifier lowers again."""
    monkeypatch.setattr(programs, "store_dir", lambda: str(ways["dir"]))
    altered = tuple((f, "0" * 64 if f == "fe.py" else d) for f, d in programs.source_digests())
    monkeypatch.setattr(programs, "source_digests", lambda: altered)
    before = files(ways["dir"])
    bv = verifier()
    try:
        assert bv.verify(ways["items"]) == ways["want"]
        assert record(bv)["program"] == "exported"
        assert len(files(ways["dir"])) == len(before) + 1
    finally:
        for name in set(files(ways["dir"])) - set(before):
            os.unlink(ways["dir"] / name)


# -- (4) whatever goes wrong, the traced kernel and a right answer -----------


def _stored_blob(ways) -> bytes:
    (name,) = ways["files_after_export"]
    return (ways["dir"] / name).read_bytes()


def _plant(data):
    def arrange(mp, ways, directory, bv):
        path = programs.path_of(str(directory), bv._programs.fields(BUCKET))
        with open(path, "wb") as f:
            f.write(data(_stored_blob(ways)))

    return arrange


def _flipped(blob: bytes) -> bytes:
    mid = len(blob) // 2
    return blob[:mid] + bytes([blob[mid] ^ 0x10]) + blob[mid + 1 :]


def _deserialize_raises(mp, ways, directory, bv):
    _plant(lambda blob: blob)(mp, ways, directory, bv)

    def boom(_blob):
        raise RuntimeError("a serialisation this JAX does not read")

    mp.setattr(programs.export, "deserialize", boom)


def _read_only(mp, ways, directory, bv):
    # (the tests run as root, whom no mode bits stop)
    real = os.access
    mp.setattr(programs.os, "access", lambda p, mode: False if p == str(directory) else real(p, mode))


def _disk_full(mp, ways, directory, bv):
    real = os.fsync

    def fsync(fd):
        real(fd)
        raise OSError(errno.ENOSPC, "No space left on device")

    mp.setattr(programs.fs.os, "fsync", fsync)


def _refuses_the_platform(mp, ways, directory, bv):
    """A whole, well-keyed file whose program was lowered for a platform
    this process has not got: ``exported.call`` refuses at its first call."""
    alien = jax.export.export(jax.jit(lambda p: p[0] == p[32]), platforms=["tpu"])(
        jax.ShapeDtypeStruct((128, BUCKET), jnp.uint8)
    )
    programs.save(programs.path_of(str(directory), bv._programs.fields(BUCKET)), alien)


FAULTS = {
    "truncated": (_plant(lambda blob: blob[: len(blob) // 2]), "BadProgramFile"),
    "zero-length": (_plant(lambda blob: b""), "BadProgramFile"),
    "random-bytes": (_plant(lambda blob: random.Random(38).randbytes(len(blob))), "BadProgramFile"),
    "one-bit-flipped": (_plant(_flipped), "BadProgramFile"),
    "digest-of-garbage": (
        _plant(lambda blob: hashlib.sha256(b"not a program").digest() + b"not a program"),
        None,  # whatever the deserialiser raises on it
    ),
    "deserialize-raises": (_deserialize_raises, "RuntimeError"),
    "read-only-directory": (_read_only, "PermissionError"),
    "disk-full-at-the-write": (_disk_full, "OSError"),
    "refuses-the-platform": (_refuses_the_platform, None),
}


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_falls_back_counts_and_leaves_no_bad_file(ways, store, shared_kernel, fault):
    arrange, error = FAULTS[fault]
    bv = verifier()
    with pytest.MonkeyPatch.context() as mp:
        arrange(mp, ways, store, bv)
        items = signed(11, salt=3, forge_every=5)
        assert bv.verify(items) == [i % 5 != 1 for i in range(11)]
    rec = record(bv)
    assert rec["program"] == "traced"
    assert rec["program_error"] == error or (error is None and rec["program_error"])
    assert counts(bv) == {"stored": 0, "exported": 0, "traced": 1}
    assert files(store) == []  # neither the bad file nor a temporary
    with bv._programs._lock:
        assert bv._programs._calls[BUCKET] is bv._programs.kernel
    # nothing is tried again for the bucket: a good file appearing later
    # (another process stored it) is not looked at by this one
    (store / ways["files_after_export"][0]).write_bytes(_stored_blob(ways))
    assert bv.verify(items[:4]) == [True, False, True, True]
    assert counts(bv)["traced"] == 1 and len(bv.stats()["first_dispatch"]["buckets"]) == 1


# -- (5) two threads at one cold bucket ---------------------------------------


def test_two_threads_at_one_cold_bucket_leave_one_whole_file(ways, store, shared_kernel, monkeypatch):
    both_in = threading.Barrier(2, timeout=120)
    real_load = programs.load

    def load(path):
        found = real_load(path)
        both_in.wait()  # neither goes on before both have missed
        return found

    monkeypatch.setattr(programs, "load", load)
    bv = verifier()
    jobs = [signed(9, salt=5, forge_every=3), signed(13, salt=6)]
    out: dict = {}
    errors: list = []

    def run(k):
        try:
            out[k] = bv.verify(jobs[k])
        except BaseException as e:  # shown below, on the test's thread
            errors.append(e)

    threads = [threading.Thread(target=run, args=(k,)) for k in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(300)
    assert not errors and not any(t.is_alive() for t in threads)
    assert out[0] == [i % 3 != 1 for i in range(9)] and out[1] == [True] * 13
    (name,) = files(store)  # one file, no temporary beside it
    monkeypatch.setattr(programs, "load", real_load)
    assert programs.load(str(store / name)) is not None  # and it is whole
    # one record, one callable: the loser's account is dropped
    assert counts(bv) == {"stored": 0, "exported": 1, "traced": 0}
    with bv._programs._lock:
        assert list(bv._programs._calls) == [BUCKET]
    # and a third verifier loads what the two left
    third = verifier()
    assert third.verify(jobs[0]) == out[0] and record(third)["program"] == "stored"


# -- (6) the account's inequality ---------------------------------------------


@pytest.mark.parametrize("way", KINDS)
def test_stages_and_load_fit_the_wall_time(ways, way):
    rec = record(ways[way])
    wall = rec["end"] - rec["start"]
    parts = rec["trace_s"] + rec["lower_s"] + rec["compile_s"] + rec["program_load_s"]
    assert 0 < parts <= wall
    assert rec["rest_s"] == pytest.approx(wall - parts)
    assert (rec["program_load_s"] > 0) == (way != "traced")
    fd = ways[way].stats()["first_dispatch"]
    assert sum(fd["programs_" + k] for k in KINDS) == len(fd["buckets"]) == 1


def test_an_export_that_reports_no_stage_is_timed_by_the_call(ways, store, shared_kernel, monkeypatch):
    """Should a JAX report neither trace nor lowering from inside
    ``export.export``, the record times the call itself: the account does
    not go blind on the one path that still costs a minute."""
    import time

    (name,) = ways["files_after_export"]
    ready = programs.load(str(ways["dir"] / name))

    def silent_export(kernel):
        assert kernel is shared_kernel

        def lower(shape):
            assert shape.shape == (128, BUCKET)
            time.sleep(0.2)  # no stage event from in here
            return ready

        return lower

    monkeypatch.setattr(programs.export, "export", silent_export)
    bv = verifier()
    assert bv.verify(ways["items"]) == ways["want"]
    rec = record(bv)
    assert rec["program"] == "exported" and files(store) == [name]
    assert rec["trace_s"] + rec["lower_s"] >= 0.2
    assert rec["trace_s"] + rec["lower_s"] + rec["compile_s"] + rec["program_load_s"] <= rec["end"] - rec["start"]


def test_the_span_and_the_log_line_carry_the_program(store, shared_kernel):
    import logging

    from stellar_tpu.trace.tracer import Tracer

    lines: list = []

    class Keep(logging.Handler):
        def emit(self, rec):
            lines.append(rec.getMessage())

    keep, level = Keep(), programs._log.level
    programs._log.addHandler(keep)
    programs._log.setLevel(logging.INFO)
    tracer = Tracer()
    bv = verifier(tracer=tracer)
    try:
        assert bv.verify(signed(5, salt=8)) == [True] * 5
    finally:
        programs._log.setLevel(level)
        programs._log.removeHandler(keep)
    (first,) = [s for s in tracer.spans() if s.name == "ed25519.device_dispatch"]
    assert first.attrs["first"] is True and first.attrs["program"] == "exported"
    assert "program" in programs._FIRST_SPAN_ATTRS
    lines = [m for m in lines if "first dispatch" in m]
    assert len(lines) == 1 and "program exported" in lines[0]


# -- (7) the benchmark's reader -----------------------------------------------


def test_reader_gives_none_without_the_count_and_the_count_with_it(ways):
    from benchmarks.layers import programs_stored_setup as reader

    def run_of(block):
        return {"counters": {"before": {"sig_backend": {"first_dispatch": block}}, "after": {}}}

    fd = ways["stored"].stats()["first_dispatch"]
    assert reader.read(run_of(fd)) == 1
    assert reader.read(run_of(ways["exported"].stats()["first_dispatch"])) == 0
    # the parent's block: every sum it had, none of the three counts
    old = {k: v for k, v in fd.items() if not k.startswith("programs_")}
    assert reader.read(run_of(old)) is None
    assert reader.read({"counters": {"before": {"sig_backend": {"backend": "cpu"}}}}) is None
    assert reader.read({"counters": {"before": {}}}) is None


# -- the store's own edges ----------------------------------------------------


def test_store_lives_in_a_subdirectory_of_the_cache_made_at_import():
    import stellar_tpu.ops as ops

    cache = jax.config.jax_compilation_cache_dir
    assert programs.store_dir() == os.path.join(cache, ops.PROGRAMS_SUBDIR)
    assert os.path.isdir(programs.store_dir())
    # JAX's own entries and ours never share a directory
    assert not any(n.endswith(".jaxexport") for n in os.listdir(cache))


def test_save_then_load_round_trips_and_discard_removes(store):
    exported = jax.export.export(jax.jit(lambda p: p[0] == p[32]))(
        jax.ShapeDtypeStruct((128, BUCKET), jnp.uint8)
    )
    path = programs.path_of(str(store), {"bucket": BUCKET})
    assert programs.load(path) is None
    back = programs.save(path, exported)
    again = programs.load(path)
    assert again.mlir_module_serialized == back.mlir_module_serialized == exported.mlir_module_serialized
    assert files(store) == [os.path.basename(path)]
    programs.discard(path)
    programs.discard(path)  # twice is fine
    assert files(store) == [] and programs.load(path) is None
