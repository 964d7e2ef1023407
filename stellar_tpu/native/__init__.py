"""Native runtime components (C, loaded via ctypes).

The reference's bucket hot path is native C++ on worker threads
(src/bucket/Bucket.cpp merge + SHA256, src/main/ApplicationImpl.cpp:120
worker pool); ours is ``bucketmerge.c``: streaming merge + SHA-256 with no
Python in the loop.  ctypes releases the GIL for the duration of the call,
so merges running on the worker pool never stall the main crank — the
property the reference gets from real C++ threads.

Each shared object is built on first use with the system compiler and
cached next to its source, together with a ``<name>.so.srchash`` stamp of
the source content and flags it was built from; a copied or checked-out
tree has arbitrary mtimes, so staleness is decided by that content hash
and nothing else.  If no toolchain is available everything falls back to
the pure-Python implementations (``loaded()`` says which extensions are
live, so a harness can refuse the slow path).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import re
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "bucketmerge.c")
_SO = os.path.join(_HERE, "_bucketmerge.so")

_lock = threading.Lock()
_lib = None
_tried = False


# -- sanitizer build mode ----------------------------------------------------
#
# STELLAR_TPU_SANITIZE=<list> (e.g. "address,undefined") rebuilds every
# extension with -fsanitize=<list> into a SEPARATE "<name>.san.so" artifact
# (the normal .so is never clobbered) — the test-only build mode the
# ASan+UBSan differential leg drives (tests/test_native_build.py).  A
# sanitized CPython extension only loads into an interpreter with the
# sanitizer runtime present, so the leg runs its driver in a subprocess
# with LD_PRELOAD set from sanitizer_preload_libs().


def sanitize_mode() -> Optional[str]:
    return os.environ.get("STELLAR_TPU_SANITIZE") or None


def _san_flags() -> tuple:
    mode = sanitize_mode()
    if not mode:
        return ()
    return (f"-fsanitize={mode}", "-fno-sanitize-recover=all", "-g", "-O1")


def _san_so(so: str) -> str:
    """Artifact name encodes the EXACT sanitize set, so an address-only
    build is never reused for an address,undefined run."""
    mode = sanitize_mode()
    if not mode:
        return so
    slug = re.sub(r"[^A-Za-z0-9]+", "-", mode).strip("-")
    return f"{so[:-3]}.san-{slug}.so"


def sanitizer_preload_libs(kinds: Sequence[str] = ("asan", "ubsan")) -> Optional[List[str]]:
    """Resolved shared-runtime paths to LD_PRELOAD for a subprocess that
    loads sanitized extensions, or None when the toolchain can't name them
    (clang's static runtimes, no toolchain at all)."""
    out = []
    for kind in kinds:
        path = None
        for cc in ("cc", "gcc", "clang"):
            try:
                r = subprocess.run(
                    [cc, f"-print-file-name=lib{kind}.so"],
                    capture_output=True,
                    timeout=30,
                    text=True,
                )
            except (OSError, subprocess.TimeoutExpired):
                continue
            cand = r.stdout.strip()
            if r.returncode == 0 and os.sep in cand and os.path.exists(cand):
                path = cand
                break
        if path is None:
            return None
        out.append(path)
    return out


def _source_digest(src: str, flags: Sequence[str]) -> str:
    """Content hash of what a build is made from: the source bytes and
    the compiler flags."""
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(flags).encode())
    return h.hexdigest()


def _compile_so(src: str, so: str, extra_flags: Sequence[str] = ()) -> bool:
    flags = (*_san_flags(), *extra_flags)
    digest = _source_digest(src, flags)
    # per-process temp name: concurrent first-use builds in sibling
    # processes must not interleave writes into one file
    tmp = f"{so}.{os.getpid()}.tmp"
    for cc in ("cc", "gcc", "clang"):
        try:
            r = subprocess.run(
                [cc, "-O2", "-shared", "-fPIC", *flags, "-o", tmp, src],
                capture_output=True,
                timeout=120,
            )
        except (OSError, subprocess.TimeoutExpired):
            continue
        if r.returncode == 0:
            os.replace(tmp, so)
            stamp_tmp = f"{so}.srchash.{os.getpid()}.tmp"
            with open(stamp_tmp, "w") as f:
                f.write(digest)
            os.replace(stamp_tmp, so + ".srchash")
            return True
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return False


def _needs_build(src: str, so: str, extra_flags: Sequence[str] = ()) -> bool:
    """True when the .so must be (re)built: it is missing, or the stamp
    beside it does not match the source and flags now on disk.  The .so
    files are git-ignored and only ever built from the checkout's own
    source, so a loaded library always has every symbol the source has."""
    if not os.path.exists(so):
        return True
    try:
        with open(so + ".srchash") as f:
            built_from = f.read().strip()
    except OSError:
        return True
    return built_from != _source_digest(src, (*_san_flags(), *extra_flags))


def _build() -> bool:
    return _compile_so(_SRC, _san_so(_SO))


def _load():
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _san_so(_SO)
        if _needs_build(_SRC, so):
            if not _build():
                return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            return None
        lib.bucket_merge.restype = ctypes.c_int
        lib.bucket_merge.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_char_p),
            ctypes.c_int,
            ctypes.c_int,
            ctypes.c_char_p,
            ctypes.c_char * 32,
            ctypes.POINTER(ctypes.c_longlong),
        ]
        lib.sha256_file.restype = ctypes.c_int
        lib.sha256_file.argtypes = [ctypes.c_char_p, ctypes.c_char * 32]
        lib.bucket_merge_v2.restype = ctypes.c_int
        lib.bucket_merge_v2.argtypes = lib.bucket_merge.argtypes
        lib.bucket_hash_v2_file.restype = ctypes.c_int
        lib.bucket_hash_v2_file.argtypes = [
            ctypes.c_char_p,
            ctypes.c_char * 32,
            ctypes.POINTER(ctypes.c_longlong),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def merge_files(
    old_path: str,
    new_path: str,
    shadow_paths: Sequence[str],
    keep_dead: bool,
    out_path: str,
) -> Optional[Tuple[bytes, int]]:
    """Merge two sorted bucket files into out_path.

    Returns (content_hash, record_count), or None if the native engine is
    unavailable or the merge failed (caller falls back to Python).
    A zero record count reports hash over the empty stream — the caller
    maps that to the canonical empty bucket.
    """
    lib = _load()
    if lib is None or len(shadow_paths) > 32:
        return None
    shadows = (ctypes.c_char_p * max(1, len(shadow_paths)))()
    for i, p in enumerate(shadow_paths):
        shadows[i] = p.encode()
    out_hash = (ctypes.c_char * 32)()
    out_count = ctypes.c_longlong(0)
    rc = lib.bucket_merge(
        old_path.encode(),
        new_path.encode(),
        shadows,
        len(shadow_paths),
        1 if keep_dead else 0,
        out_path.encode(),
        out_hash,
        ctypes.byref(out_count),
    )
    if rc != 0:
        return None
    return bytes(out_hash), int(out_count.value)


def sha256_file(path: str) -> Optional[bytes]:
    lib = _load()
    if lib is None:
        return None
    out = (ctypes.c_char * 32)()
    if lib.sha256_file(path.encode(), out) != 0:
        return None
    return bytes(out)


def merge_files_v2(
    old_path: str,
    new_path: str,
    shadow_paths: Sequence[str],
    keep_dead: bool,
    out_path: str,
) -> Optional[Tuple[bytes, int]]:
    """merge_files with the v2 per-record-digest bucket hash (ISSUE r22,
    bucket/hashplane.py).  Same record stream as merge_files; only the
    content hash differs.  None when the engine is unavailable — the
    caller's Python fallback produces the identical v2 hash."""
    lib = _load()
    if lib is None or len(shadow_paths) > 32:
        return None
    shadows = (ctypes.c_char_p * max(1, len(shadow_paths)))()
    for i, p in enumerate(shadow_paths):
        shadows[i] = p.encode()
    out_hash = (ctypes.c_char * 32)()
    out_count = ctypes.c_longlong(0)
    rc = lib.bucket_merge_v2(
        old_path.encode(),
        new_path.encode(),
        shadows,
        len(shadow_paths),
        1 if keep_dead else 0,
        out_path.encode(),
        out_hash,
        ctypes.byref(out_count),
    )
    if rc != 0:
        return None
    return bytes(out_hash), int(out_count.value)


def bucket_hash_v2_file(path: str) -> Optional[Tuple[bytes, int]]:
    """(v2 content hash, record count) of an existing bucket file, or
    None when unavailable (caller falls back to the Python walk) — a
    malformed/truncated frame also returns None (treated as corrupt by
    the verify layer, which re-checks in Python for the verdict)."""
    lib = _load()
    if lib is None:
        return None
    out = (ctypes.c_char * 32)()
    count = ctypes.c_longlong(0)
    if lib.bucket_hash_v2_file(path.encode(), out, ctypes.byref(count)) != 0:
        return None
    return bytes(out), int(count.value)


# -- cxdrpack: the C XDR pack interpreter (CPython extension) ---------------

_CXDR_SRC = os.path.join(_HERE, "cxdrpack.c")
_CXDR_SO = os.path.join(_HERE, "_cxdrpack.so")

_cxdr_lock = threading.Lock()
_cxdr_mod = None
_cxdr_tried = False


def _load_extension(name: str, src: str, so: str, extra_flags=()):
    """Build (if missing or stale) and load a CPython extension .so by
    path.  The unresolved CPython symbols bind into the running
    interpreter at dlopen time, so no libpython link is needed."""
    import sysconfig

    flags = (f"-I{sysconfig.get_paths()['include']}", *extra_flags)
    if _needs_build(src, so, flags) and not _compile_so(src, so, flags):
        return None
    try:
        import importlib.machinery
        import importlib.util

        loader = importlib.machinery.ExtensionFileLoader(name, so)
        spec = importlib.util.spec_from_file_location(name, so, loader=loader)
        mod = importlib.util.module_from_spec(spec)
        loader.exec_module(mod)
        return mod
    except (ImportError, OSError):
        return None


def load_cxdrpack():
    """The compiled C pack interpreter module, or None (pure-Python
    fallback).  Built on first use like the merge engine above."""
    global _cxdr_mod, _cxdr_tried
    with _cxdr_lock:
        if _cxdr_mod is not None or _cxdr_tried:
            return _cxdr_mod
        _cxdr_tried = True
        _cxdr_mod = _load_extension("_cxdrpack", _CXDR_SRC, _san_so(_CXDR_SO))
        return _cxdr_mod


# -- sighash: the ed25519 batch host stage (CPython extension) ---------------

_SIGHASH_SRC = os.path.join(_HERE, "sighash.c")
_SIGHASH_SO = os.path.join(_HERE, "_sighash.so")

# -- halfagg: the ed25519 half-aggregation curve core (CPython extension) ----

_HALFAGG_SRC = os.path.join(_HERE, "halfagg.c")
_HALFAGG_SO = os.path.join(_HERE, "_halfagg.so")

_halfagg_lock = threading.Lock()
_halfagg_mod = None
_halfagg_tried = False


def load_halfagg():
    """The compiled half-aggregation curve core (strict batch point
    ``decompress`` + Pippenger ``msm``/``msm_ext``), or None (the
    aggregate plane falls back to the pure-Python ref25519 path —
    correct, but slow enough that the scheme only wins with this
    module built)."""
    global _halfagg_mod, _halfagg_tried
    with _halfagg_lock:
        if _halfagg_mod is not None or _halfagg_tried:
            return _halfagg_mod
        _halfagg_tried = True
        # -O3 after the default -O2 (last flag wins): the [L]P torsion
        # ladder and Pippenger loops are tight fe-limb arithmetic that
        # measurably benefits from the extra unrolling.  NOT in sanitizer
        # builds — it would also out-rank _san_flags()' deliberate -O1
        # and degrade ASan/UBSan report fidelity.
        flags = () if sanitize_mode() else ("-O3",)
        _halfagg_mod = _load_extension(
            "_halfagg", _HALFAGG_SRC, _san_so(_HALFAGG_SO), flags
        )
        return _halfagg_mod

# -- applycore: the apply loop's native leg (CPython extension) --------------

_APPLYCORE_SRC = os.path.join(_HERE, "applycore.c")
_APPLYCORE_SO = os.path.join(_HERE, "_applycore.so")

_applycore_lock = threading.Lock()
_applycore_mod = None
_applycore_tried = False


def load_applycore():
    """The compiled leg of the close's two passes over a set: its history
    rows in one native call each (``encode_history_rows(items)`` from the
    apply loop, ``encode_fee_rows(ledger_seq, items)`` from the fee pass),
    or None (tx/history.transaction_rows and fee_rows then encode per row
    with ``base64``/``hex`` in Python — same bytes, slower)."""
    global _applycore_mod, _applycore_tried
    with _applycore_lock:
        if _applycore_mod is not None or _applycore_tried:
            return _applycore_mod
        _applycore_tried = True
        _applycore_mod = _load_extension(
            "_applycore", _APPLYCORE_SRC, _san_so(_APPLYCORE_SO)
        )
        return _applycore_mod


_sighash_lock = threading.Lock()
_sighash_mod = None
_sighash_tried = False


def load_sighash():
    """The compiled batch gate+SHA-512-mod-L host stage
    (``stage(items, start, count, out, ok, blacklist, threads)``), or
    None (the verifier falls back to the hashlib/numpy staging loop).
    Needs -pthread for the internal worker pool."""
    global _sighash_mod, _sighash_tried
    with _sighash_lock:
        if _sighash_mod is not None or _sighash_tried:
            return _sighash_mod
        _sighash_tried = True
        _sighash_mod = _load_extension(
            "_sighash", _SIGHASH_SRC, _san_so(_SIGHASH_SO), ("-pthread",)
        )
        return _sighash_mod


def loaded() -> dict:
    """Which of the five extensions are live in this process, building
    each on first use — what a harness checks before it trusts that no
    pure-Python fallback is standing in for native code."""
    return {
        "bucketmerge": _load() is not None,
        "cxdrpack": load_cxdrpack() is not None,
        "sighash": load_sighash() is not None,
        "halfagg": load_halfagg() is not None,
        "applycore": load_applycore() is not None,
    }
