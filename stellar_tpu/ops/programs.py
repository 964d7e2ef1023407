"""The program store: a bucket's lowered verify program, kept beside the
executable JAX's persistent compilation cache keeps of it.

The cache spares a process the compile; it does not spare it running the
kernel's Python body to a jaxpr and walking that jaxpr to MLIR, which is
98-99 % of a bucket's first dispatch on a cache hit (PERF.md "Where set-up
goes").  The lowered program is the same bytes whoever lowers it, so the
first process to lower a bucket serialises it (``jax.export``) into
``<cache dir>/programs/<key>`` and every later one loads it from there.

The key is a SHA-256 over everything that decides the lowered program
(``BatchVerifier._program_fields``: the bytes of the kernel's sources, the
versions of JAX, jaxlib and the backend, the device, the mesh, the bucket,
the layout, the lowering and its flags) and nothing else — no path, host
name or call stack, so two checkouts of one tree that share a cache
directory share their programs, and a one-chunk flush and a two-chunk flush
ask for one file (and, through it, one executable).

A file is as trusted as the executables JAX loads from the same directory;
deleting the directory, or any file in it, is safe: the next process lowers
the bucket again.  A file that does not read back whole is never run: it
carries the digest of its payload in front.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
from typing import Optional, Tuple

import jax
from jax import export

from ..util import fs
from . import PROGRAMS_SUBDIR

# Every source file the verify kernel's body is traced through, XLA and
# Pallas lowering alike (their imports: ed25519 -> fe, ref25519, sha512;
# ed25519_pallas -> fe, ed25519; sha512 -> fe, ref25519).  A module the body
# can reach and this list leaves out is a stale program waiting to happen.
SOURCE_FILES = (
    "ed25519.py",
    "ed25519_pallas.py",
    "fe.py",
    "sha512.py",
    "ref25519.py",
)
_OPS_DIR = os.path.dirname(os.path.abspath(__file__))
_DIGEST_BYTES = hashlib.sha256().digest_size


class BadProgramFile(ValueError):
    """A stored program whose payload is not the one its digest names."""


def store_dir() -> Optional[str]:
    """Where the programs live, or None where there is nowhere to keep
    them: no cache directory, or one whose ``programs`` could not be made
    at import (``ops/__init__.py``)."""
    cache = jax.config.jax_compilation_cache_dir
    if not cache:
        return None
    d = os.path.join(cache, PROGRAMS_SUBDIR)
    return d if os.path.isdir(d) else None


@functools.lru_cache(maxsize=None)
def source_digests(root: str = _OPS_DIR) -> Tuple[Tuple[str, str], ...]:
    """(file, SHA-256 of its bytes) for each of ``SOURCE_FILES``; read once
    a process: the modules are imported by then, and what a later edit of
    the file says is not what this process would trace."""
    out = []
    for name in SOURCE_FILES:
        with open(os.path.join(root, name), "rb") as f:
            out.append((name, hashlib.sha256(f.read()).hexdigest()))
    return tuple(out)


def key(fields: dict) -> str:
    """The store's name for the program ``fields`` decide."""
    canon = json.dumps(fields, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(canon.encode()).hexdigest()


def path_of(directory: str, fields: dict) -> str:
    return os.path.join(directory, key(fields) + ".jaxexport")


def load(path: str) -> Optional[export.Exported]:
    """The program stored at ``path``; None where there is none.  Raises
    where there is a file and it is not a whole program."""
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        return None
    digest, payload = blob[:_DIGEST_BYTES], blob[_DIGEST_BYTES:]
    if not payload or hashlib.sha256(payload).digest() != digest:
        raise BadProgramFile(path)
    return export.deserialize(bytearray(payload))


def save(path: str, exported: export.Exported) -> export.Exported:
    """Store ``exported`` at ``path`` (tmp -> fsync -> rename: two writers
    of one key leave one whole file) and return it as a later process will
    read it, so that the first process runs, and caches the executable of,
    the very program the others load."""
    payload = bytes(exported.serialize())
    fs.durable_write(path, hashlib.sha256(payload).digest() + payload)
    return export.deserialize(bytearray(payload))


def discard(path: str) -> None:
    """Remove a file that could not be used; the next process stores the
    bucket's program again."""
    try:
        os.unlink(path)
    except OSError:
        pass
