"""Batched multi-block SHA-256 on the device, in JAX (ISSUE r22).

The state plane's dominant hash workload is per-record digests of
variable-length bucket entries (bucket/hashplane.py): every
``Bucket.fresh``, every level-spill merge, and selfcheck's full-tree
re-hash walk thousands-to-millions of XDR frames and SHA-256 each one
independently — embarrassingly parallel many-block hashing, the same
integer-kernel-on-AI-ASIC playbook as ops/sha512.py (arXiv:2604.17808)
applied to SHA-256.

Representation: SHA-256 words are 32-bit, so unlike the SHA-512 kernel
there are no hi/lo lane pairs — every word is ONE int32 lane (the bit
pattern is what matters; logical right shifts are emulated as
arithmetic shift + mask, int32 adds wrap two's-complement exactly like
uint32).  The 64 rounds run under one ``lax.fori_loop`` whose body
rolls a 16-word schedule window by static-slice concatenation —
Mosaic-safe, no scatter, no dynamic value slicing.

Variable length rides fixed shapes through **chained compression over
per-item block counts**: the host pads each item per FIPS 180-4 (0x80
terminator + 8-byte big-endian bit length) into a
``(max_blocks * 64, N)`` uint8 column layout plus an ``(N,)`` int32
block-count vector; the kernel runs ``max_blocks`` compressions and
carries each lane's chaining state forward only while
``b < nblocks[lane]`` (``jnp.where`` select — lanes past their last
block coast, their digest frozen).  A 55-byte entry and a 500-byte
entry land in the same batch, same grid, same compiled graph.

Two lowerings share all the math: ``sha256_rows_from_packed`` (XLA)
and ``sha256_pallas`` (TPU Pallas, constants pre-broadcast to a VMEM
ref because Mosaic allows dynamic ROW reads on int32 refs but not
dynamic slicing of values — same trick as ops/sha512.py's
``_sha_kernel``).  Bit-exactness vs hashlib is pinned by
tests/test_sha256_device.py across the 55/56/63/64/65-byte padding
boundaries, multi-block sizes, and the empty string.
"""

from __future__ import annotations

import struct

import jax
import jax.numpy as jnp
import numpy as np

from .sha512 import _i32, _shl, _shr

# FIPS 180-4 round constants / IV as int32 bit patterns
_K256 = [
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B,
    0x59F111F1, 0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01,
    0x243185BE, 0x550C7DC3, 0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7,
    0xC19BF174, 0xE49B69C1, 0xEFBE4786, 0x0FC19DC6, 0x240CA1CC,
    0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA, 0x983E5152,
    0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC,
    0x53380D13, 0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85,
    0xA2BFE8A1, 0xA81A664B, 0xC24B8B70, 0xC76C51A3, 0xD192E819,
    0xD6990624, 0xF40E3585, 0x106AA070, 0x19A4C116, 0x1E376C08,
    0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A, 0x5B9CCA4F,
    0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
]
_H256_IV = [
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
]

_K_NP = np.asarray([_i32(k) for k in _K256], dtype=np.int32)
_IV_NP = np.asarray([_i32(v) for v in _H256_IV], dtype=np.int32)


def _rotr(x, n: int):
    """32-bit rotate right of the uint32 bit pattern in an int32 lane."""
    return _shr(x, n) | _shl(x, 32 - n)


# ---------------------------------------------------------------------------
# the compression function (one block), fori_loop over 64 rounds
# ---------------------------------------------------------------------------


def _compress_block(state, block_rows, k_at):
    """One SHA-256 compression: ``state`` is the (8, N) int32 chaining
    value, ``block_rows`` 64 int32 (N,) byte rows of one padded block,
    ``k_at(t)`` the round-constant accessor (a value index on the XLA
    path, a VMEM-ref row read inside the Pallas kernel).  Returns the
    new (8, N) chaining value (feedback add included)."""
    w = [
        _shl(block_rows[4 * t], 24)
        | _shl(block_rows[4 * t + 1], 16)
        | _shl(block_rows[4 * t + 2], 8)
        | block_rows[4 * t + 3]
        for t in range(16)
    ]

    def round_body(t, carry):
        st, w = carry
        k = k_at(t)
        a, b, c, d = st[0], st[1], st[2], st[3]
        e, f, g, h = st[4], st[5], st[6], st[7]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + k + w[0]
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        mj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + mj
        # state rotation: (a..h) -> (t1+t2, a, b, c, d+t1, e, f, g)
        st = jnp.concatenate(
            [(t1 + t2)[None], st[0:3], (d + t1)[None], st[4:7]], axis=0
        )
        # schedule roll: w holds w[t .. t+15]; produce w[t+16] (garbage
        # past round 47 — never consumed)
        sg0 = _rotr(w[1], 7) ^ _rotr(w[1], 18) ^ _shr(w[1], 3)
        sg1 = _rotr(w[14], 17) ^ _rotr(w[14], 19) ^ _shr(w[14], 10)
        nw = w[0] + sg0 + w[9] + sg1
        w = jnp.concatenate([w[1:], nw[None]], axis=0)
        return st, w

    st, _ = jax.lax.fori_loop(0, 64, round_body, (state, jnp.stack(w)))
    return st + state  # int32 add wraps mod 2^32 — the feedback add


def _digest_rows(rows, nblocks, k_at):
    """``len(rows)`` = max_blocks * 64 int32 byte rows + per-lane block
    counts -> 32 digest byte rows via chained compression: block b only
    advances lanes with b < nblocks (earlier-finished lanes coast with
    their digest frozen)."""
    max_blocks = len(rows) // 64
    n_shape = rows[0].shape
    st = jnp.stack(
        [jnp.full(n_shape, int(_IV_NP[i]), jnp.int32) for i in range(8)]
    )
    for b in range(max_blocks):
        new_st = _compress_block(st, rows[64 * b : 64 * (b + 1)], k_at)
        if b == 0:
            st = new_st  # every item has >= 1 block (padding guarantees)
        else:
            st = jnp.where((b < nblocks)[None, :], new_st, st)
    out = []
    for i in range(8):
        out.extend(
            [
                _shr(st[i], 24) & 0xFF,
                _shr(st[i], 16) & 0xFF,
                _shr(st[i], 8) & 0xFF,
                st[i] & 0xFF,
            ]
        )
    return jnp.stack(out)


# ---------------------------------------------------------------------------
# XLA entry
# ---------------------------------------------------------------------------


def sha256_rows_from_packed(p, nblocks):
    """XLA entry: (max_blocks * 64, N) uint8 padded columns + (N,) int32
    block counts -> (32, N) int32 digest byte rows (big-endian word
    order — the exact byte string hashlib would emit per column)."""
    rows = [p[i].astype(jnp.int32) for i in range(p.shape[0])]
    k = jnp.asarray(_K_NP)
    return _digest_rows(rows, nblocks.astype(jnp.int32), lambda t: k[t])


_jit_rows_from_packed = jax.jit(sha256_rows_from_packed)


# ---------------------------------------------------------------------------
# Pallas kernel (TPU): same math, constants arriving as a VMEM ref
# ---------------------------------------------------------------------------


def _sha256_kernel(k_ref, nb_ref, p_ref, out_ref):
    rows = [p_ref[i].astype(jnp.int32) for i in range(p_ref.shape[0])]
    # Mosaic cannot dynamic-slice a VALUE, but CAN dynamic-row-read an
    # int32 ref — the round constants stay behind the ref accessor
    # (pre-broadcast to the lane tile like ops/sha512.py)
    out_ref[:] = _digest_rows(rows, nb_ref[0], lambda t: k_ref[t])


# the kernel's name in a device trace (stated, as ed25519_pallas's is)
SHA256_KERNEL_NAME = "sha256_pallas"


def sha256_pallas(p, nblocks, interpret: bool = False):
    """Pallas stage over the packed (max_blocks * 64, N) uint8 columns
    -> (32, N) int32 digest rows.  N must be a multiple of the verify
    kernel's batch tile (shared grid split with ed25519_pallas)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from .ed25519_pallas import NT

    rows, n = p.shape
    assert n % NT == 0, f"batch {n} not a multiple of tile {NT}"
    grid = n // NT

    consts = jnp.broadcast_to(
        jnp.asarray(_K_NP)[:, None], (64, NT)
    )  # (64, NT) int32
    nb = nblocks.astype(jnp.int32).reshape(1, n)
    return pl.pallas_call(
        _sha256_kernel,
        name=SHA256_KERNEL_NAME,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(
                (64, NT), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (1, NT), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(
                (rows, NT), lambda i: (0, i), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (32, NT), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((32, n), jnp.int32),
        interpret=interpret,
    )(consts, nb, p)


# ---------------------------------------------------------------------------
# host-side staging (numpy) — FIPS 180-4 padding into fixed shapes
# ---------------------------------------------------------------------------


def blocks_for(length: int) -> int:
    """Padded block count of an ``length``-byte message (terminator byte
    + 8-byte length field force a new block past length % 64 == 55)."""
    return (length + 8) // 64 + 1


def pack_frames(items, max_blocks: int = 0):
    """Pad each item per FIPS 180-4 into the fixed (max_blocks * 64, N)
    uint8 column layout + (N,) int32 block counts the kernels consume.
    ``max_blocks`` > 0 pins the row count (for shape-stable jit reuse);
    it must cover the longest item."""
    n = len(items)
    counts = np.asarray([blocks_for(len(it)) for it in items], np.int32)
    need = int(counts.max()) if n else 1
    if max_blocks:
        if need > max_blocks:
            raise ValueError(
                f"item needs {need} blocks > pinned max {max_blocks}"
            )
        need = max_blocks
    packed = np.zeros((need * 64, max(n, 1)), dtype=np.uint8)
    for i, it in enumerate(items):
        ln = len(it)
        end = int(counts[i]) * 64
        if ln:
            packed[:ln, i] = np.frombuffer(it, dtype=np.uint8)
        packed[ln, i] = 0x80
        packed[end - 8 : end, i] = np.frombuffer(
            struct.pack(">Q", ln * 8), dtype=np.uint8
        )
    return packed, counts


def sha256_batch(items, pallas: bool = False, interpret: bool = False):
    """Convenience oracle for tests and the hashplane device backend:
    a list of bytes -> a list of their 32-byte SHA-256 digests via the
    batched kernel (Pallas pads the batch to the NT tile with empty
    columns; the pads are computed and dropped)."""
    if not items:
        return []
    n = len(items)
    if pallas:
        from .ed25519_pallas import NT

        pad = (-n) % NT
        packed, counts = pack_frames(list(items) + [b""] * pad)
        rows = sha256_pallas(
            jnp.asarray(packed), jnp.asarray(counts), interpret=interpret
        )
    else:
        packed, counts = pack_frames(items)
        rows = _jit_rows_from_packed(
            jnp.asarray(packed), jnp.asarray(counts)
        )
    out = np.asarray(rows, dtype=np.int32).astype(np.uint8)
    return [out[:, i].tobytes() for i in range(n)]
