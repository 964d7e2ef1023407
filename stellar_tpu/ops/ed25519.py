"""Batched ed25519 verification on TPU (JAX): the curve arithmetic and the
kernel, as ``jax.jit`` traces them.

The split (SURVEY.md §7 hard-part #1, BASELINE.json north star):

- **host** (``ops/verifier.py``): libsodium's strict input gate (canonical
  s, canonical A, small-order A/R rejection) + SHA-512(R‖A‖M) mod L +
  packed staging, all in one GIL-releasing C pass per chunk
  (native/sighash.c; hashlib/numpy fallback mirrors
  ops/ref25519.strict_input_ok);
- **device** (here): point decompress of A (field exponentiation), Straus
  double-scalar multiplication R' = s·B + h·(−A) with 4-bit windows
  (shared doublings, niels tables, complete a=−1 twisted Edwards formulas),
  point encoding, byte compare against R.

Verification semantics are bit-exact with libsodium
``crypto_sign_verify_detached`` (differential suite: tests/test_ed25519_tpu.py).

Curve math dataflow is pure int32; batch axis N rides the TPU vector lanes
(layout notes in ops/fe.py).  One compile per padded batch size.

This file is one of ``programs.SOURCE_FILES``: its bytes are part of every
stored program's key, so it holds what a program's body is traced through
and nothing of the host pipeline around it.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from . import fe
from . import ref25519 as ref

D = ref.D
D2 = (2 * ref.D) % ref.P
SQRT_M1 = ref.SQRT_M1

_D_FE = fe.const_fe(D)
_D2_FE = fe.const_fe(D2)
_SQRT_M1_FE = fe.const_fe(SQRT_M1)

WINDOWS = 64  # 4-bit windows over 256-bit scalars


# ---------------------------------------------------------------------------
# point ops — extended coordinates (X:Y:Z:T), a=-1 complete formulas
# ---------------------------------------------------------------------------


def point_identity(n, dtype=jnp.int32):
    zero = jnp.zeros((fe.LIMBS, n), dtype)
    one = fe.one_fe(n, dtype)
    return (zero, one, one, zero)


def point_add(p, q):
    """General extended + extended (add-2008-hwcd-3 shape, 9M)."""
    X1, Y1, Z1, T1 = p
    X2, Y2, Z2, T2 = q
    a = fe.mul(fe.sub(Y1, X1), fe.sub(Y2, X2))
    b = fe.mul(fe.add(Y1, X1), fe.add(Y2, X2))
    c = fe.mul(fe.mul(T1, T2), fe._c("D2", _D2_FE))
    d = fe.mul_small(fe.mul(Z1, Z2), 2)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), fe.mul(e, h))


def point_add_niels(p, n, need_t: bool = True):
    """Extended + precomputed niels (YpX, YmX, T2d, Z2): 8M (7M w/o T).

    ``need_t=False`` when the result feeds a doubling (which ignores T)."""
    X1, Y1, Z1, T1 = p
    YpX2, YmX2, T2d2, Z22 = n
    a = fe.mul(fe.sub(Y1, X1), YmX2)
    b = fe.mul(fe.add(Y1, X1), YpX2)
    c = fe.mul(T1, T2d2)
    d = fe.mul(Z1, Z22)
    e = fe.sub(b, a)
    f = fe.sub(d, c)
    g = fe.add(d, c)
    h = fe.add(b, a)
    t = fe.mul(e, h) if need_t else jnp.zeros_like(X1)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), t)


def point_double(p, need_t: bool = True):
    """dbl-2008-hwcd with a=-1: 4S + 4M (3M with ``need_t=False``).

    Doubling never reads the input T, so inside a doubling chain only the
    last double before an addition needs to produce T — the others skip
    the E·H multiply and return a zero T placeholder.
    """
    X1, Y1, Z1, _ = p
    a = fe.sqr(X1)
    b = fe.sqr(Y1)
    c = fe.mul_small(fe.sqr(Z1), 2)
    d = fe.neg(a)  # a_coef = -1
    e = fe.sub(fe.sub(fe.sqr(fe.add(X1, Y1)), a), b)
    g = fe.add(d, b)
    f = fe.sub(g, c)
    h = fe.sub(d, b)
    t = fe.mul(e, h) if need_t else jnp.zeros_like(X1)
    return (fe.mul(e, f), fe.mul(g, h), fe.mul(f, g), t)


def to_niels(p):
    X, Y, Z, T = p
    return (
        fe.add(Y, X),
        fe.sub(Y, X),
        fe.mul(T, fe._c("D2", _D2_FE)),
        fe.mul_small(Z, 2),
    )


def point_negate(p):
    X, Y, Z, T = p
    return (fe.neg(X), Y, Z, fe.neg(T))


def compress(p, batch_inv: bool = False):
    """-> ((32, N) bytes, x-parity already folded into byte 31).

    ``batch_inv`` switches the Z inversion to fe.inv_batch (tree-product
    Montgomery inversion across lanes) — correct only when the batch axis
    is local to the caller (Pallas tile / unsharded XLA batch; NOT under a
    mesh-sharded jit, where cross-lane slicing would force collectives) and
    when zero-Z lanes are masked downstream (inv_batch returns garbage for
    them, not 0)."""
    X, Y, Z, _ = p
    zinv = fe.inv_batch(Z) if batch_inv else fe.inv(Z)
    x = fe.mul(X, zinv)
    y = fe.mul(Y, zinv)
    by = fe.bytes_from_limbs(fe.canonical(y))
    sign = fe.parity(x)
    by = fe.set_row(by, 31, by[31] + (sign << 7))
    return by


def decompress(y_limbs, sign):
    """-> (point, fail) matching ref25519.decompress for canonical y."""
    one = fe.one_fe(y_limbs.shape[1:], y_limbs.dtype)
    yy = fe.sqr(y_limbs)
    u = fe.sub(yy, one)
    v = fe.add(fe.mul(yy, fe._c("D", _D_FE)), one)
    v3 = fe.mul(fe.sqr(v), v)
    v7 = fe.mul(fe.sqr(v3), v)
    x = fe.mul(fe.mul(u, v3), fe.pow_p58(fe.mul(u, v7)))
    vxx = fe.mul(v, fe.sqr(x))
    ok1 = fe.eq(vxx, u)
    ok2 = fe.eq(vxx, fe.neg(u))
    x = fe.select(ok2, fe.mul(x, fe._c("SQRT_M1", _SQRT_M1_FE)), x)
    fail = ~(ok1 | ok2)
    fail = fail | (fe.is_zero(x) & (sign == 1))
    flip = fe.parity(x) != sign
    x = fe.select(flip, fe.neg(x), x)
    return (x, y_limbs, one, fe.mul(x, y_limbs)), fail


# ---------------------------------------------------------------------------
# fixed-base table (host-precomputed from the reference implementation)
# ---------------------------------------------------------------------------


def _base_niels_table_np() -> np.ndarray:
    """(4, 16, 20) int32: niels components of k*B for k=0..15."""
    tab = np.zeros((4, 16, fe.LIMBS), dtype=np.int32)
    pt = ref.IDENT
    B = ref.base_point()
    for k in range(16):
        x, y, z, t = pt
        zinv = ref.fe_inv(z)
        xa, ya = x * zinv % ref.P, y * zinv % ref.P
        ta = xa * ya % ref.P
        tab[0, k] = fe.int_to_limbs((ya + xa) % ref.P)
        tab[1, k] = fe.int_to_limbs((ya - xa) % ref.P)
        tab[2, k] = fe.int_to_limbs(ta * D2 % ref.P)
        tab[3, k] = fe.int_to_limbs(2)
        pt = ref.point_add(pt, B)
    return tab


_BASE_TABLE = jnp.asarray(_base_niels_table_np())  # (4, 16, 20)


def _select_base(nib):
    """nib (N,) -> niels tuple of (20, N) from the static base table."""
    onehot = (nib[None, :] == jnp.arange(16, dtype=nib.dtype)[:, None]).astype(
        jnp.int32
    )  # (16, N)
    comps = jnp.einsum("kn,ckl->cln", onehot, _BASE_TABLE)  # (4, 20, N)
    return (comps[0], comps[1], comps[2], comps[3])


def _select_dyn(table, nib):
    """table: tuple of 4 arrays (20, 16, N); nib (N,)."""
    onehot = (nib[None, :] == jnp.arange(16, dtype=nib.dtype)[:, None]).astype(
        jnp.int32
    )  # (16, N)
    return tuple(jnp.einsum("kn,lkn->ln", onehot, t) for t in table)


def _build_a_table(neg_a):
    """niels table of k*(-A) for k=0..15: tuple of 4 arrays (20, 16, N).

    Sequential adds run under lax.scan (15 iterations, one traced body);
    the niels conversion is then vectorized across all 16 entries at once —
    fe ops are shape-polymorphic in the trailing dims.
    """
    n = neg_a[0].shape[1]

    def step(p, _):
        p2 = point_add(p, neg_a)
        return p2, p2

    _, mults = jax.lax.scan(step, point_identity(n), None, length=15)
    # mults: 4 arrays (15, 20, N); prepend identity and move limbs first
    ident = point_identity(n)
    full = tuple(
        jnp.concatenate([ident[c][None], mults[c]], axis=0).transpose(1, 0, 2)
        for c in range(4)
    )  # (20, 16, N)
    return to_niels(full)


# ---------------------------------------------------------------------------
# the verify kernel
# ---------------------------------------------------------------------------


def verify_kernel(a_bytes, r_bytes, s_nibs, h_nibs, batch_inv: bool = False):
    """All-device batched check R' == R.

    a_bytes   (32,N) — public key A bytes (little-endian, sign in bit 255)
    r_bytes   (32,N) — signature R bytes (to compare against)
    s_nibs    (64,N) — s scalar nibbles, little-endian
    h_nibs    (64,N) — h = SHA512(R‖A‖M) mod L nibbles, little-endian
    batch_inv — use lane-tree Montgomery inversion in compress; only valid
                when the batch axis is unsharded (see compress)
    returns   (N,) bool
    """
    a_sign = a_bytes[31] >> 7
    a_masked = fe.set_row(a_bytes, 31, a_bytes[31] & 0x7F)
    a_y_limbs = fe.limbs_from_bytes(a_masked)
    a_pt, fail = decompress(a_y_limbs, a_sign)
    neg_a = point_negate(a_pt)
    a_table = _build_a_table(neg_a)

    n = a_bytes.shape[1]

    def body(i, acc):
        t = WINDOWS - 1 - i
        for k in range(4):
            # only the last double feeds an addition, which is the sole
            # consumer of T — the first three skip the E·H multiply
            acc = point_double(acc, need_t=(k == 3))
        acc = point_add_niels(acc, _select_base(s_nibs[t]))
        # the next consumer is the following window's doubling: no T needed
        acc = point_add_niels(acc, _select_dyn(a_table, h_nibs[t]), need_t=False)
        return acc

    acc = jax.lax.fori_loop(0, WINDOWS, body, point_identity(n))
    enc = compress(acc, batch_inv=batch_inv)
    match = jnp.all(enc == r_bytes, axis=0)
    return match & ~fail


# ---------------------------------------------------------------------------
# the packed staging layouts, as the kernel reads them
# ---------------------------------------------------------------------------


def _nibbles_np(scalars_le_bytes: np.ndarray) -> np.ndarray:
    """(N, 32) uint8 -> (64, N) int32 nibbles little-endian."""
    lo = scalars_le_bytes & 0x0F
    hi = scalars_le_bytes >> 4
    inter = np.empty((scalars_le_bytes.shape[0], 64), dtype=np.int32)
    inter[:, 0::2] = lo
    inter[:, 1::2] = hi
    return np.ascontiguousarray(inter.T)


def _nibbles_dev(b):
    """(32, N) byte rows -> (64, N) int32 little-endian nibbles, on device
    (the packed-upload path widens and splits inside the jit program)."""
    b = b.astype(jnp.int32)
    return jnp.stack([b & 0x0F, b >> 4], axis=1).reshape(64, -1)


def _verify_packed(p, batch_inv: bool = False):
    """verify_kernel over the packed (128, N) uint8 staging layout
    (rows 0:32 A, 32:64 R, 64:96 s, 96:128 h)."""
    a = p[0:32].astype(jnp.int32)
    r = p[32:64].astype(jnp.int32)
    return verify_kernel(
        a, r, _nibbles_dev(p[64:96]), _nibbles_dev(p[96:128]),
        batch_inv=batch_inv,
    )


def _verify_packed_device_hash(p, batch_inv: bool = False):
    """The DEVICE-HASH fusion: SHA-512(R‖A‖M) mod L computed on device
    (ops/sha512.py) from the packed (160, N) raw-byte staging layout,
    then the same verify kernel — one jit, no host hash.  flag=0 lanes
    (multi-block residuals, torsion-proof columns) carry a host h in
    rows 96:128 and bypass the device hash by selection."""
    from . import sha512 as dsha

    a = p[0:32].astype(jnp.int32)
    r = p[32:64].astype(jnp.int32)
    h = dsha.h_rows_from_packed(p)
    return verify_kernel(
        a, r, _nibbles_dev(p[64:96]), _nibbles_dev(h),
        batch_inv=batch_inv,
    )
