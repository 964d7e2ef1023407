"""Batched GF(2^255-19) arithmetic for TPU, in JAX.

Design (SURVEY.md §7 hard-part #1): TPU VPUs have no 64-bit integer multiply,
so field elements use **radix 2^13 with 20 int32 limbs**, batch-last layout
``(20, N)`` (N rides the 8x128 vector lanes; the limb axis stays on sublanes).
Bounds that make int32 safe throughout:

- weakly-reduced elements have limbs < 2^13, value < 2^255 + ε
- schoolbook products: ≤ 20 terms × (2^13-1)² < 2^31          (no overflow)
- 2^260 ≡ 608 (mod p) folds the high 19 limbs back with ≤ 2^23 additions

Everything is shape-polymorphic in N and differentiably irrelevant — pure
integer ops, jit-compiled once per batch shape.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

P = 2**255 - 19
LIMBS = 20
RADIX = 13
MASK = (1 << RADIX) - 1  # 8191
FOLD = 608  # 2^260 mod p = 19 * 2^5



def int_to_limbs(v: int) -> np.ndarray:
    """Python int -> (20,) int32 limb vector (host-side)."""
    out = np.zeros(LIMBS, dtype=np.int32)
    for i in range(LIMBS):
        out[i] = v & MASK
        v >>= RADIX
    assert v == 0
    return out


def limbs_to_int(l) -> int:
    l = np.asarray(l)
    return sum(int(l[i]) << (RADIX * i) for i in range(LIMBS))


def const_fe(v: int) -> jnp.ndarray:
    """(20, 1) broadcastable constant."""
    return jnp.asarray(int_to_limbs(v % P)).reshape(LIMBS, 1)


def _sub_pad_limbs() -> np.ndarray:
    """4p written with every limb >= 2^14 (limb 19 >= 2^9): ``a - b + pad``
    then has all-positive limbs for weakly-reduced a, b, so the parallel
    carry passes never ripple borrows.  Built by borrowing 2 units of each
    limb's radix from the limb above (value preserved)."""
    four_p = 4 * P
    l = np.zeros(LIMBS, dtype=np.int64)
    v = four_p
    for i in range(LIMBS):
        l[i] = v & MASK if i < LIMBS - 1 else v
        v >>= RADIX
    assert l[LIMBS - 1] >= 2 + 512, l[LIMBS - 1]  # room to borrow 2
    d = l.copy()
    d[0] += 2 << RADIX
    for i in range(1, LIMBS - 1):
        d[i] += (2 << RADIX) - 2
    d[LIMBS - 1] -= 2
    assert sum(int(d[i]) << (RADIX * i) for i in range(LIMBS)) == four_p
    assert all(d[i] >= 2 * MASK for i in range(LIMBS - 1)) and d[LIMBS - 1] >= 512
    return d.astype(np.int32)


SUB_PAD = jnp.asarray(_sub_pad_limbs()).reshape(LIMBS, 1)
P_LIMBS_COL = jnp.asarray(int_to_limbs(P)).reshape(LIMBS, 1)

# Pallas kernels may not close over array constants — they must arrive as
# kernel inputs.  ops/ed25519_pallas.py passes a packed constant block and
# installs these overrides for the duration of the kernel trace.  A
# ContextVar (not a module global) keeps a trace on one thread — e.g. the
# BatchVerifier stager thread — from leaking its tracer constants into a
# concurrent trace on another thread.
import contextvars

_OVERRIDE: contextvars.ContextVar = contextvars.ContextVar(
    "fe_const_override", default={}
)


class const_override:
    """Context manager substituting the module's array constants during a
    pallas kernel trace (keys: SUB_PAD, P_COL, D, D2, SQRT_M1, PALLAS)."""

    def __init__(self, d):
        self.d = d

    def __enter__(self):
        self._token = _OVERRIDE.set(self.d)

    def __exit__(self, *exc):
        _OVERRIDE.reset(self._token)


def _c(name, default):
    return _OVERRIDE.get().get(name, default)


def zero_like(x):
    return jnp.zeros_like(x)


def set_row(x, i: int, v):
    """x with row i replaced by v (static i), via concatenation — the
    jnp ``.at[i].set`` form lowers to lax.scatter, which Pallas/Mosaic
    cannot compile."""
    parts = []
    if i > 0:
        parts.append(x[:i])
    parts.append(v[None] if v.ndim == x.ndim - 1 else v)
    if i < x.shape[0] - 1:
        parts.append(x[i + 1 :])
    return jnp.concatenate(parts, axis=0)


def one_fe(n, dtype=jnp.int32):
    """(20, *n) field element 1 without scatter ops."""
    shape = n if isinstance(n, tuple) else (n,)
    one = jnp.ones((1,) + shape, dtype)
    rest = jnp.zeros((LIMBS - 1,) + shape, dtype)
    return jnp.concatenate([one, rest], axis=0)


def _carry_pass(x: jnp.ndarray) -> jnp.ndarray:
    """ONE data-parallel carry pass over all limbs at once.

    The sequential 19-step chain was the kernel's critical path (each step
    a tiny dependent (N,) op); a pass is ~6 full-(20,N) ops with depth 3.
    Limbs 0..18 carry at 2^13; limb 19 holds bits 247..254 and folds its
    overflow back to limb 0 via 2^255 ≡ 19 (mod p).  Arithmetic shifts
    floor-divide, so negative limbs borrow correctly.
    """
    k = x.shape[0] - 1  # positive static indices: negative indexing
    c_lo = x[:k] >> RADIX  # lowers to dynamic_slice, which Mosaic lacks
    r_lo = x[:k] - (c_lo << RADIX)
    c_hi = x[k] >> 8
    r_hi = x[k] - (c_hi << 8)
    carries = jnp.concatenate([(c_hi * 19)[None], c_lo], axis=0)
    return jnp.concatenate([r_lo, r_hi[None]], axis=0) + carries


def carry(x: jnp.ndarray, passes: int = 3) -> jnp.ndarray:
    """Parallel carry -> weakly reduced (limbs <= 2^13 + 3).

    Pass-count bounds (see the mul/add/sub callers): products after the
    fold have limbs < 2^31 -> 3 passes leave every limb <= MASK + 3;
    add/sub inputs <= 2^14.6 need only 2.
    """
    for _ in range(passes):
        x = _carry_pass(x)
    return x


def carry_exact(x: jnp.ndarray) -> jnp.ndarray:
    """Sequential full chain: limbs land exactly in [0, 2^13) (limb 19 in
    [0, 2^8)).  O(limbs) dependent steps — only for ``canonical`` (a few
    calls per verify); the hot path uses the parallel ``carry``."""
    limbs = [x[i] for i in range(LIMBS)]
    for i in range(LIMBS - 1):
        c = limbs[i] >> RADIX
        limbs[i] = limbs[i] - (c << RADIX)
        limbs[i + 1] = limbs[i + 1] + c
    t = limbs[LIMBS - 1] >> 8
    limbs[LIMBS - 1] = limbs[LIMBS - 1] & 0xFF
    limbs[0] = limbs[0] + t * 19
    for i in range(2):
        c = limbs[i] >> RADIX
        limbs[i] = limbs[i] - (c << RADIX)
        limbs[i + 1] = limbs[i + 1] + c
    return jnp.stack(limbs)


def _bcast(c, x):
    """Reshape a (20, 1) constant to broadcast against x's trailing dims.
    Pallas overrides pass constants already expanded to x's full shape
    (Mosaic cannot broadcast in sublanes and lanes at once) — pass through.
    """
    if c.shape == x.shape:
        return c
    return c.reshape((LIMBS,) + (1,) * (x.ndim - 1))


def add(a, b):
    # both weakly reduced (<= MASK+3): sums <= 2^14, 2 passes suffice
    return carry(a + b, passes=2)


def sub(a, b):
    # a - b + pad: pad has every limb >= 2^13+ε, so limbs stay positive in
    # [~8150, 3*2^13] — no borrow ripple, 2 passes suffice
    return carry(a - b + _bcast(_c("SUB_PAD", SUB_PAD), a), passes=2)


def neg(a):
    return carry(_bcast(_c("SUB_PAD", SUB_PAD), a) - a, passes=2)


def mul(a, b):
    """Schoolbook multiply + parallel fold + carry.

    Inputs weakly reduced (limbs <= ~2^13): every product column is
    < 20·(2^13+3)^2 < 2^31, so sums stay in int32.  (Tree-structured and
    grouped accumulation variants were tried on a v5e: both blew compile
    time through the roof; the plain accumulate loop fuses fine.)  The 19 high limbs fold back with 2^260 ≡ 608 (mod p),
    split into a low part (<= MASK, ×608 <= 2^22.3) and a carry part
    (<= 2^17.7, ×608 <= 2^27.3, shifted one limb up) so the fold
    multiplies can't overflow either.
    """
    n = a.shape[1:]
    if _c("PALLAS", False):
        # Mosaic can lower neither lax.scatter (.at[].add) nor
        # lax.dynamic_slice on values — accumulate the low (cols 0..19)
        # and high (cols 20..38) halves with static slices + concats.
        lo = jnp.zeros((LIMBS,) + n, dtype=jnp.int32)
        hi = jnp.zeros((LIMBS - 1,) + n, dtype=jnp.int32)
        for j in range(LIMBS):
            term = a * b[j][None]  # contributes to columns j .. j+19
            if j == 0:
                lo = lo + term
            else:
                lo = lo + jnp.concatenate(
                    [jnp.zeros((j,) + n, jnp.int32), term[: LIMBS - j]], 0
                )
                hi_parts = [term[LIMBS - j :]]
                if LIMBS - 1 - j > 0:
                    hi_parts.append(
                        jnp.zeros((LIMBS - 1 - j,) + n, jnp.int32)
                    )
                hi = hi + (
                    jnp.concatenate(hi_parts, 0)
                    if len(hi_parts) > 1
                    else hi_parts[0]
                )
        prod = jnp.concatenate([lo, hi], axis=0)
    else:
        prod = jnp.zeros((2 * LIMBS - 1,) + n, dtype=jnp.int32)
        for j in range(LIMBS):
            prod = prod.at[j : j + LIMBS].add(a * b[j][None])
    return _fold_and_carry(prod, n)


def _fold_and_carry(prod, n):
    """(39, ...) product columns -> weakly-reduced (20, ...) element.

    Shared tail of mul/sqr: fold the 19 high limbs back with
    2^260 ≡ 608 (mod p), split so no int32 overflow (see mul), then 3
    parallel carry passes.
    """
    lo = prod[:LIMBS]
    hi = prod[LIMBS:]  # 19 limbs, each < 2^31
    hi_lo = hi & MASK
    hi_hi = hi >> RADIX
    zero = jnp.zeros((1,) + n, dtype=jnp.int32)
    lo = lo + jnp.concatenate([hi_lo * FOLD, zero], axis=0)
    lo = lo + jnp.concatenate([zero, hi_hi * FOLD], axis=0)
    return carry(lo, passes=3)


def sqr(a):
    """Squaring = mul(a, a).  A half-product triangular variant was
    measured SLOWER on TPU: variable-length slice updates and the strided
    diagonal scatter defeat XLA's fusion, costing more than the saved
    multiplies.  The uniform schoolbook wins."""
    return mul(a, a)


def mul_small(a, k: int):
    """Multiply by a small scalar constant (k < 2^17)."""
    return carry(a * k)


def _sq_n(x, n: int):
    if n <= 4:
        for _ in range(n):
            x = sqr(x)
        return x
    return jax.lax.fori_loop(0, n, lambda _, v: sqr(v), x)


def _pow_core(z):
    """Shared prefix of the classic curve25519 exponentiation chains:
    returns (z^(2^250 - 1), z^11, z^(2^5 - 1))."""
    t0 = sqr(z)  # 2
    t1 = mul(z, _sq_n(t0, 2))  # 9
    t0 = mul(t0, t1)  # 11
    t2 = sqr(t0)  # 22
    t1 = mul(t1, t2)  # 31 = 2^5 - 1
    z5 = t1
    t2 = _sq_n(t1, 5)
    t1 = mul(t1, t2)  # 2^10 - 1
    t2 = mul(_sq_n(t1, 10), t1)  # 2^20 - 1
    t3 = mul(_sq_n(t2, 20), t2)  # 2^40 - 1
    t2 = mul(_sq_n(t3, 10), t1)  # 2^50 - 1
    t3 = mul(_sq_n(t2, 50), t2)  # 2^100 - 1
    t4 = mul(_sq_n(t3, 100), t3)  # 2^200 - 1
    t3 = mul(_sq_n(t4, 50), t2)  # 2^250 - 1
    return t3, t0, z5


def inv(z):
    """z^(p-2) = z^(2^255 - 21)."""
    t3, z11, _ = _pow_core(z)
    return mul(_sq_n(t3, 5), z11)  # 2^255 - 32 + 11 = 2^255 - 21


def pow_p58(z):
    """z^((p-5)/8) = z^(2^252 - 3)."""
    t3, _, _ = _pow_core(z)
    return mul(_sq_n(t3, 2), z)  # 2^252 - 4 + 1 = 2^252 - 3


def inv_batch(z, min_width: int = 128):
    """Montgomery-style batched inversion across the lane (batch) axis.

    ``inv`` runs a ~254-step square/multiply ladder on every lane; on TPU a
    (20, 512) tile occupies four 128-lane vregs, so the ladder's cost is
    proportional to width.  Tree-reduce the batch by pairwise lane products
    down to ``min_width`` (one vreg), run the ladder ONCE at that width,
    then expand the inverses back up: from i = 1/(a·b), 1/a = i·b and
    1/b = i·a.  Extra cost ≈ 2–3 full-width muls; saving ≈ 3/4 of the
    ladder at 512 lanes.

    A single zero lane would null every tree product, poisoning the whole
    batch, so zeros are substituted with 1 first; their output slot is
    garbage (NOT 0, unlike ``inv``) — callers must already be masking those
    lanes (in the verify kernel a zero Z can only arise from a
    decompress-failed lane, which ``fail`` masks; complete Edwards
    additions keep Z ≠ 0 for curve points).

    mul/sqr use no broadcast constants, so narrow widths are safe under
    the Pallas const-override scheme (constants there are pre-broadcast to
    the full tile width and never reach this code path).
    """
    n = z.shape[1]
    if n <= min_width or n % 2:
        return inv(z)
    zero = is_zero(z)
    cur = select(zero, one_fe(z.shape[1:], z.dtype), z)
    levels = [cur]
    while cur.shape[1] > min_width and cur.shape[1] % 2 == 0:
        half = cur.shape[1] // 2
        cur = mul(cur[:, :half], cur[:, half:])
        levels.append(cur)
    invs = inv(cur)
    for lvl in reversed(levels[:-1]):
        half = lvl.shape[1] // 2
        inv_lo = mul(invs, lvl[:, half:])
        inv_hi = mul(invs, lvl[:, :half])
        invs = jnp.concatenate([inv_lo, inv_hi], axis=1)
    return invs


def canonical(x):
    """Weakly-reduced -> fully reduced (< p), canonical limbs."""
    x = carry_exact(x)
    # weakly reduced: x < p + ε < 2p, so at most one subtraction of p.
    # lexicographic compare with p from the top limb down: x >= p?
    p_limbs = int_to_limbs(P)
    eq_so_far = jnp.ones_like(x[0], dtype=jnp.bool_)
    gt = jnp.zeros_like(x[0], dtype=jnp.bool_)
    for i in range(LIMBS - 1, -1, -1):
        pi = int(p_limbs[i])
        gt = gt | (eq_so_far & (x[i] > pi))
        eq_so_far = eq_so_far & (x[i] == pi)
    need_sub = gt | eq_so_far
    sub_p = _bcast(_c("P_COL", P_LIMBS_COL), x)
    return carry_exact(x - jnp.where(need_sub[None], sub_p, 0))


def eq(a, b):
    ca, cb = canonical(a), canonical(b)
    return jnp.all(ca == cb, axis=0)


def is_zero(a):
    return jnp.all(canonical(a) == 0, axis=0)


def parity(a):
    """Least-significant bit of the canonical value."""
    return canonical(a)[0] & 1


def select(cond, a, b):
    """cond: (N,) bool; a, b: (20, N)."""
    return jnp.where(cond[None], a, b)


# -- byte conversion (device) ----------------------------------------------
def limbs_from_bytes(b):
    """(32, N) int32 bytes (little-endian) -> (20, N) limbs.  The caller
    masks the sign bit out of byte 31 first if decoding a point."""
    limbs = []
    for k in range(LIMBS):
        bit0 = RADIX * k
        j0, r0 = divmod(bit0, 8)
        acc = b[j0] >> r0
        width = 8 - r0
        j = j0 + 1
        while width < RADIX and j < 32:
            acc = acc | (b[j] << width)
            width += 8
            j += 1
        limbs.append(acc & MASK)
    return jnp.stack(limbs)


def bytes_from_limbs(x):
    """canonical (20, N) limbs -> (32, N) int32 bytes little-endian."""
    out = []
    for j in range(32):
        bit0 = 8 * j
        k0, r0 = divmod(bit0, RADIX)
        acc = x[k0] >> r0
        width = RADIX - r0
        if width < 8 and k0 + 1 < LIMBS:
            acc = acc | (x[k0 + 1] << width)
        out.append(acc & 0xFF)
    return jnp.stack(out)
