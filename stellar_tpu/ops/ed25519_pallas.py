"""Pallas TPU kernel for batched ed25519 verification.

Same math and bit-exact semantics as ops/ed25519.verify_kernel (decompress +
Straus double-scalar-mult + encode + compare; see that module for the
host/device split and provenance), but tiled over the batch so the per-item
dynamic niels table and the accumulator stay **VMEM-resident** for the whole
64-window ladder.  The XLA version re-reads the (4·16·20·N) table from HBM
on every window (~10.7 GB per 32k batch) — that traffic and the
fusion-boundary spills are what this kernel removes.

Layout per grid step: a batch tile of ``NT`` lanes; field elements are
(20, NT) int32 (radix-2^13 limbs on sublanes, items on lanes — ops/fe.py).
VMEM budget at NT=512: inputs ~3 MB (incl. the pre-broadcast tables),
table scratch 2.6 MB, live temps ~2 MB — under the 16 MB core limit.

Mosaic lowering constraints shaped this module (all hit in practice):
no lax.scatter (`.at[].add/.set`), no lax.dynamic_slice on values, no
broadcast across sublanes AND lanes in one op (constants arrive
pre-broadcast to (…, NT)), no zero-sized vectors.  fe.py selects
Mosaic-safe forms via the ``PALLAS`` const-override flag.

Falls back to interpreter mode off-TPU so the differential tests exercise
the same code path on the CPU mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import fe
from . import ed25519 as ed

NT = 512  # batch tile (lanes); must divide the padded batch

# Compress-stage lane-tree Montgomery inversion: one field inversion a tile
# in place of one a lane (a tile's batch axis is local: ed.compress).
_BATCH_INV = True

# Signed-digit windows: recode the radix-16 scalar digits to [-8, 7] with
# carry, so both niels tables need only k = 1..8 (half the dynamic-table
# build, ~half the select where-chains; the sign is applied at select time
# — a niels negation is one component swap plus one field negation).  Off:
# never measured on the chip; the path stays for the one paired run that
# decides it (ROADMAP S5: flip this in a scratch copy).
_SIGNED_WIN = False

_CONST_NAMES = ("SUB_PAD", "P_COL", "D", "D2", "SQRT_M1")


def _niels_identity(n):
    zero = jnp.zeros((fe.LIMBS, n), jnp.int32)
    one = fe.one_fe(n)
    return (one, one, zero, one + one)


def _select_niels(tab_ref, nib):
    """Where-chain select of niels entry ``nib`` from a (4, 16, 20, NT)
    VMEM table ref -> 4 × (20, NT).  Entry 0 is the niels identity."""
    comps = list(_niels_identity(nib.shape[0]))
    for k in range(1, 16):
        mask = (nib == k)[None, :]
        for c in range(4):
            comps[c] = jnp.where(mask, tab_ref[c, k], comps[c])
    return tuple(comps)


def _select_niels_signed(tab_ref, d):
    """Signed-digit select: the table holds k·P (niels) for k = 1..8 and
    ``d`` ∈ [-8, 7]; |d| picks the entry, d < 0 negates it (x → −x in
    niels form: swap Y+X ↔ Y−X, negate T·2d, Z unchanged)."""
    k = jnp.abs(d)
    comps = list(_niels_identity(d.shape[0]))
    for kk in range(1, 9):
        mask = (k == kk)[None, :]
        for c in range(4):
            comps[c] = jnp.where(mask, tab_ref[c, kk], comps[c])
    yp, ym, t2d, z2 = comps
    negm = (d < 0)[None, :]
    return (
        jnp.where(negm, ym, yp),
        jnp.where(negm, yp, ym),
        jnp.where(negm, fe.neg(t2d), t2d),
        z2,
    )


def _kernel(
    const_ref, base_ref, a_ref, r_ref, s_ref, h_ref, out_ref, tab_ref,
    nib_ref, *, signed,
):
    override = {
        name: const_ref[i] for i, name in enumerate(_CONST_NAMES)
    }  # each (20, NT), pre-broadcast on host
    override["PALLAS"] = True  # select Mosaic-compatible lowerings in fe ops
    with fe.const_override(override):
        a_bytes = a_ref[:].astype(jnp.int32)
        r_bytes = r_ref[:].astype(jnp.int32)

        a_sign = a_bytes[31] >> 7
        a_masked = fe.set_row(a_bytes, 31, a_bytes[31] & 0x7F)
        a_y_limbs = fe.limbs_from_bytes(a_masked)
        a_pt, fail = ed.decompress(a_y_limbs, a_sign)
        neg_a = ed.point_negate(a_pt)

        # dynamic table: k * (-A), niels form, into VMEM scratch —
        # k = 1..15 unsigned, only 1..8 signed (the select negates)
        top = 9 if signed else 16
        pt = neg_a
        for k in range(1, top):
            niels = ed.to_niels(pt)
            for c in range(4):
                tab_ref[c, k] = niels[c]
            if k < top - 1:
                pt = ed.point_add(pt, neg_a)

        n = a_bytes.shape[1]

        # scalars arrive as 32 packed bytes (8x less transfer than int32
        # nibbles); split into (64, NT) int32 nibble scratch with STATIC
        # row indices — Mosaic allows dynamic row reads on int32 refs but
        # not int8, and the loop below indexes rows dynamically.
        for j in range(32):
            sb = s_ref[j].astype(jnp.int32)
            hb = h_ref[j].astype(jnp.int32)
            nib_ref[0, 2 * j] = sb & 0xF
            nib_ref[0, 2 * j + 1] = sb >> 4
            nib_ref[1, 2 * j] = hb & 0xF
            nib_ref[1, 2 * j + 1] = hb >> 4

        if signed:
            # recode digits to [-8, 7] with carry; both scalars are < L
            # < 2^253 (strict gate / host mod-L — the verify_kernel_pallas
            # docstring's stated precondition), so the top nibble is at
            # most 1 and the final carry can never overflow window 63
            for plane in range(2):
                carry = jnp.zeros((n,), jnp.int32)
                for t in range(64):
                    d = nib_ref[plane, t] + carry
                    carry = (d >= 8).astype(jnp.int32)
                    nib_ref[plane, t] = d - (carry << 4)

        sel = _select_niels_signed if signed else _select_niels

        def body(i, acc):
            t = ed.WINDOWS - 1 - i
            for k in range(4):
                acc = ed.point_double(acc, need_t=(k == 3))
            s_nib = nib_ref[0, t]
            h_nib = nib_ref[1, t]
            acc = ed.point_add_niels(acc, sel(base_ref, s_nib))
            acc = ed.point_add_niels(
                acc, sel(tab_ref, h_nib), need_t=False
            )
            return acc

        acc = jax.lax.fori_loop(0, ed.WINDOWS, body, ed.point_identity(n))
        enc = ed.compress(acc, batch_inv=_BATCH_INV)
        match = jnp.all(enc == r_bytes, axis=0)
        out_ref[:] = (match & ~fail)[None]


# The kernel's name in a device trace, stated here and not inherited from
# whatever a Python function happens to be called: the benchmark finds the
# kernel's device events by it (benchmarks/layers/common.py).
VERIFY_KERNEL_NAME = "verify_kernel_pallas"


@functools.partial(jax.jit, static_argnames=("interpret", "signed"))
def verify_kernel_pallas(
    a_bytes, r_bytes, s_bytes, h_bytes, interpret=False, signed=None
):
    """Same math/result as ops/ed25519.verify_kernel, but the four inputs
    are raw (32, N) uint8 byte columns (A, R, s, h=SHA-512(R‖A‖M) mod L,
    all little-endian) — 8x less host->device transfer than the XLA
    kernel's int32+nibble interface.  N must be a multiple of NT.
    ``signed`` picks the signed-digit window variant (default: the
    module's ``_SIGNED_WIN``).  PRECONDITION for equivalence:
    s and h < 2^253 — i.e. gate-canonical s (strict_input_ok_batch
    rejects s >= L, exactly libsodium's rule) and host-reduced h.  Every
    BatchVerifier path guarantees this; a RAW caller feeding an ungated
    s in [8L, 2^256) would see the unsigned kernel accept via the modular
    identity while the signed recode drops its window-63 carry and
    rejects — neither answer is consensus-reachable because the composed
    verifier (gate + kernel) rejects such s before dispatch either way."""
    if signed is None:
        signed = _SIGNED_WIN
    tabn = 9 if signed else 16
    n = a_bytes.shape[1]
    assert n % NT == 0, f"batch {n} not a multiple of tile {NT}"
    grid = n // NT
    consts = jnp.stack(
        [
            jnp.broadcast_to(c, (fe.LIMBS, NT))
            for c in (
                fe.SUB_PAD,
                fe.P_LIMBS_COL,
                fe.const_fe(ed.D),
                fe.const_fe(ed.D2),
                fe.const_fe(ed.SQRT_M1),
            )
        ]
    )  # (5, 20, NT)
    base_tab = jnp.broadcast_to(
        ed._BASE_TABLE[:, :tabn, :, None], (4, tabn, fe.LIMBS, NT)
    )  # static niels table of k*B, lane-replicated for Mosaic
    return pl.pallas_call(
        functools.partial(_kernel, signed=signed),
        name=VERIFY_KERNEL_NAME,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec(
                (5, fe.LIMBS, NT), lambda i: (0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (4, tabn, fe.LIMBS, NT), lambda i: (0, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((32, NT), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((32, NT), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((32, NT), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((32, NT), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, NT), lambda i: (0, i), memory_space=pltpu.VMEM
        ),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.bool_),
        scratch_shapes=[
            pltpu.VMEM((4, tabn, fe.LIMBS, NT), jnp.int32),
            pltpu.VMEM((2, 64, NT), jnp.int32),
        ],
        interpret=interpret,
    )(consts, base_tab, a_bytes, r_bytes, s_bytes, h_bytes)[0]
