"""Batched multi-block SHA-256 device kernel (ISSUE r22, ops/sha256.py).

Differential against hashlib across the FIPS 180-4 padding boundaries —
55/56 (terminator fits / spills), 63/64/65 (block edge), the empty
string — and genuinely multi-block messages, all through the chained
compression over per-item block counts (mixed lengths share one batch,
one compiled graph).  Host-side staging (``blocks_for`` /
``pack_frames``) is pinned byte-for-byte.

Compile budget: the XLA legs share ONE batch per row-shape (mixed
lengths by design), so the whole module adds two small compile shapes;
the Pallas-interpret parity leg rides ``-m slow`` per the r10 budget
policy (the Mosaic-compiled kernel runs on the chip in chip_smoke.py's
kernel leg).
"""

from __future__ import annotations

import hashlib
import struct

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from stellar_tpu.ops import sha256 as dev  # noqa: E402

pytestmark = pytest.mark.tpu_kernel

# every padding boundary class: 0, tiny, 55/56 (terminator+length fit /
# spill), 63/64/65 (block edge), two-block edges at 119/120, deeper
# multi-block tails
BOUNDARY_LENGTHS = (0, 1, 3, 54, 55, 56, 63, 64, 65, 119, 120, 127, 128,
                    200, 255, 256)


def _messages(lengths, seed=17):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
            for n in lengths]


class TestHostStaging:
    def test_blocks_for_boundaries(self):
        # 55 is the last length whose 0x80 + 8-byte length field fit in
        # one block; 64*k - 9 is the general edge
        assert dev.blocks_for(0) == 1
        assert dev.blocks_for(55) == 1
        assert dev.blocks_for(56) == 2
        assert dev.blocks_for(64) == 2
        assert dev.blocks_for(119) == 2
        assert dev.blocks_for(120) == 3

    def test_pack_frames_layout(self):
        msg = bytes(range(10))
        packed, counts = dev.pack_frames([msg])
        assert counts.tolist() == [1]
        assert packed.shape == (64, 1)
        col = packed[:, 0]
        assert col[:10].tobytes() == msg
        assert col[10] == 0x80
        assert col[11:56].tobytes() == bytes(45)
        assert col[56:64].tobytes() == struct.pack(">Q", 80)  # 10 bytes
        # pinned max_blocks widens the shape without moving the padding
        packed2, _ = dev.pack_frames([msg], max_blocks=4)
        assert packed2.shape == (256, 1)
        assert (packed2[:64, 0] == col).all()
        assert not packed2[64:].any()

    def test_pack_frames_refuses_overflow(self):
        with pytest.raises(ValueError, match="blocks"):
            dev.pack_frames([bytes(200)], max_blocks=1)

    def test_empty_batch(self):
        assert dev.sha256_batch([]) == []


class TestXlaKernel:
    def test_boundary_lengths_vs_hashlib(self):
        """One mixed batch across every padding class — the chained
        compression must freeze each lane at ITS block count."""
        msgs = _messages(BOUNDARY_LENGTHS)
        got = dev.sha256_batch(msgs)
        for m, d in zip(msgs, got):
            assert d == hashlib.sha256(m).digest(), f"len={len(m)}"

    def test_pinned_shape_reuse_matches_unpinned(self):
        """The hashplane device backend pins power-of-two max_blocks for
        jit reuse: digests must not depend on how far the shape is
        padded past the longest item."""
        msgs = _messages((0, 55, 56, 120), seed=23)
        packed, counts = dev.pack_frames(msgs, max_blocks=8)
        rows = dev._jit_rows_from_packed(
            jnp.asarray(packed), jnp.asarray(counts)
        )
        out = np.asarray(rows, dtype=np.int32).astype(np.uint8)
        for i, m in enumerate(msgs):
            assert out[:, i].tobytes() == hashlib.sha256(m).digest()


@pytest.mark.slow
class TestPallasParity:
    def test_pallas_interpret_matches_hashlib(self):
        msgs = _messages(BOUNDARY_LENGTHS, seed=29)
        got = dev.sha256_batch(msgs, pallas=True, interpret=True)
        for m, d in zip(msgs, got):
            assert d == hashlib.sha256(m).digest(), f"len={len(m)}"
