"""verify pipeline (ops/ed25519.py): ``lane_fill_pct`` in a close cell —
items handed to the verifier per device lane dispatched."""

from benchmarks.layers.lane_fill_pct import read  # noqa: F401
