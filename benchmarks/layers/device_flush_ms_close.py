"""signature backend (crypto/sigbackend.py): ``device_flush_ms`` in a close
cell — the median ``sig.device_flush`` span, one a close."""

from benchmarks.layers.device_flush_ms import read  # noqa: F401
