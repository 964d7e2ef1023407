"""signature backend (crypto/sigbackend.py): share of the window's
verifications that reached the device (``/info`` ``sig_backend`` counters)."""

from benchmarks.layers.common import device_verify_share_pct as read  # noqa: F401
