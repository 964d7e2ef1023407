"""verify pipeline (ops/ed25519.py): the share of the window's
``ed25519.drain`` time spent waiting for the device's answer
(``ed25519.wait``); the rest is the read-back (device -> host copy, gate
mask, list)."""

from benchmarks import spans as SP


def read(run):
    drain = SP.seconds(run["spans"], "ed25519.drain")
    if drain <= 0 or not SP.named(run["spans"], "ed25519.wait"):
        return None
    return 100.0 * SP.seconds(run["spans"], "ed25519.wait") / drain
