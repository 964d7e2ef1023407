"""verify pipeline (ops/ed25519.py): ``ed25519.host_hash`` span time (strict
gate + SHA-512 mod L + staging) per item staged."""

from benchmarks import spans as SP


def read(run):
    sp = SP.named(run["spans"], "ed25519.host_hash")
    items = sum((s.attrs or {}).get("items", 0) for s in sp)
    if not items:
        return None
    return SP.seconds(sp, "ed25519.host_hash") / items * 1e6
