"""What the readers of the SCP intake share: the generator's repeats of the
program's ``scp.deliver`` / ``herder.recheck`` attributes (``bench.scp_intake``)
and of ``overlay.scp_flush``'s (``bench.scp_flush``); ``spans.compact`` keeps
neither span's attributes."""

from benchmarks import spans as SP


def intake(run):
    """-> (seconds, receive_s, close_s, to_scp) summed over the window's
    hand-over loops and rechecks, or None."""
    sp = SP.named(run["spans"], "bench.scp_intake")
    if not sp:
        return None
    return tuple(sum(s.attrs[k] for s in sp) for k in ("seconds", "receive_s", "close_s", "to_scp"))


def flushed(run) -> int:
    return sum(s.attrs["envelopes"] for s in SP.named(run["spans"], "bench.scp_flush"))
