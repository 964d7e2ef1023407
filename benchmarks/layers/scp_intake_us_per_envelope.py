"""herder / SCP (overlay/manager.py, herder/): the hand-over of a flush's
envelopes — ``scp.deliver`` and, for envelopes that waited for a tx set or a
quorum set, ``herder.recheck`` — less SCP's own seconds and the ledger close
inside them (``receive_s``, ``close_s``), per envelope flushed: the window
check, the eager re-verify, ``PendingEnvelopes``, the item fetch and the
relay; microseconds."""

from benchmarks.layers import scp_common as SC


def read(run):
    got, n = SC.intake(run), SC.flushed(run)
    if got is None or not n:
        return None
    seconds, receive_s, close_s, _ = got
    return (seconds - receive_s - close_s) / n * 1e6
