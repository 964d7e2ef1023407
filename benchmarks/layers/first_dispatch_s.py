"""verify pipeline (ops/ed25519.py): the seconds of set-up in which a thread
was inside a bucket's first dispatch — ``wall_s`` of the ``first_dispatch``
block of ``BatchVerifier.stats()`` (PR 37: the union of the records'
intervals) as the counters stood when the window opened.  An absolute of the
process up to there, not a delta over the window."""


def account(run):
    """The program's account of its first dispatches at the window's
    opening; None from a program that keeps none."""
    try:
        return run["counters"]["before"]["sig_backend"]["first_dispatch"]
    except KeyError:
        return None


def read(run):
    fd = account(run)
    return None if fd is None else fd["wall_s"]
