"""history (history/catchupsm.py): ``catchup.decode`` (both files of a
checkpoint read into header entries and transaction frames, each set decoded
once) plus ``catchup.verify_chain`` (every header re-hashed and linked to the
one before and to the local LCL), per round of the window."""

from benchmarks import spans as SP


def read(run):
    rounds = len(SP.named(run["spans"], "catchup.decode"))
    if not rounds:
        return None
    return SP.seconds(run["spans"], "catchup.decode", "catchup.verify_chain") * 1e3 / rounds
