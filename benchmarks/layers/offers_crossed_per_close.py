"""apply / commit (tx/offerexchange.py): resting offers taken or reduced in
a close (``op.exchange``'s ``crossed``, which the generator repeats on
``bench.exchange``); median over the window's closes."""

from benchmarks import spans as SP


def read(run):
    if not SP.named(run["spans"], "bench.exchange"):
        return None
    return SP.per_reading_median(
        run["spans"], run["readings"],
        lambda sp: float(sum(s.attrs["crossed"] for s in SP.named(sp, "bench.exchange"))),
    )
