"""herder / SCP (herder/herder.py): envelopes handed to SCP a slot (the
herder's ``to_scp``, which the generator reads at a slot's two ends and puts
on ``bench.scp_slot``); median over the window's slots: how much of a flood
SCP sees before the slot closes."""

import statistics

from benchmarks import spans as SP


def read(run):
    n = [s.attrs["to_scp"] for s in SP.named(run["spans"], "bench.scp_slot")]
    return float(statistics.median(n)) if n else None
