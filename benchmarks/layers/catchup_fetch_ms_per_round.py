"""history (history/catchupsm.py): the ``catchup.fetch`` span — every file of
the range downloaded (``get``) and gunzipped by subprocesses, from the first
spawn to the last exit; median over the window's rounds."""

from benchmarks.layers import catchup_common as C


def read(run):
    return C.median_ms(run, "catchup.fetch")
