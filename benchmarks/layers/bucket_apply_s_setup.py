"""history (history/catchupsm.py ``_apply_minimal``, bucket/bucket.py
``Bucket.apply``): the seconds of set-up the catch-up spent replaying bucket
entries into SQL — ``bucket_apply_s`` of ``/info`` ``history`` as the counters
stood when the window opened (an absolute of the process up to there, as the
``first_dispatch_*`` readers read theirs).  None from a program that does not
count it."""


def read(run):
    try:
        return float(run["counters"]["before"]["history"]["bucket_apply_s"])
    except KeyError:
        return None
