"""closed loop (harness): the median of the window's slice rates of
transactions in closed ledgers, beside the end-to-end rate (all of them over
the window's seconds).  A stall hardly moves the median: where it reads
above the rate, the window held one."""

from benchmarks.layers.common import slice_rate_p50 as read  # noqa: F401
