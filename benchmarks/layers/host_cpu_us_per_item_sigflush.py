"""host (the machine, not the program): CPU microseconds the measuring
process spent, all its threads, user and system, for each item verified in
the window (``getrusage`` at the window's two edges).  The same work costs
more CPU time where the host's cores are shared (a busy sibling thread, a
cold cache), and less where the program stages and hashes more cheaply."""

from benchmarks.layers.common import host_cpu_us_per_item as read  # noqa: F401
