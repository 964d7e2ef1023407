"""herder / SCP (scp/quorum.py): nodes visited by ``is_quorum_with`` /
``is_v_blocking_with`` per envelope handed to SCP, over the window
(``/info`` ``scp``: ``quorum_nodes_scanned`` / ``to_scp``).  The engagement
reader of federated voting: the watcher's transitive quorum is four nodes,
so a scan of it alone would read about five."""

from benchmarks.layers import common as C


def read(run):
    try:
        n = C.counter_delta(run, "scp", "to_scp")
        nodes = C.counter_delta(run, "scp", "quorum_nodes_scanned")
    except KeyError:  # a program without the counters
        return None
    return nodes / n if n else None
