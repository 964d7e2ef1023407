"""closed loop (harness): verdicts returned in the timed window over its
seconds, per chip of the backend's mesh (``mesh_devices`` of the
``sig_backend`` counters; an unsharded backend counts as one chip) —
``BASELINE.json``'s own unit.  Against ``pay5000.sigflush``'s
``verifies_per_s`` it is what a chip retains of the one-chip rate."""

from benchmarks import stats


def read(run):
    m = stats.work_over_wall(run["all_readings"], *run["window"])
    if m is None:
        return None
    chips = run["counters"]["after"]["sig_backend"].get("mesh_devices") or 1
    return m.value / chips
