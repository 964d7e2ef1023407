"""What several layer readers share.  A reader is ``read(run) -> float |
None``; ``run`` holds the traced window's spans (``benchmarks.spans.S``),
readings, counters before and after, the reduced device trace and the
cell's files.  A reader that finds nothing to read returns None and the
harness leaves the metric out of the line."""

from __future__ import annotations

from benchmarks import reduce as R
from benchmarks import spans as SP
from benchmarks import stats


def counter_delta(run: dict, *path: str) -> float:
    a, b = run["counters"]["after"], run["counters"]["before"]
    for k in path:
        a, b = a[k], b[k]
    return a - b


def device_verify_share_pct(run: dict):
    """Share of the window's signature verifications that ran on the
    device: device items / (device + cutover + wedge fallback + host
    assist), from the signature backend's counters."""
    d = lambda k: counter_delta(run, "sig_backend", k)  # noqa: E731
    host = d("cpu_cutover_items") + d("wedge_fallback_items") + d("host_assist_items")
    device = d("items") - d("host_assist_items")
    if device + host <= 0:
        return None
    return 100.0 * device / (device + host)


def verify_kernel_seconds(run: dict) -> float:
    ops = R.op_seconds(run["trace"], run["w0"], run["w1"])
    return sum(v for k, v in ops.items() if "verify_kernel_pallas" in k)


def ms_per_close(run: dict, fn):
    v = SP.per_reading_median(run["spans"], run["readings"], fn)
    return None if v is None else v * 1e3


def slice_rate_p50(run: dict):
    """Median of the timed window's slice rates (``slice_s`` of the traffic
    file), edge-straddling readings prorated."""
    m = stats.slice_rate_median(
        run["all_readings"], *run["window"], float(run["traffic"].get("slice_s", 1.0))
    )
    return None if m is None else m.value


def host_cpu_us_per_item(run: dict):
    """CPU microseconds of the measuring process (user + system, all
    threads) per item of the window's readings (``measure.host_counters``
    at the window's two edges)."""
    host = run.get("host")
    items = sum(r.items for r in run["readings"])
    if not host or not items:
        return None
    cpu = sum(host["after"][k] - host["before"][k] for k in ("utime", "stime"))
    return cpu * 1e6 / items
