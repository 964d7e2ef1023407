#!/usr/bin/env python
"""The measuring process of one run of one cell (started by ``run.py``).

Set-up (build the node or backend and the data from the seed; warm up on the
cell's own traffic until three consecutive readings are steady; collect and
freeze the garbage), then the window of ``--seconds``, then the drain, the
comparison with the plain reference and the one result line.
"""

from __future__ import annotations

import argparse
import gc
import glob
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import stats  # noqa: E402
from benchmarks.spans import S, compact  # noqa: E402

STEADY_READINGS = 3
MAX_WARMUP_READINGS = 40


class Ctx:
    """What a generator is given: the cell, the seed, where to write."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.spans: list = []  # the harness's own spans (bench.*)

    def span(self, name: str, start: float, end: float, **attrs) -> None:
        self.spans.append(S(name, start, end, 0, attrs or None))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: dict, workload: str):
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, conf


def metric_cells(metric: dict, bench: dict, moves: str) -> list:
    """The cells a metric is read in: its ``workloads`` key, else every cell
    that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return metric["workloads"]
    e2e = next(m for m in bench["end_to_end"] if m["name"] == moves)
    return e2e.get("workloads", [w["name"] for w in bench["workloads"]])


def phase_table(spans: list, readings: list, names: list) -> dict:
    """Per reading, the seconds of each named span that started inside it:
    the series behind the per-layer medians, kept in the run's file."""
    from benchmarks.spans import by_reading, seconds

    table = {n: [] for n in names}
    if names:
        for inside in by_reading([s for s in spans if s.name in names], readings):
            for n in names:
                table[n].append(seconds(inside, n))
    return table


def device_info(rehearsal: bool) -> dict:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearsal:
        print(
            f"benchmarks: JAX found no TPU (platform {devs[0].platform!r}); nothing measured",
            file=sys.stderr,
        )
        raise SystemExit(2)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}


def host_counters() -> dict:
    """What the operating system says of this process and its host, read at
    the window's two edges: its CPU seconds, its context switches, and where
    the machine has them the cgroup's throttle count and the host's CPU
    pressure (a sealed machine may report the last three as nothing)."""
    import resource

    ru = resource.getrusage(resource.RUSAGE_SELF)
    out = {
        "nivcsw": ru.ru_nivcsw, "nvcsw": ru.ru_nvcsw,
        "utime": ru.ru_utime, "stime": ru.ru_stime, "loadavg": os.getloadavg()[0],
    }
    for key, path in (("cpu_stat", "/sys/fs/cgroup/cpu.stat"), ("pressure", "/proc/pressure/cpu")):
        try:
            with open(path) as f:
                out[key] = f.read()
        except OSError:
            pass
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--out", default=None, help="directory for the run's file")
    ap.add_argument("--t0", type=float, default=None, help="time.time() at process start")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--control", default=None, help="break the timed path (benchmarks/controls.py)")
    ap.add_argument("--keep-trace", action="store_true")
    return ap.parse_args(argv)


def main(argv=None, root: str = ROOT) -> int:
    args = parse(argv)
    t_start = args.t0 if args.t0 is not None else time.time()
    mono_minus_wall = time.monotonic() - time.time()
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell, conf = find_cell(bench, args.workload)
    config = load_json(os.path.join(root, conf["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))
    rehearsal = args.rehearse_cpu
    device = device_info(rehearsal)
    if device["count"] < cell["chips"] and not rehearsal:
        print(f"benchmarks: {device['count']} chip(s), the cell needs {cell['chips']}", file=sys.stderr)
        return 2

    import jax
    import jax.numpy as jnp

    from benchmarks import steady

    out_dir = args.out or os.path.join(root, "chiprun_out", "bench")
    os.makedirs(out_dir, exist_ok=True)
    tag = "%s.s%d.t%d.%d" % (args.workload, args.seed, args.trace, int(t_start))
    work = os.path.join(root, ".bench_work", tag + ".%d" % os.getpid())
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    ctx = Ctx(
        seed=args.seed, config=config, traffic=traffic, cell=cell, work=work,
        rehearsal=rehearsal, root=root, seconds=args.seconds,
    )
    watch = steady.Watch()
    gen = importlib.import_module("benchmarks.generators." + traffic["generator"])
    run_file: dict = {
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "rehearsal": rehearsal, "control": args.control, "device": device,
    }
    wl = None
    try:
        t_build = time.time()
        wl = gen.Workload(ctx)
        print(
            "set-up: %.1f s to the harness, %.1f s to build the node and the data"
            % (t_build - t_start, time.time() - t_build),
            flush=True,
        )
        if args.control:
            from benchmarks import controls

            controls.apply(args.control, wl)
        if args.trace:
            # one tiny operation for the traced run, dispatched between the
            # start of the trace and the opening of the window: a cell whose
            # traffic never reaches the device still shows the device alive
            probe = jax.jit(lambda x: x + 1)
            probe_arg = jnp.zeros((8, 128), jnp.int32)
            probe(probe_arg).block_until_ready()

        # -- warm-up: the cell's own traffic until steady ------------------------
        steady_run, warm = 0, []
        min_warm = int(traffic.get("min_warmup_readings", STEADY_READINGS))
        while steady_run < STEADY_READINGS or len(warm) < min_warm:
            mark = watch.mark()
            r = wl.step(False)
            first = watch.note_spans(wl.drain_spans())
            d = watch.since(mark)
            d["first_bucket_dispatches"] = first
            d["seconds"] = r.end - r.start
            warm.append(d)
            quiet = not (d["compile_events"] or d["cache_entries"] or first)
            steady_run = steady_run + 1 if quiet else 0
            if len(warm) > MAX_WARMUP_READINGS:
                raise RuntimeError("the program did not become steady in warm-up")
        run_file["warmup"] = warm
        print(
            "warm-up: %d readings in %.1f s, %d compile events (%.1f s), buckets %s, cache entries %d"
            % (len(warm), sum(w["seconds"] for w in warm), watch.compile_events, watch.compile_seconds,
               sorted(watch.buckets), watch.cache_entries()),
            flush=True,
        )
        ctx.spans.clear()
        before = wl.counters()
        logdir = os.path.join(work, "profile")

        def sync():
            # a clock marker: the trace's clock against time.monotonic
            with jax.profiler.TraceAnnotation("bench.sync.%d" % time.monotonic_ns()):
                pass

        gc.collect()
        gc.freeze()
        t_trace = None
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(logdir, profiler_options=opts)
            t_trace = time.monotonic()
            sync()
            # the device's clock runs a millisecond or two off the host's:
            # keep the probe clear of the traced window's edge
            time.sleep(0.01)
            probe(probe_arg).block_until_ready()

        # -- the window -----------------------------------------------------------
        readings, prog_spans, unsteady, gc_pauses = [], [], [], []
        gc_t = [0.0]

        def on_gc(phase, info):
            if phase == "start":
                gc_t[0] = time.monotonic()
            elif info["generation"] == 2:
                gc_pauses.append([gc_t[0], time.monotonic() - gc_t[0]])

        gc.callbacks.append(on_gc)
        compile_mark = watch.mark()
        host_before = host_counters()
        t_open = time.monotonic()
        setup_s = (t_open - mono_minus_wall) - t_start
        t_close = t_open + args.seconds
        try:
            while time.monotonic() < t_close:
                r = wl.step(True)
                readings.append(r)
                sp = wl.drain_spans()
                if watch.note_spans(sp):
                    unsteady.append({"at": r.end - t_open, "first_bucket_dispatch": True})
                prog_spans.extend(compact(sp))
            t_end = time.monotonic()
            host_after = host_counters()
            if args.trace:
                sync()
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        in_window = watch.since(compile_mark)
        gc.callbacks.remove(on_gc)
        wl.finish()
        prog_spans.extend(compact(wl.drain_spans()))
        after = wl.counters()
        mem = jax.devices()[0].memory_stats() or {}
        device["memory_peak_bytes"] = int(mem.get("peak_bytes_in_use", 0))

        # -- metrics --------------------------------------------------------------
        metrics, samples = {}, {}
        for name, how in traffic["end_to_end"].items():
            m = stats.reduce(how, readings, t_open, t_close)
            if m is None:
                raise RuntimeError(f"no reading of {name} in the window")
            metrics[name] = m.value * how.get("scale", 1.0)
            samples[name] = m.samples
            print("%s: %s over %d readings" % (name, how["reduce"], m.samples), flush=True)
        metrics["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}

        # -- correct --------------------------------------------------------------
        from benchmarks.reference import Check

        check = Check()
        check.compare("compilations_in_window", in_window["compile_events"], 0)
        check.compare("cache_entries_in_window", in_window["cache_entries"], 0)
        check.compare("first_dispatches_in_window", len(unsteady), 0)
        attempted, failed = wl.check(check)
        check.print()
        correct = bool(check.ok and failed == 0)

        line = {
            "correct": correct, "attempted": int(attempted), "failed": int(failed),
            "metrics": {}, "device": device,
        }
        spans = prog_spans + ctx.spans
        run_file["phases"] = phase_table(spans, readings, traffic.get("phases", []))
        run_file["gc_full_pauses"] = gc_pauses
        if args.trace:
            from benchmarks import reduce as R

            paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
            if not paths:
                raise RuntimeError("the profiler wrote no trace")
            trace = R.load(paths[0])
            if trace.offset_ns is None:
                raise RuntimeError("no clock marker in the trace")
            # the traced window opens just before the probe and closes with
            # the timed one; the layer readers get the timed window
            tw0 = t_trace * 1e9 + trace.offset_ns
            w0 = t_open * 1e9 + trace.offset_ns
            w1 = t_end * 1e9 + trace.offset_ns
            device["busy_s"] = R.busy_seconds(trace, tw0, w1)
            device["window_s"] = (w1 - tw0) / 1e9
            on_trace = [(s.name, s.start * 1e9 + trace.offset_ns, s.end * 1e9 + trace.offset_ns) for s in spans]
            line["breakdown"] = {
                "device_ops": R.top(R.op_seconds(trace, tw0, w1)),
                "idle_gaps": R.top(R.idle_gaps(trace, on_trace, tw0, w1)),
            }
            run = {
                "spans": [s for s in spans if s.start >= t_open and s.end <= t_end],
                "readings": stats.in_window(readings, t_open, t_end),
                "all_readings": readings,
                "counters": {"before": before, "after": after},
                "host": {"before": host_before, "after": host_after},
                "trace": trace, "w0": w0, "w1": w1, "window": (t_open, t_end),
                "config": config, "traffic": traffic, "device": device,
                "bench_dir": bench_dir, "rehearsal": rehearsal,
            }
            for m in bench["per_layer"]:
                if args.workload not in metric_cells(m, bench, m["moves"]):
                    continue
                reader = importlib.import_module("benchmarks.layers." + m["name"].replace("-", "_").replace(".", "_"))
                v = reader.read(run)
                if v is not None:
                    line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
            if args.keep_trace:
                shutil.copy(paths[0], os.path.join(out_dir, tag + ".xplane.pb"))
        else:
            for name, v in metrics.items():
                line["metrics"][name] = {"value": v, "unit": units[name]}

        run_file.update(
            {
                "t_open": t_open, "t_close": t_close, "setup_s": setup_s,
                "readings": [list(r) for r in readings],
                "samples": samples, "end_to_end": metrics,
                "compilations_in_window": in_window["compile_events"],
                "unsteady": unsteady, "checks": check.rows, "notes": wl.notes(),
                "counters": {"before": before, "after": after}, "line": line,
                "host": {"before": host_before, "after": host_after},
            }
        )
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(run_file, f, default=str)
        if rehearsal:
            line["rehearsal"] = True
        print(json.dumps(line), flush=True)
        return 0
    finally:
        if wl is not None:
            wl.close()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    raise SystemExit(main())
