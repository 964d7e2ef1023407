#!/usr/bin/env python
"""Device-only verify-kernel timing on the real TPU (developer tool),
plus the multi-chip scaling harness behind the MULTICHIP_r*.json curve.

Measures the Pallas kernel's per-call time at batch N with inputs already
device-resident, nets out the fixed dispatch round trip (measured with a
trivial kernel), and prints verifies/s.  This is the harness behind the
round-3 device-kernel numbers (230k/s; the round-4 lane-tree Montgomery
inversion in compress is measured with the same method).

Usage: python profile_kernel.py [batch]   # needs the TPU
       python profile_kernel.py --mesh-curve [--tpu] [--devices 1,2,4,8]
           [--per-chip 2048] [--reps 3] [--out PATH]
         # the 1->N sharded-verify scaling curve (ISSUE r13): each leg is
         # a child process with its own device count; the CPU-mesh leg
         # (default) is the always-runnable differential oracle, --tpu is
         # the real-chip leg (not yet run on chips, ROADMAP S7).  Writes
         # MULTICHIP_r*.json.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))


def main(batch=32768, ab=False):
    import jax
    import jax.numpy as jnp

    from stellar_tpu.crypto import SecretKey
    from stellar_tpu.ops.ed25519 import BatchVerifier, L

    assert jax.default_backend() == "tpu", (
        f"needs the TPU (have {jax.default_backend()}); "
        "do not force JAX_PLATFORMS=cpu"
    )
    bv = BatchVerifier(max_batch=batch, backend="pallas")

    items = []
    for i in range(batch):
        sk = SecretKey.pseudo_random_for_testing(i)
        msg = b"kernel profile %08d" % i
        items.append((i, sk.public_raw, msg, sk.sign(msg)))
    staged = bv._stage_chunk(items, 0, len(items))
    # the packed (128, N) staging rows ARE the transposed byte columns
    a_b, r_b, s_b, h_b = (
        jnp.asarray(staged.packed[32 * k : 32 * (k + 1)]) for k in range(4)
    )

    # fixed dispatch RTT: a trivial jitted op on the same arrays
    trivial = jax.jit(lambda x: x[0] + 1)
    trivial(a_b).block_until_ready()
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        trivial(a_b).block_until_ready()
        rtts.append(time.perf_counter() - t0)
    rtt = min(rtts)

    from stellar_tpu.ops.ed25519_pallas import verify_kernel_pallas

    def leg(signed):
        ok = verify_kernel_pallas(a_b, r_b, s_b, h_b, signed=signed)
        ok.block_until_ready()  # compile
        assert bool(np.asarray(ok).all()), "profile signatures must verify"
        times = []
        for _ in range(5):
            t0 = time.perf_counter()
            verify_kernel_pallas(
                a_b, r_b, s_b, h_b, signed=signed
            ).block_until_ready()
            times.append(time.perf_counter() - t0)
        best = min(times)
        net = best - rtt
        tag = "signed-window" if signed else "unsigned"
        print(
            f"batch {batch} [{tag}]: kernel call best {best * 1e3:.1f} ms "
            f"(rtt {rtt * 1e3:.1f} ms) -> net {net * 1e3:.1f} ms = "
            f"{batch / net:,.0f} verifies/s device-only",
            flush=True,
        )
        return net

    if ab:
        # same-process same-window A/B/A (cross-window absolutes are
        # confounded — PROFILE.md); order off/on/off controls drift
        off1 = leg(False)
        on = leg(True)
        off2 = leg(False)
        gain = 1.0 - on / min(off1, off2)
        print(f"signed-window gain vs best unsigned leg: {gain:+.1%}")
    else:
        leg(None)


def device_hash_ab(
    batch: int, reps: int, out_path: str, expect_tpu: bool
) -> int:
    """Same-window paired device-hash certification (ISSUE r16): the
    three numbers ROADMAP #2's acceptance compares —

      rate_kernel_only       device-resident kernel calls (inputs staged
                             and uploaded once; dispatch RTT netted out)
      rate_e2e_host_hash     BatchVerifier.verify, host SHA-512 C stage
      rate_e2e_device_hash   BatchVerifier.verify, SHA-512 fused on
                             device (Config.DEVICE_HASH path)

    Both end-to-end legs first prove the mixed hostile-lane mask
    bit-exact vs libsodium on their exact compiled bucket.  Commits
    DEVICE_HASH_r16.json; exits 1 when the certification leg (a real
    accelerator, --tpu) misses the floor rate_e2e_device_hash >= 0.9 *
    rate_kernel_only — the CPU leg is the always-runnable differential
    oracle and records the same JSON without gating (its "device" IS the
    host, so the fused sha competes with the C stage core-for-core)."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, REPO)
    import __graft_entry__ as graft
    from stellar_tpu.crypto import SecretKey
    from stellar_tpu.ops.ed25519 import BatchVerifier

    if expect_tpu:
        assert jax.default_backend() == "tpu", (
            f"--tpu leg ran on {jax.default_backend()!r}; a silent CPU "
            "fallback must not be recorded as a chip measurement"
        )
    bv_host = BatchVerifier(max_batch=batch, streams=1, device_hash=False)
    bv_dev = BatchVerifier(max_batch=batch, streams=1, device_hash=True)
    batch = bv_host.max_batch  # granule rounding

    # oracle first: the mixed valid/corrupt-R/corrupt-s/bad-A mask must
    # be bit-exact on BOTH compiled buckets before anything is timed
    t0 = time.perf_counter()
    mixed, want = graft._mixed_lane_items(batch)
    for bv, tag in ((bv_host, "host-hash"), (bv_dev, "device-hash")):
        got = np.asarray(bv.verify(mixed))
        assert (got == want).all(), (
            f"{tag} verdicts diverge from libsodium at lanes "
            f"{np.nonzero(got != want)[0][:8].tolist()}"
        )
    compile_s = time.perf_counter() - t0

    items = []
    for i in range(batch):
        sk = SecretKey.pseudo_random_for_testing(900_000 + i)
        msg = b"device hash ab %08d" % i
        items.append((sk.public_raw, msg, sk.sign(msg)))

    # kernel-only: one staged upload, then repeated device-resident calls
    staged = bv_host._stage_chunk(items, 0, len(items))
    arr = jnp.asarray(staged.packed)
    bv_host._kernel(arr).block_until_ready()
    trivial = jax.jit(lambda x: x[0] + 1)
    trivial(arr).block_until_ready()
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        trivial(arr).block_until_ready()
        rtts.append(time.perf_counter() - t0)
    rtt = min(rtts)
    kt = []
    for _ in range(reps):
        t0 = time.perf_counter()
        ok = bv_host._kernel(arr)
        ok.block_until_ready()
        kt.append(time.perf_counter() - t0)
    assert bool(np.asarray(ok)[: len(items)].all())
    bv_host._pool.release(staged.bufs)
    kernel_only = batch / max(1e-9, min(kt) - rtt)

    def e2e(bv):
        best = 0.0
        for _ in range(reps):
            t0 = time.perf_counter()
            out = bv.verify(items)
            dt = time.perf_counter() - t0
            assert all(out)
            best = max(best, len(items) / dt)
        return best

    e2e_host = e2e(bv_host)
    e2e_dev = e2e(bv_dev)
    floor = 0.9
    ok_gate = e2e_dev >= floor * kernel_only
    result = {
        "round": "r16",
        "harness": "profile_kernel.py --device-hash-ab"
        + (" --tpu" if expect_tpu else ""),
        "jax_backend": jax.default_backend(),
        "kernel_backend": bv_host.backend,
        "batch": batch,
        "reps": reps,
        "mixed_oracle_exact_both_layouts": True,
        "compile_plus_oracle_s": round(compile_s, 1),
        "dispatch_rtt_ms": round(rtt * 1e3, 2),
        "rate_kernel_only": round(kernel_only, 1),
        "rate_e2e_host_hash": round(e2e_host, 1),
        "rate_e2e_device_hash": round(e2e_dev, 1),
        "e2e_device_hash_vs_kernel_only": round(e2e_dev / kernel_only, 3),
        "device_hash_vs_host_hash": round(e2e_dev / max(1e-9, e2e_host), 3),
        "floor": floor,
        "ok": ok_gate,
        "gated": expect_tpu,
        "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(json.dumps(result), flush=True)
    # only the accelerator leg certifies/gates; the CPU oracle leg is
    # informational (on a CPU host the "device" sha shares the silicon
    # the C host stage would have used)
    return 0 if (ok_gate or not expect_tpu) else 1


def mesh_leg(n_devices: int, per_chip: int, reps: int, expect_tpu: bool) -> int:
    """One curve point, run in a child whose platform/device count the
    parent pinned.  Proves the mixed-lane oracle mask (incl. a remainder
    batch) bit-exact vs libsodium on this exact compiled bucket FIRST,
    then times uniform valid batches end-to-end through
    ``BatchVerifier.verify`` (host gate + staging + sharded dispatch +
    drain) and prints one ``MESH_LEG {json}`` line."""
    import jax

    sys.path.insert(0, REPO)
    import __graft_entry__ as graft
    from stellar_tpu.crypto import SecretKey
    from stellar_tpu.ops.ed25519 import BatchVerifier
    from stellar_tpu.parallel.mesh import make_mesh

    if expect_tpu:
        assert jax.default_backend() == "tpu", (
            f"--tpu leg ran on {jax.default_backend()!r}; a silent CPU "
            "fallback must not be recorded as a chip measurement"
        )
    devs = jax.local_devices()
    if len(devs) < n_devices:
        print(
            "MESH_LEG "
            + json.dumps(
                {
                    "n_devices": n_devices,
                    "skipped": f"only {len(devs)} addressable device(s)",
                }
            ),
            flush=True,
        )
        return 0
    host_cores = os.cpu_count() or 1
    on_cpu = jax.default_backend() == "cpu"
    # effective chips: on the CPU oracle, virtual devices beyond the
    # host's cores time-slice the same silicon — normalizing per VIRTUAL
    # device would measure the host's core budget, not the dispatch path.
    # Real accelerators are real chips.
    eff = min(n_devices, host_cores) if on_cpu else n_devices
    batch = per_chip * eff
    if n_devices > 1:
        bv = BatchVerifier(
            max_batch=batch,
            mesh=make_mesh(devs[:n_devices]),
            min_device_batch=n_devices,
        )
    else:
        # the 1-chip point is the PRODUCTION single-queue path — the
        # baseline sharded dispatch must retain
        bv = BatchVerifier(max_batch=batch)
    batch = bv.max_batch  # granule rounding (whole tiles per shard)
    t0 = time.perf_counter()
    mixed, want = graft._mixed_lane_items(batch)
    got = np.asarray(bv.verify(mixed))
    assert (got == want).all(), (
        f"sharded verdicts diverge from libsodium at lanes "
        f"{np.nonzero(got != want)[0][:8].tolist()}"
    )
    rem = batch - max(1, n_devices - 1)  # live lanes % n_devices != 0
    got_rem = np.asarray(bv.verify(mixed[:rem]))
    assert (got_rem == want[:rem]).all(), "remainder chunk diverges"
    compile_s = time.perf_counter() - t0
    items = []
    for i in range(batch):
        sk = SecretKey.pseudo_random_for_testing(500_000 + i)
        msg = b"mesh curve %08d" % i
        items.append((sk.public_raw, msg, sk.sign(msg)))
    out = bv.verify(items)  # warm pass (bucket compiled above)
    assert all(out), "curve signatures must all verify"
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = bv.verify(items)
        times.append(time.perf_counter() - t0)
        assert all(out)
    best = min(times)
    leg = {
        "n_devices": n_devices,
        "effective_chips": eff,
        "host_cores": host_cores,
        "jax_backend": jax.default_backend(),
        "kernel_backend": bv.backend,
        "sharded": bv.mesh is not None,
        "batch": batch,
        "device_calls": bv.n_device_calls,
        "reps_s": [round(t, 4) for t in times],
        "best_s": round(best, 4),
        "verifies_per_sec": round(batch / best, 1),
        "verifies_per_sec_per_chip": round(batch / best / eff, 1),
        "mixed_oracle_exact": True,
        "compile_plus_oracle_s": round(compile_s, 1),
    }
    print("MESH_LEG " + json.dumps(leg), flush=True)
    return 0


def mesh_curve(
    dev_counts, per_chip, reps, tpu, out_path, leg_timeout=1500.0
) -> int:
    """Run one child per device count and commit the scaling curve.

    Every leg's captured tail is run through filter_xla_noise and capped:
    the committed MULTICHIP artifacts carry verdict lines, never the
    kilobytes of XLA AOT feature spam MULTICHIP_r05.json shipped with."""
    sys.path.insert(0, REPO)
    from __graft_entry__ import filter_xla_noise

    here = os.path.abspath(__file__)
    legs, failures = [], []
    for n in dev_counts:
        env = dict(os.environ)
        if not tpu:
            env["JAX_PLATFORMS"] = "cpu"
            flags = [
                f
                for f in env.get("XLA_FLAGS", "").split()
                if "xla_force_host_platform_device_count" not in f
            ]
            flags.append(f"--xla_force_host_platform_device_count={n}")
            env["XLA_FLAGS"] = " ".join(flags)
        argv = [
            sys.executable, "-u", here,
            "--mesh-leg", str(n),
            "--per-chip", str(per_chip),
            "--reps", str(reps),
        ]
        if tpu:
            argv.append("--expect-tpu")
        print(f"# mesh-curve: leg n_devices={n} starting", flush=True)
        try:
            proc = subprocess.run(
                argv, env=env, cwd=REPO, capture_output=True, text=True,
                timeout=leg_timeout,
            )
        except subprocess.TimeoutExpired:
            failures.append(
                {"n_devices": n, "error": f"timed out after {leg_timeout:.0f}s"}
            )
            continue
        leg = None
        for line in proc.stdout.splitlines():
            if line.startswith("MESH_LEG "):
                leg = json.loads(line[len("MESH_LEG "):])
        if proc.returncode != 0 or leg is None:
            failures.append(
                {
                    "n_devices": n,
                    "rc": proc.returncode,
                    "tail": filter_xla_noise(
                        proc.stdout + "\n" + proc.stderr, cap=800
                    ).strip(),
                }
            )
            continue
        if tail := filter_xla_noise(proc.stderr, cap=300).strip():
            leg["tail"] = tail
        legs.append(leg)
        print(f"#   leg done: {json.dumps(leg)}", flush=True)
    measured = [l for l in legs if "verifies_per_sec_per_chip" in l]
    skipped = [l for l in legs if "skipped" in l]
    curve = {
        str(l["n_devices"]): l["verifies_per_sec_per_chip"] for l in measured
    }
    retention = None
    if len(measured) > 1:
        base = min(measured, key=lambda l: l["n_devices"])
        top = max(measured, key=lambda l: l["n_devices"])
        retention = round(
            top["verifies_per_sec_per_chip"]
            / base["verifies_per_sec_per_chip"],
            3,
        )
    # a certification needs the whole curve: a skipped leg (undersized
    # host) or a single measured point must NOT exit 0 with "ok": true —
    # a caller would otherwise green-light a 1->8 scaling claim nobody
    # measured
    ok = (
        len(measured) > 1
        and not failures
        and not skipped
        and retention is not None
        and retention >= 0.7
    )
    result = {
        "round": "r13",
        "harness": "profile_kernel.py --mesh-curve" + (" --tpu" if tpu else ""),
        "oracle": (
            "real-tpu"
            if tpu
            else "cpu-mesh (JAX_PLATFORMS=cpu + "
            "--xla_force_host_platform_device_count=N child per leg)"
        ),
        "per_chip_batch": per_chip,
        "reps_per_leg": reps,
        "host_cores": os.cpu_count() or 1,
        "methodology": (
            "weak scaling: each leg verifies per_chip_batch x "
            "effective_chips items end-to-end through BatchVerifier.verify "
            "(host strict gate + SHA-512 staging + per-shard upload + "
            "sharded dispatch + drain all-gather), best-of-reps.  "
            "effective_chips = min(n_devices, host_cores) on the CPU "
            "oracle: virtual devices past the core count time-slice the "
            "same silicon, so per-chip retention there isolates "
            "sharded-DISPATCH overhead vs the single-queue path; real "
            "per-chip scaling is what the --tpu leg certifies.  Every leg "
            "first proves the mixed valid/corrupt-R/corrupt-s/bad-A lane "
            "mask (plus a remainder batch, live lanes % n_devices != 0) "
            "bit-exact vs libsodium on the same compiled bucket."
        ),
        "verifies_per_sec_per_chip": curve,
        "per_chip_retention_at_max_devices": retention,
        "retention_floor": 0.7,
        "legs": legs,
        "failures": failures,
        "skipped_legs": [l["n_devices"] for l in skipped],
        "ok": ok,
        "generated_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
    print(
        json.dumps(
            {
                "mesh_curve_per_chip": curve,
                "retention": retention,
                "ok": ok,
                "out": out_path,
            }
        ),
        flush=True,
    )
    return 0 if ok else 1


def _flag_val(argv, name, default):
    if name in argv:
        i = argv.index(name)
        if i + 1 >= len(argv):
            sys.exit(f"profile_kernel: {name} needs a value")
        return argv[i + 1]
    return default


if __name__ == "__main__":
    argv = sys.argv[1:]
    if "--device-hash-ab" in argv:
        tpu = "--tpu" in argv
        if not tpu:
            # the CPU oracle leg must not touch the chip
            os.environ.setdefault("JAX_PLATFORMS", "cpu")
        out = _flag_val(argv, "--out", None) or os.path.join(
            REPO, "DEVICE_HASH_TPU_r16.json" if tpu else "DEVICE_HASH_r16.json"
        )
        sys.exit(
            device_hash_ab(
                int(_flag_val(argv, "--batch", "8192")),
                int(_flag_val(argv, "--reps", "3")),
                out,
                expect_tpu=tpu,
            )
        )
    if "--mesh-leg" in argv:
        sys.exit(
            mesh_leg(
                int(_flag_val(argv, "--mesh-leg", "1")),
                int(_flag_val(argv, "--per-chip", "2048")),
                int(_flag_val(argv, "--reps", "3")),
                expect_tpu="--expect-tpu" in argv,
            )
        )
    if "--mesh-curve" in argv:
        tpu = "--tpu" in argv
        out = _flag_val(argv, "--out", None) or os.path.join(
            REPO, "MULTICHIP_TPU_r13.json" if tpu else "MULTICHIP_r13.json"
        )
        sys.exit(
            mesh_curve(
                [
                    int(c)
                    for c in _flag_val(argv, "--devices", "1,2,4,8").split(",")
                ],
                int(_flag_val(argv, "--per-chip", "2048")),
                int(_flag_val(argv, "--reps", "3")),
                tpu,
                out,
                leg_timeout=float(_flag_val(argv, "--leg-timeout", "1500")),
            )
        )
    args = [a for a in argv if a != "--ab"]
    main(
        int(args[0]) if args else 32768,
        ab="--ab" in argv,
    )
