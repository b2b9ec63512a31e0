"""Multi-chip device-plane scaling sweep: the bench.py multichip leg.

THIS SWEEP HAS NEVER TOUCHED A CHIP: `_leg_env` forces JAX_PLATFORMS=cpu
into every leg, so every "device" is a forced host device and every
record says `"platform": "cpu"`. It checks sharding correctness and
compile scaling; it measures no accelerator. Re-pointing it at real
devices is ROADMAP S4 (what four real chips showed is recorded there,
from `chip_smoke.py --chips 4`).

`python bench.py --multichip` (or `python -m benchmark.multichip`) runs a
per-device-count sweep over the virtual CPU mesh — each device count in
its OWN subprocess, because --xla_force_host_platform_device_count is
fixed at jax initialization — and writes
`benchmark/results/multichip_scaling.json`:

- per device count: the sharded verify throughput (staged msm pipeline,
  fixed bucket, median of timed steady-state dispatch windows) and the
  per-(kernel, mesh shape) compile walls from the kernel registry;
- for the acceptance device count (8): the full `__graft_entry__`
  dryrun_multichip contract (rc recorded — a compile that outlasts the
  leg's timeout is exactly what this leg guards), run TWICE so the
  warm-process walls show the once-per-container compile;
- a scaling note: every "device" is a virtual CPU device sharing the
  host's cores, so aggregate throughput cannot scale with device count —
  the curve validates compile scaling, sharding correctness and dispatch
  overhead; scaling on real chips is not measured.

The legs share the persistent compilation cache like every other process
(`JAX_COMPILATION_CACHE_DIR` if set, else `<checkout>/.jax_cache`), so
the sweep pays each (kernel, mesh shape) compile once per container.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(REPO, "benchmark", "results", "multichip_scaling.json")
MARK = "MULTICHIP-LEG-RESULT "

BUCKET = 512  # fixed verify bucket: divisible by every swept device count
LEG_TIMEOUT = 1800.0


def _leg_env(n_devices: int) -> dict:
    import re

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # never a chip: see the module docstring
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
    )
    env["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={max(8, n_devices)}"
    ).strip()
    env["NARWHAL_TPU_PREWARM"] = "0"
    return env


def _run_leg(n_devices: int, dryrun: bool) -> dict:
    """One device count in a fresh subprocess; returns its result record
    (rc, walls, verify rate), with rc != 0 surfaced, never swallowed."""
    cmd = [
        sys.executable,
        "-m",
        "benchmark.multichip",
        "--leg",
        str(n_devices),
    ]
    if dryrun:
        cmd.append("--dryrun")
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            cmd,
            cwd=REPO,
            env=_leg_env(n_devices),
            capture_output=True,
            text=True,
            timeout=LEG_TIMEOUT,
        )
        rc = proc.returncode
        out = proc.stdout
        tail = (proc.stdout + proc.stderr)[-1500:]
    except subprocess.TimeoutExpired as e:
        rc, out = 124, (e.stdout or "")
        tail = ((e.stdout or "") + (e.stderr or ""))[-1500:]
    record: dict = {
        "n_devices": n_devices,
        "rc": rc,
        "wall_s": round(time.monotonic() - t0, 1),
        "dryrun_included": dryrun,
    }
    for line in out.splitlines():
        if line.startswith(MARK):
            record.update(json.loads(line[len(MARK):]))
            break
    else:
        record["tail"] = tail
    return record


def leg_main(n_devices: int, dryrun: bool) -> None:
    """Subprocess body: sharded verify rate + compile walls (+ the driver
    dryrun contract when --dryrun). Emits ONE marked JSON line."""
    import numpy as np  # noqa: F401  (jax import ordering)

    import jax

    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.tpu import kernel_registry
    from narwhal_tpu.tpu.verifier import TpuVerifier, data_mesh

    t_start = time.perf_counter()
    result: dict = {
        "platform": jax.devices()[0].platform,
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }

    if dryrun:
        import __graft_entry__

        t0 = time.perf_counter()
        __graft_entry__.dryrun_multichip(n_devices, devices=jax.devices("cpu"))
        result["dryrun_wall_s"] = round(time.perf_counter() - t0, 1)

    kp = KeyPair.generate()
    items = [
        (kp.public, b"mc%d" % i, kp.sign(b"mc%d" % i)) for i in range(BUCKET)
    ]
    # data_mesh(1) at n=1: the curve isolates device-count scaling on ONE
    # code path (the staged mesh pipeline) instead of comparing the
    # monolithic single-chip kernel against the staged one.
    mesh = data_mesh(n_devices)
    verifier = TpuVerifier(
        max_bucket=BUCKET,
        msm_min_bucket=16,
        mode="msm",
        fixed_bucket=True,
        mesh=mesh,
    )
    t0 = time.perf_counter()
    ok = verifier(items)  # first dispatch: trace + compile + run
    compile_wall = time.perf_counter() - t0
    if not all(ok):
        raise SystemExit("sharded verifier rejected a valid batch")

    # Steady state: pipelined submit/collect pairs (depth 2), median of
    # timed windows — the same shape bench.py's e2e loop uses. On virtual
    # CPU devices this is an aggregate over the host's shared cores.
    handles = [verifier.submit(items) for _ in range(2)]
    rates = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(2):
            out = verifier.collect(handles.pop(0))
            if not all(out):
                raise SystemExit("steady-state verify verdicts changed")
            handles.append(verifier.submit(items))
        rates.append(2 * BUCKET / (time.perf_counter() - t0))
    for h in handles:
        verifier.collect(h)
    rates.sort()

    result.update(
        {
            "bucket": BUCKET,
            "verify_per_s": round(rates[len(rates) // 2], 1),
            "verify_per_s_min": round(rates[0], 1),
            "verify_per_s_max": round(rates[-1], 1),
            "first_dispatch_wall_s": round(compile_wall, 1),
            "compile_walls_s": kernel_registry.compile_walls_by_shape(),
            "compile_walls_detail": kernel_registry.compile_walls(),
            "total_wall_s": round(time.perf_counter() - t_start, 1),
        }
    )
    print(MARK + json.dumps(result), flush=True)


def main(argv: list[str] | None = None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if "--leg" in argv:
        i = argv.index("--leg")
        leg_main(int(argv[i + 1]), "--dryrun" in argv)
        return

    legs = []
    for n in (1, 2, 4, 8):
        legs.append(_run_leg(n, dryrun=(n == 8)))
        print(
            f"[multichip] n={n} platform={legs[-1].get('platform')} "
            f"rc={legs[-1]['rc']} "
            f"verify/s={legs[-1].get('verify_per_s')} "
            f"wall={legs[-1]['wall_s']}s",
            flush=True,
        )
    # Warm-cache rerun of the acceptance leg: with the persistent cache
    # populated, the same process-fresh 8-device leg must be dominated by
    # deserialization (a loader crash on a reloaded XLA:CPU entry would
    # surface here as rc != 0).
    warm = _run_leg(8, dryrun=True)
    print(
        f"[multichip] n=8 (warm cache) rc={warm['rc']} wall={warm['wall_s']}s",
        flush=True,
    )

    base = next((l.get("verify_per_s") for l in legs if l["n_devices"] == 1), None)
    curve = {
        str(l["n_devices"]): (
            round(l["verify_per_s"] / base, 2)
            if base and l.get("verify_per_s")
            else None
        )
        for l in legs
    }
    payload = {
        "metric": "multichip_verify_scaling",
        "bucket": BUCKET,
        "legs": legs,
        "warm_cache_leg": warm,
        "scaling_vs_1_device": curve,
        "ok": all(l["rc"] == 0 for l in legs) and warm["rc"] == 0,
        "platforms": sorted({str(l.get("platform")) for l in legs + [warm]}),
        "note": (
            "All device counts are VIRTUAL CPU devices "
            "(--xla_force_host_platform_device_count, JAX_PLATFORMS=cpu "
            "forced into every leg) sharing the host's cores, so the "
            "curve validates compile scaling (per-shape walls recorded "
            "per leg; registry guarantees one compile per (kernel, mesh "
            "shape)), sharding correctness and dispatch overhead, not "
            "silicon scaling. Scaling on real chips: not measured."
        ),
    }
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"[multichip] wrote {RESULTS} ok={payload['ok']}", flush=True)
    sys.path.insert(0, REPO)
    from tools.perf import ledger as perf_ledger

    perf_ledger.append("multichip", payload, argv=argv)
    if not payload["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
