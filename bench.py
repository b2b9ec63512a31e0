"""Headline benchmark: ed25519 signature-verification throughput per chip.

The north-star metric (BASELINE.json: ">=4x Certificate verify throughput;
sig-verify/s/chip"): the reference's per-node throughput ceiling is set by
certificate signature verification (/root/reference/types/src/primary.rs:
487-537 via ed25519-dalek/BLS). We measure verified signatures per second:

  baseline: the host library loop (OpenSSL via `cryptography`, the exact
            code the CPU fallback runs) on this machine's CPU,
  value:    the TPU batch kernel (narwhal_tpu/tpu/ed25519.py) on the one
            real chip, end-to-end including host packing + transfers.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
"""

from __future__ import annotations

import json
import os
import time

BATCH = 32768
ROUNDS = 3


def main() -> None:
    # Persist compiled kernels across runs (tpu/__init__.py: the
    # directory JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache).
    import jax  # noqa: F401

    from narwhal_tpu.tpu import enable_compilation_cache

    enable_compilation_cache()

    from narwhal_tpu.crypto import KeyPair, _host_batch_verify
    from narwhal_tpu.tpu.verifier import TpuVerifier

    keys = [KeyPair.generate() for _ in range(32)]
    items = []
    for i in range(BATCH):
        kp = keys[i % len(keys)]
        msg = b"bench" + i.to_bytes(8, "big") * 4  # digest-sized message
        items.append((kp.public, msg, kp.sign(msg)))

    # Host baseline (single-threaded OpenSSL loop, like the fallback path).
    t0 = time.perf_counter()
    host_ok = _host_batch_verify(items)
    host_dt = time.perf_counter() - t0
    assert all(host_ok)
    host_rate = BATCH / host_dt

    verifier = TpuVerifier(max_bucket=BATCH)
    out = verifier(items)  # warmup: compile + first dispatch
    assert out == host_ok, "kernel disagrees with host library"

    # Pipelined steady state: submits (host packing + async dispatch) run on
    # a worker thread while the main thread collects — the collect's device
    # readback wait releases the GIL, so packing of batch N+1 overlaps both
    # the readback of batch N and the device compute of the queued batches.
    # This is how the node's AsyncVerifierPool drives the chip under load.
    from concurrent.futures import ThreadPoolExecutor

    # A single window can under- or over-state the rate (the host shares
    # its cores with everything else on the machine). Measure
    # several sustained windows and report the MEDIAN window throughput,
    # with the observed spread alongside so the number's stability is part
    # of the artifact (VERDICT r3: a one-window headline is not
    # reproducible).
    depth = 3
    window = 4  # batches per measurement window
    windows = 7  # odd: rates[len//2] is the true median window
    with ThreadPoolExecutor(max_workers=1) as pool:
        futures = [pool.submit(verifier.submit, items) for _ in range(depth)]
        rates = []
        for _ in range(windows):
            t0 = time.perf_counter()
            for _ in range(window):
                out = verifier.collect(futures.pop(0).result())
                assert all(out)
                futures.append(pool.submit(verifier.submit, items))
            rates.append(window * BATCH / (time.perf_counter() - t0))
        for f in futures:
            verifier.collect(f.result())
    rates.sort()
    tpu_rate = rates[len(rates) // 2]
    rate_spread = (rates[0], rates[-1])

    # Device-only rate via an on-device iteration chain (two-point
    # differencing cancels the flat link latency): the chip's stable
    # capability, independent of the host link's minute-to-minute bandwidth
    # drift that the pipelined end-to-end number is exposed to.
    import jax.numpy as jnp
    from jax import lax

    from narwhal_tpu.tpu import ed25519 as kern

    import numpy as np

    rng = np.random.default_rng(0)
    # Match the production e2e bucket: the msm doubling chain is shared
    # across the whole bucket, so per-item device throughput IMPROVES with
    # bucket size (8192 understated the 32k-bucket rate by ~2x).
    dev_b = BATCH
    a_y = jnp.asarray(rng.integers(0, 1 << 13, (dev_b, 20), dtype=np.int32))
    sign = jnp.zeros((dev_b,), jnp.int32)
    dig = jnp.asarray(rng.integers(0, 16, (dev_b, 64), dtype=np.int32))

    def repeat_kernel(reps):
        @jax.jit
        def f(a_y, sign, dig):
            def body(i, acc):
                # Perturb per-iteration but stay in the 4-bit digit domain
                # the kernel's select tree assumes.
                oks, okc = kern.verify_batch_kernel(
                    a_y, sign, a_y, sign, (dig + (i & 1)) & 15, dig
                )
                return acc + jnp.sum(oks.astype(jnp.int32)) + jnp.sum(
                    okc.astype(jnp.int32)
                )
            return lax.fori_loop(0, reps, body, jnp.int32(0))
        return f

    def timed(fn, *args, iters=3):
        """(median, max-min noise) over `iters` runs after a warmup."""
        ts = []
        int(fn(*args))  # warm/compile
        for _ in range(iters):
            t0 = time.perf_counter()
            int(fn(*args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2], ts[-1] - ts[0]

    def chain_rate(make_fn, per_iter, args=None, spreads=(10, 30)):
        """Two-point differenced on-device iteration chain -> items/s."""
        args = (a_y, sign, dig) if args is None else args
        small_fn = make_fn(2)
        t_small, noise_small = timed(small_fn, *args)
        for spread in spreads:  # widen if link noise swamps the delta
            t_big, noise_big = timed(make_fn(2 + spread), *args)
            delta = t_big - t_small
            # Sanity: the delta must stand clear of the observed timing
            # noise (no assumption about absolute kernel speed).
            if delta > 4 * max(noise_small, noise_big, 1e-3):
                return spread * per_iter / delta
        return None

    item_rate = chain_rate(repeat_kernel, dev_b)

    # The production batch path: one random-linear-combination accumulate
    # per batch (msm_accumulate_kernel) — the shared doubling chain's
    # amortization is the round-3 throughput multiple. The per-batch host
    # Horner epilogue (~420 point ops on the [4, 20, 64] readback, native
    # where the scalar library loads, as the served path runs it)
    # is timed separately: in the pipelined flow it overlaps the next
    # batch's device compute, so steady state is bounded by max(device,
    # epilogue), reported below as the effective rate.
    # The kernel takes the bucket's raw rows (A | R | ak | z; any bytes are
    # a row: a y that decompresses to no point only clears the valid flag).
    rows = jnp.asarray(rng.integers(0, 256, (dev_b, kern.ROW_BYTES), dtype=np.uint8))

    def repeat_msm(reps):
        @jax.jit
        def f(rows):
            def body(i, acc):
                # Perturb the scalars' low nibbles per iteration.
                out = kern.msm_accumulate_kernel(rows.at[:, 64].add(i.astype(jnp.uint8)))
                return acc + out[0] + out[-1]
            return lax.fori_loop(0, reps, body, jnp.int32(0))
        return f

    msm_accum_rate = chain_rate(repeat_msm, dev_b, args=(rows,))

    from narwhal_tpu.tpu.verifier import _scalar_lib, msm_epilogue_check

    va_host, vr_host, _ = kern.split_msm_result(
        np.asarray(kern.msm_accumulate_kernel(np.asarray(rows)))
    )
    t0 = time.perf_counter()
    for _ in range(5):
        msm_epilogue_check(va_host, vr_host, 12345, kern, _scalar_lib())
    epi_dt = (time.perf_counter() - t0) / 5

    # Roofline accounting (VERDICT r4 item 2): measure the raw VPU fe_mul
    # rate at the kernel's own lane width, derive the analytic fe_mul-
    # equivalent cost per signature, and report achieved-vs-roofline so
    # "fast" is falsifiable.
    fe_b = 8192
    fe_a = jnp.asarray(rng.integers(0, 1 << 13, (kern.NLIMB, fe_b), dtype=np.int32))
    fe_bv = jnp.asarray(rng.integers(0, 1 << 13, (kern.NLIMB, fe_b), dtype=np.int32))

    def repeat_fe(reps):
        @jax.jit
        def f(a, b):
            def body(i, acc):
                c = kern.fe_mul(a + (i & 1), b)
                return acc + c[0]
            # Scalar result: timed() forces with int(...), which rejects
            # non-scalar arrays.
            return jnp.sum(lax.fori_loop(0, reps, body, jnp.zeros((fe_b,), jnp.int32)))
        return f

    fe_rate = chain_rate(repeat_fe, fe_b, args=(fe_a, fe_bv), spreads=(4096, 16384))
    muls_per_sig = kern.msm_field_muls_per_signature(dev_b)
    utilization = (
        round(msm_accum_rate * muls_per_sig / fe_rate, 3)
        if (msm_accum_rate and fe_rate)
        else None
    )
    # Noisy-link fallback: if the msm chain timing was inconclusive, the
    # per-item kernel's stable rate is still a valid device-only headline —
    # but label its source so nobody records an item-kernel number as the
    # msm batch rate.
    if msm_accum_rate:
        device_rate = min(msm_accum_rate, dev_b / epi_dt)
        device_source = "msm-batch"
    else:
        device_rate = item_rate
        device_source = "per-item-kernel-fallback"

    print(
        json.dumps(
            {
                "metric": "ed25519_verify_per_s_per_chip",
                "value": round(tpu_rate, 1),
                "unit": "verifies/s",
                "vs_baseline": round(tpu_rate / host_rate, 3),
                "window_min_per_s": round(rate_spread[0], 1),
                "window_max_per_s": round(rate_spread[1], 1),
                "device_only_per_s": round(device_rate, 1) if device_rate else None,
                "device_only_vs_baseline": (
                    round(device_rate / host_rate, 3) if device_rate else None
                ),
                "device_only_per_item_kernel_per_s": (
                    round(item_rate, 1) if item_rate else None
                ),
                "device_only_source": device_source,
                "msm_accumulate_per_s": (
                    round(msm_accum_rate, 1) if msm_accum_rate else None
                ),
                "msm_host_epilogue_ms_per_batch": round(epi_dt * 1000, 2),
                "fe_mul_per_s": round(fe_rate, 1) if fe_rate else None,
                "fe_muls_per_verify": round(muls_per_sig, 1),
                "vpu_utilization_vs_fe_mul_roofline": utilization,
                "host_per_s": round(host_rate, 1),
                "note": "value = median pipelined e2e window (of "
                f"{windows} windows x {window} batches) incl. host packing "
                "(native/scalar_ops.cpp) and host<->device transfers; "
                "window_min/max give the observed spread; device_only = the "
                "production batch path's steady-state rate min(device msm "
                f"accumulate, host Horner epilogue) at batch {BATCH} "
                "(random-linear-combination check); "
                "device_only_per_item_kernel = the per-item Straus kernel "
                "(the fallback path, round 2's headline); "
                "vpu_utilization_vs_fe_mul_roofline = msm accumulate rate x "
                "analytic fe-mul-equivalents per verify "
                "(ed25519.msm_field_muls_per_signature documents the "
                "derivation) / the measured raw fe_mul chain rate",
            }
        )
    )


if __name__ == "__main__":
    import sys

    if "--multichip" in sys.argv:
        # The multi-chip device-plane leg: per-device-count sweep (1/2/4/8
        # virtual devices, each in its own subprocess) ->
        # benchmark/results/multichip_scaling.json with per-(kernel, mesh
        # shape) compile walls. See benchmark/multichip.py.
        from benchmark.multichip import main as multichip_main

        multichip_main([a for a in sys.argv[1:] if a != "--multichip"])
    elif "--fuzz" in sys.argv:
        # The FaultPlan fuzzer: seeded random fault schedules under the
        # simnet safety/liveness oracles, failures shrunk to minimal
        # reproducers, one perf-ledger record per campaign. See
        # narwhal_tpu/simnet/fuzz.py.
        from narwhal_tpu.simnet.fuzz import main as fuzz_main

        raise SystemExit(
            fuzz_main([a for a in sys.argv[1:] if a != "--fuzz"])
        )
    else:
        main()
