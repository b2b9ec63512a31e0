"""MXU engagement experiment (VERDICT r3 item 4).

The ed25519 kernels run 13-bit-limb arithmetic on int32 VPU lanes while the
MXU (the chip's matmul systolic array, ~2 orders more int8/bf16 FLOPs) sits
idle. README round 3 hypothesized int8 packing of limb products could move
the multiplication work there. This script MEASURES the two candidate
mappings instead of hand-waving:

A. Field-mul limb convolution as a matmul.
   c[b, k] = sum_{i+j=k} a[b, i] * b[b, j] is per-item work with NO shared
   operand; the only matmul-shaped factorization is
       outer[b, i*j] = a[b, i] * b[b, j]    (still B*400 VPU multiplies)
       c = outer @ T                        (T[i*20+j, k] = [i+j == k])
   i.e. the MXU can only take over the REDUCTION (which schoolbook gets for
   free inside its multiply-accumulate), at the cost of materializing the
   [B, 400] outer product. Measured head-to-head below.

B. The DAG reach walk's link propagation as an MXU matmul.
   reach_mask's inner step is frontier' = links^T @ frontier over [N, N]
   uint8 adjacency — a real matmul with contraction N. At bench committee
   sizes (N <= 50) it underfills the 128x128 systolic tile; at N = 128
   walks batched B-wide it tiles exactly. Measured int32-VPU vs
   bf16-MXU-shaped.

Prints one JSON line per measurement. Two-point-differenced on-device
iteration chains cancel the flat dispatch latency (bench.py's method).
"""

from __future__ import annotations

import json
import time

def _enable_cache() -> None:
    from narwhal_tpu.tpu import enable_compilation_cache

    enable_compilation_cache()


def _chain_rate(make_fn, args, per_iter, spreads=(4096, 16384)):
    """items/s via two-point differencing of an on-device iteration chain.
    Uses MIN-of-5 (the latency lower bound is the robust statistic through
    a drifting link) and accepts the first spread whose delta clearly
    clears the small chain's time."""
    import numpy as np

    def timed(fn, iters=5):
        ts = []
        np.asarray(fn(*args))  # warm/compile
        for _ in range(iters):
            t0 = time.perf_counter()
            np.asarray(fn(*args))
            ts.append(time.perf_counter() - t0)
        return min(ts)

    small = timed(make_fn(2))
    for spread in spreads:
        big = timed(make_fn(2 + spread))
        delta = big - small
        if delta > max(0.5 * small, 0.05):
            return spread * per_iter / delta
    return None


def experiment_a(batch: int = 8192) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from narwhal_tpu.tpu import ed25519 as K

    NL = K.NLIMB
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(0, 1 << 13, (NL, batch), dtype=np.int32))
    b = jnp.asarray(rng.integers(0, 1 << 13, (NL, batch), dtype=np.int32))

    def make_vpu(reps):
        @jax.jit
        def f(a, b):
            def body(i, acc):
                c = K.fe_mul(a + (i & 1), b)
                return acc + c[0]

            return lax.fori_loop(0, reps, body, jnp.zeros((batch,), jnp.int32))

        return f

    # MXU-shaped: [B, NL*NL] outer @ [NL*NL, 2NL-1] index-sum matrix.
    T = np.zeros((NL * NL, 2 * NL - 1), np.int8)
    for i in range(NL):
        for j in range(NL):
            T[i * NL + j, i + j] = 1
    Tj = jnp.asarray(T)

    def make_mxu(reps):
        @jax.jit
        def f(a, b):
            def body(i, acc):
                at = (a + (i & 1)).T  # [B, NL]
                bt = b.T
                outer = (at[:, :, None] * bt[:, None, :]).reshape(batch, NL * NL)
                c = lax.dot(
                    outer.astype(jnp.bfloat16),
                    Tj.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )  # [B, 2NL-1] — the reduction on the MXU
                return acc + c[:, 0].astype(jnp.int32)

            return lax.fori_loop(0, reps, body, jnp.zeros((batch,), jnp.int32))

        return f

    out = []
    for name, mk in (("vpu-schoolbook", make_vpu), ("mxu-outer-matmul", make_mxu)):
        rate = _chain_rate(mk, (a, b), batch)
        out.append(
            {
                "metric": f"fe_mul_per_s[{name}]",
                "value": round(rate, 1) if rate else None,
                "unit": "field-muls/s",
                "batch": batch,
                "note": "bf16 matmul path is NOT exact for 13-bit limb "
                "products (>=2^26 exceeds bf16's 8-bit mantissa); measured "
                "as an upper bound on the MXU formulation's speed only",
            }
        )
    return out


def experiment_b(n: int = 128, walks: int = 256, rounds: int = 32) -> list[dict]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    rng = np.random.default_rng(1)
    links = (rng.random((rounds, n, n)) < 0.6).astype(np.uint8)
    frontier0 = (rng.random((walks, n)) < 0.5).astype(np.uint8)
    links_j = jnp.asarray(links)
    f0 = jnp.asarray(frontier0)

    def make_int32(reps):
        @jax.jit
        def f(links, f0):
            def body(i, acc):
                def step(fr, w):
                    nxt = (
                        fr.astype(jnp.int32) @ links[w].astype(jnp.int32) > 0
                    ).astype(jnp.int32)
                    return nxt, ()

                fr, _ = lax.scan(step, f0.astype(jnp.int32) + (i & 1), jnp.arange(rounds))
                return acc + jnp.sum(fr)

            return lax.fori_loop(0, reps, body, jnp.int32(0))

        return f

    def make_bf16(reps):
        @jax.jit
        def f(links, f0):
            def body(i, acc):
                def step(fr, w):
                    nxt = (
                        lax.dot(
                            fr.astype(jnp.bfloat16),
                            links[w].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32,
                        )
                        > 0
                    ).astype(jnp.bfloat16)
                    return nxt, ()

                fr, _ = lax.scan(
                    step, f0.astype(jnp.bfloat16) + (i & 1), jnp.arange(rounds)
                )
                return acc + jnp.sum(fr.astype(jnp.int32))

            return lax.fori_loop(0, reps, body, jnp.int32(0))

        return f

    per_iter = walks * rounds  # frontier-propagation steps per chain iter
    out = []
    for name, mk in (("int32-vpu", make_int32), ("bf16-mxu", make_bf16)):
        rate = _chain_rate(mk, (links_j, f0), per_iter)
        out.append(
            {
                "metric": f"reach_step_per_s[{name}]",
                "value": round(rate, 1) if rate else None,
                "unit": "frontier-steps/s",
                "committee": n,
                "walks": walks,
                "rounds": rounds,
            }
        )
    return out


def main() -> None:
    _enable_cache()
    for rec in experiment_a() + experiment_b():
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
