"""Commit-walk microbench: device adjacency-tensor kernels vs host order_dag.

The reference's per-commit hot loop is a pointer-chasing DFS
(/root/reference/consensus/src/utils.rs:11-101; criterion bench at
consensus/benches/process_certificates.rs:18-80). Here the same work is the
`TpuBullshark` walk (narwhal_tpu/tpu/dag_kernels.py): reachability as masked
[N, N] matmul scans over the round window, leader support as a stake dot
product. This bench streams a synthetic lossless DAG through both engines,
asserts identical commit sequences, and reports certificates processed per
second for each.

Usage: python -m benchmark.dag_walk_bench [--size 32] [--rounds 64] [--gc 50]
Prints one JSON line per engine.
"""

from __future__ import annotations

import argparse
import json
import time


def run(size: int, rounds: int, gc: int) -> None:
    import jax

    from narwhal_tpu.consensus import Bullshark, ConsensusState
    from narwhal_tpu.fixtures import CommitteeFixture, make_optimal_certificates
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.tpu.dag_kernels import TpuBullshark
    from narwhal_tpu.types import Certificate

    f = CommitteeFixture(size=size)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_optimal_certificates(f.committee, 1, rounds, genesis)
    certs = list(certs)

    def stream(engine):
        state = ConsensusState(Certificate.genesis(f.committee))
        seq, index = [], 0
        t0 = time.perf_counter()
        for c in certs:
            out = engine.process_certificate(state, index, c)
            index += len(out)
            seq.extend(o.certificate.digest for o in out)
        return time.perf_counter() - t0, seq

    host = Bullshark(f.committee, NodeStorage(None).consensus_store, gc)
    dev = TpuBullshark(f.committee, NodeStorage(None).consensus_store, gc, prewarm=False)

    # Warmup compiles the device kernels for this (W, N) shape.
    warm = TpuBullshark(f.committee, NodeStorage(None).consensus_store, gc, prewarm=False)
    stream(warm)

    host_dt, host_seq = stream(host)
    dev_dt, dev_seq = stream(dev)
    assert host_seq == dev_seq, "device commit sequence diverged from host"

    # Separate the device COMPUTE from the device->host readback: report
    # both the end-to-end stream rate and the per-commit-event walk times
    # that the hardware actually determines.
    import numpy as np

    from narwhal_tpu.tpu import dag_kernels as dk

    events = {"n": 0, "compute": 0.0, "readback": 0.0}
    orig = dk.chain_commit

    def timed(*a):
        t0 = time.perf_counter()
        out = orig(*a)
        jax.block_until_ready(out)
        t1 = time.perf_counter()
        np.asarray(out)
        t2 = time.perf_counter()
        events["n"] += 1
        events["compute"] += t1 - t0
        events["readback"] += t2 - t1
        return out

    dk.chain_commit = timed
    try:
        stream(TpuBullshark(f.committee, NodeStorage(None).consensus_store, gc, prewarm=False))
    finally:
        dk.chain_commit = orig

    # Pure device time of one chain_commit at this (W, N) shape, measured
    # with an on-device iteration chain + two-point differencing (which
    # cancels the flat dispatch + readback latency).
    import jax.numpy as jnp
    from jax import lax

    win = dev.win
    parent_j = jnp.asarray(win.parent)
    present_j = jnp.asarray(win.present)
    lc = jnp.zeros((win.N,), jnp.int32)
    offs_j = jnp.zeros((1,), jnp.int32).at[0].set(win.W - 2)
    onehots_j = jnp.zeros((1, win.N), jnp.uint8).at[0, 0].set(1)

    def chained(reps):
        @jax.jit
        def f(parent, present, lc, offs, onehots):
            def body(i, acc):
                masks = dk.chain_commit(
                    parent, present, jnp.int32(gc), lc, jnp.int32(0), offs,
                    jnp.roll(onehots, i, axis=1),
                )
                return acc + jnp.sum(masks.astype(jnp.int32))
            return lax.fori_loop(0, reps, body, jnp.int32(0))
        return f

    def timed(fn, iters=3):
        ts = []
        int(fn(parent_j, present_j, lc, offs_j, onehots_j))
        for _ in range(iters):
            t0 = time.perf_counter()
            int(fn(parent_j, present_j, lc, offs_j, onehots_j))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2]

    # The walk is microseconds on device; thousands of chained reps are
    # needed for the delta to clear the host clock's timing noise.
    t_small = timed(chained(2))
    t_big = timed(chained(4002))
    device_chain_ms = max(t_big - t_small, 0.0) / 4000 * 1000

    # Host per-event walk time for comparison: total host stream time is
    # dominated by the flatten (state bookkeeping is shared by both engines).
    n = len(certs)
    n_events = max(events["n"], 1)
    rows = [
        {
            "metric": "commit_walk_certs_per_s[host_order_dag]",
            "value": round(n / host_dt, 1),
            "unit": "certs/s",
        },
        {
            "metric": "commit_walk_certs_per_s[tpu_dag_kernels_e2e]",
            "value": round(n / dev_dt, 1),
            "unit": "certs/s",
        },
        {
            "metric": "commit_event_ms[host]",
            "value": round(host_dt / n_events * 1000, 2),
            "unit": "ms/event",
        },
        {
            "metric": "commit_event_ms[tpu_compute]",
            "value": round(events["compute"] / n_events * 1000, 2),
            "unit": "ms/event",
        },
        {
            "metric": "commit_event_ms[tpu_readback]",
            "value": round(events["readback"] / n_events * 1000, 2),
            "unit": "ms/event",
        },
        {
            "metric": "commit_event_ms[tpu_device_chain]",
            "value": round(device_chain_ms, 3),
            "unit": "ms/event",
        },
    ]
    for row in rows:
        row.update(
            committee=size,
            rounds=rounds,
            committed=len(host_seq),
            events=events["n"],
            backend=jax.default_backend(),
        )
        print(json.dumps(row))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=32)
    ap.add_argument("--rounds", type=int, default=64)
    ap.add_argument("--gc", type=int, default=50)
    a = ap.parse_args()
    run(a.size, a.rounds, a.gc)
