"""In-process committee benchmark: the whole committee as asyncio tasks in
ONE process (the test harness Cluster, reference
test_utils/src/cluster.rs:31-793), with rate-controlled load and
executed-transaction measurement.

Two reasons this exists next to the multi-process LocalBench:

1. Committee scaling on small hosts. A 20-node LocalBench spawns 60+
   Python processes; on a 1-2 core host the measurement is dominated by
   scheduler thrash, not the protocol. One asyncio process loses far less
   to context switching, so larger committees produce meaningful numbers.
2. TPU backends. A chip belongs to one process at a time, so the
   crypto/DAG offload backends can serve a whole committee only when it
   runs in-process — warm-up and committee in the ONE process that
   touches JAX (`chip_smoke.py` follows the same pattern).

    python -m benchmark.inprocess --nodes 20 --rate 1000 --duration 40
    python -m benchmark.inprocess --nodes 20 --crypto-backend tpu ...

Emits one JSON record (tps/latency percentiles/config) on stdout and
optionally appends it to --out.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import statistics
import sys
import time


async def run_bench(args) -> dict:
    from narwhal_tpu.cluster import Cluster
    from narwhal_tpu.messages import SubmitTransactionStreamMsg
    from narwhal_tpu.network import NetworkClient

    from narwhal_tpu.config import Parameters

    if args.crypto_backend == "tpu" and not args.no_precompile:
        # Warm the merged-flush bucket ladder BEFORE the committee boots:
        # an in-protocol first compile (minutes, uncached) would otherwise
        # land inside the measurement window. One-time per machine — the
        # persistent .jax_cache serves later runs in seconds.
        from narwhal_tpu.tpu.verifier import VerifyService

        svc = VerifyService.shared("msm")  # Cluster defaults tpu->cofactored
        t0 = time.time()
        # One shape only: the service runs fixed-bucket, so this single
        # warm covers every flush (and the msm fallback kernel for
        # adversarial input).
        print(
            f"precompiling verify bucket {svc.verifier.max_bucket}...",
            file=sys.stderr,
        )
        svc.verifier.precompile((svc.verifier.max_bucket,))
        print(f"precompile done in {time.time() - t0:.0f}s", file=sys.stderr)

    cluster = Cluster(
        size=args.nodes,
        workers=args.workers,
        parameters=Parameters(
            max_header_delay=args.max_header_delay,
            max_batch_delay=args.max_batch_delay,
            # The whole in-process fleet shares one backend, so a tpu run
            # can uniformly use the cofactored accept set — the msm batch
            # kernel, the mode the precompile above warmed. (An explicit
            # Parameters bypasses Cluster's same-reasoning default.)
            verify_rule=(
                "cofactored" if args.crypto_backend == "tpu" else "strict"
            ),
            cert_format=args.cert_format,
        ),
        crypto_backend=args.crypto_backend,
        dag_backend=args.dag_backend,
        dag_shards=args.dag_shards,
        consensus_protocol=args.consensus_protocol,
    )
    await cluster.start(args.nodes - args.faults)
    await cluster.assert_progress(commit_threshold=2, timeout=args.warmup_timeout)

    alive = args.nodes - args.faults
    executed = [0] * alive
    # Per-node execution-order prefixes (first 9 bytes identify a sample
    # tx): compared up to the shortest node so in-flight tails at cancel
    # time can't fake a divergence, and count-only equality can't hide one.
    orders: list[list[bytes]] = [[] for _ in range(alive)]
    latencies: list[float] = []
    sent_at: dict[int, float] = {}

    async def drain(i: int) -> None:
        ch = cluster.authorities[i].primary.tx_execution_output
        while True:
            _, tx = await ch.recv()
            executed[i] += 1
            orders[i].append(bytes(tx[:9]))
            # Sample txs carry a sequence id (benchmark_client format:
            # 0x00 + u64 counter) for end-to-end latency.
            if i == 0 and tx[:1] == b"\x00":
                sid = int.from_bytes(tx[1:9], "big")
                t0 = sent_at.pop(sid, None)
                if t0 is not None:
                    latencies.append(time.time() - t0)

    drains = [asyncio.ensure_future(drain(i)) for i in range(alive)]
    client = NetworkClient()
    lanes = [
        cluster.authorities[i].worker_transactions_address(wid)
        for i in range(alive)
        for wid in range(args.workers)
    ]
    share = max(1, args.rate // len(lanes))
    next_sid = 0
    # Admission-control accounting: bursts the worker explicitly refused
    # (RESOURCE_EXHAUSTED) vs transport hiccups. Shed bursts are the
    # intended overload behavior, counted rather than logged per event.
    shed = {"bursts": 0, "txs": 0, "errors": 0}

    async def inject(lane: str) -> None:
        nonlocal next_sid
        end = time.time() + args.duration
        while time.time() < end:
            tick = time.time()
            txs = []
            for _ in range(share):
                next_sid += 1
                sid = next_sid
                sent_at[sid] = time.time()
                txs.append(
                    b"\x00" + sid.to_bytes(8, "big") + b"\x01" * (args.tx_size - 9)
                )
            try:
                await client.request(lane, SubmitTransactionStreamMsg(tuple(txs)))
            except Exception as e:
                if "RESOURCE_EXHAUSTED" in str(e):
                    shed["bursts"] += 1
                    shed["txs"] += len(txs)
                else:  # lane hiccup: drop this tick's share
                    shed["errors"] += 1
                    print(f"inject {lane}: {e}", file=sys.stderr)
                # Either way this tick's samples never entered the system.
                for tx in txs:
                    sent_at.pop(int.from_bytes(tx[1:9], "big"), None)
            await asyncio.sleep(max(0.0, 1.0 - (time.time() - tick)))

    from narwhal_tpu.network.rpc import WireStats

    def primary_sent_by_type(a) -> dict[str, float]:
        m = a.primary.registry.get("wire_bytes_sent_total")
        if m is None:
            return {}
        return {k[0]: c.value for k, c in m._children.items()}

    t_start = time.time()
    rounds_start = {
        a.name: a.metric("consensus_last_committed_round")
        for a in cluster.authorities[:alive]
    }
    wire_start = WireStats.snapshot()
    egress_start = [primary_sent_by_type(a) for a in cluster.authorities[:alive]]
    await asyncio.gather(*(inject(lane) for lane in lanes))
    await asyncio.sleep(args.drain_tail)
    window = time.time() - t_start
    wire_end = WireStats.snapshot()
    # Committed protocol rounds during the window: at committee sizes where
    # this 1-core host cannot push transactions through inside any window
    # (N=50: each round is ~7.5k signed control messages), rounds/s is the
    # meaningful backend-comparison metric.
    rounds_end = {
        a.name: a.metric("consensus_last_committed_round")
        for a in cluster.authorities[:alive]
    }
    committed_rounds = max(
        rounds_end[k] - rounds_start.get(k, 0) for k in rounds_end
    )
    # Per-PRIMARY egress from the per-link wire metrics (the quantity the
    # fanout tree + delta headers attack), by message type.
    egress_end = [primary_sent_by_type(a) for a in cluster.authorities[:alive]]
    egress_delta_by_type: dict[str, float] = {}
    egress_per_node = []
    for before, after in zip(egress_start, egress_end):
        node_total = 0.0
        for msg_type, value in after.items():
            d = value - before.get(msg_type, 0.0)
            node_total += d
            egress_delta_by_type[msg_type] = (
                egress_delta_by_type.get(msg_type, 0.0) + d
            )
        egress_per_node.append(node_total)
    mean_egress = sum(egress_per_node) / max(1, len(egress_per_node))
    for d in drains:
        d.cancel()
    client.close()
    # Embed node 0's scrape (counters/gauges + histogram sums) so the
    # results record is self-contained: any later A/B can recompute stage
    # means and wire rates without rerunning the bench.
    from narwhal_tpu.metrics import scrape_snapshot

    telemetry = {
        "primary-0": scrape_snapshot(cluster.authorities[0].primary.registry),
        "worker-0-0": scrape_snapshot(
            cluster.authorities[0].workers[0].registry
        ),
    }
    await cluster.shutdown()

    tps = executed[0] / window if executed[0] else 0.0
    wire_sent = wire_end["bytes_sent"] - wire_start["bytes_sent"]
    wire_frames = wire_end["frames_sent"] - wire_start["frames_sent"]
    lat_sorted = sorted(latencies)

    def pct(p: float) -> float:
        if not lat_sorted:
            return 0.0
        return lat_sorted[min(len(lat_sorted) - 1, int(p * len(lat_sorted)))]

    return {
        "mode": "in-process",
        "committee_size": args.nodes,
        "workers_per_node": args.workers,
        "faults": args.faults,
        "input_rate": args.rate,
        "tx_size": args.tx_size,
        "duration_s": round(window, 1),
        "consensus_protocol": args.consensus_protocol,
        "crypto_backend": args.crypto_backend,
        "dag_backend": args.dag_backend,
        "dag_shards": args.dag_shards,
        "cert_format": args.cert_format,
        "verify_rule": "cofactored" if args.crypto_backend == "tpu" else "strict",
        "executed_tps": round(tps, 1),
        "executed_total": executed[0],
        "committed_rounds_in_window": round(committed_rounds, 1),
        "committed_rounds_per_s": round(committed_rounds / window, 4),
        # Control-plane wire accounting (bytes-per-round is the quantity
        # the compact certificate form targets at byte-bound committees).
        "wire_bytes_sent_in_window": wire_sent,
        "wire_frames_sent_in_window": wire_frames,
        "wire_bytes_per_round": (
            round(wire_sent / committed_rounds, 1) if committed_rounds else None
        ),
        "wire_frames_per_round": (
            round(wire_frames / committed_rounds, 1) if committed_rounds else None
        ),
        # Per-PRIMARY control-plane egress (mean across nodes) from the
        # wire_bytes_sent_total{msg_type=} metrics — the r9 wire-diet
        # acceptance metric — plus the committee-wide breakdown by type.
        "primary_egress_bytes_per_round": (
            round(mean_egress / committed_rounds, 1) if committed_rounds else None
        ),
        "primary_egress_bytes_by_msg_type": {
            k: round(v, 1) for k, v in sorted(egress_delta_by_type.items())
        },
        "relay_fanout": os.environ.get("NARWHAL_RELAY_FANOUT", "default"),
        "header_wire": os.environ.get("NARWHAL_HEADER_WIRE", "default"),
        "identical_execution_prefix": (
            (lambda L: all(o[:L] == orders[0][:L] for o in orders))(
                min(len(o) for o in orders)
            )
            if orders
            else True
        ),
        "compared_prefix_len": min(len(o) for o in orders) if orders else 0,
        "e2e_latency_p50_ms": round(pct(0.50) * 1000, 1),
        "e2e_latency_p90_ms": round(pct(0.90) * 1000, 1),
        "e2e_latency_p95_ms": round(pct(0.95) * 1000, 1),
        "e2e_latency_p99_ms": round(pct(0.99) * 1000, 1),
        "latency_samples": len(lat_sorted),
        # Admission control: offered vs admitted load. delivered_rate is
        # what actually entered the system after shedding — under deliberate
        # overload the headline is bounded p50 at this rate, not the
        # offered one.
        "shed_bursts": shed["bursts"],
        "shed_txs": shed["txs"],
        "inject_errors": shed["errors"],
        "delivered_rate": round(
            max(0.0, args.rate - shed["txs"] / max(args.duration, 1e-9)), 1
        ),
        "pacing": os.environ.get("NARWHAL_PACING", "1") not in ("0", "false", "off"),
        "ingest_policy": os.environ.get("NARWHAL_INGEST_POLICY", "shed"),
        "trace": os.environ.get("NARWHAL_TRACE", "0"),
        "trace_sample": os.environ.get("NARWHAL_TRACE_SAMPLE", "1.0"),
        "telemetry_scrape": telemetry,
    }


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmark.inprocess")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--rate", type=int, default=1_000)
    ap.add_argument("--tx-size", type=int, default=512)
    ap.add_argument("--duration", type=int, default=30)
    ap.add_argument("--drain-tail", type=float, default=5.0)
    ap.add_argument("--max-header-delay", type=float, default=0.05)
    ap.add_argument("--max-batch-delay", type=float, default=0.05)
    ap.add_argument("--warmup-timeout", type=float, default=120.0,
                    help="boot-to-first-commits window (TPU backends pay "
                    "their first compiles here)")
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--consensus-protocol", choices=("bullshark", "tusk"),
                    default="bullshark")
    ap.add_argument("--crypto-backend", choices=("cpu", "pool", "tpu"),
                    default="cpu")
    ap.add_argument("--dag-backend", choices=("cpu", "tpu"), default="cpu")
    ap.add_argument("--dag-shards", type=int, default=1)
    ap.add_argument("--cert-format", choices=("full", "compact"),
                    default="compact",
                    help="certificate wire form (compact = half-aggregated "
                    "proofs broadcast by reference — the committee default; "
                    "full = the per-signer opt-out)")
    ap.add_argument("--no-precompile", action="store_true",
                    help="skip the tpu verify-bucket warmup before boot")
    ap.add_argument("--out", default=None,
                    help="append the JSON record to this file")
    args = ap.parse_args()

    record = asyncio.run(run_bench(args))
    print(json.dumps(record))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        existing = []
        if os.path.exists(args.out):
            with open(args.out) as f:
                existing = json.load(f)
        existing.append(record)
        with open(args.out, "w") as f:
            json.dump(existing, f, indent=2)
    from tools.perf import ledger as perf_ledger

    perf_ledger.append(
        "inprocess", record,
        scrape=record.get("telemetry_scrape"), argv=sys.argv[1:],
    )


if __name__ == "__main__":
    main()
