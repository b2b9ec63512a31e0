"""Saturation sweep: run the local bench across increasing input rates,
find the throughput knee, and emit a machine-readable result set.

The reference finds its knee by hand-editing fabfile parameters and re-running
`fab local`; this automates it:

    python -m benchmark.sweep --rates 5000 15000 30000 40000 --duration 20
    python -m benchmark.sweep --auto --duration 20      # geometric auto-sweep

Writes `.bench/sweep.json` (one record per run, LogParser.to_dict shape) and
prints a markdown table. Plot with `python -m benchmark.plot .bench/sweep.json`.
"""

from __future__ import annotations

import argparse
import json
import os

from narwhal_tpu.config import Parameters

from .local import BenchParameters, LocalBench
from .logs import ParseError


def run_once(rate: int, args) -> dict:
    bench = LocalBench(
        BenchParameters(
            nodes=args.nodes,
            workers=args.workers,
            rate=rate,
            tx_size=args.tx_size,
            duration=args.duration,
            faults=args.faults,
            consensus_protocol=args.consensus_protocol,
            crypto_backend=args.crypto_backend,
            dag_backend=args.dag_backend,
            dag_shards=args.dag_shards,
        ),
        node_parameters=Parameters(
            max_header_delay=args.max_header_delay,
            max_batch_delay=args.max_batch_delay,
            cert_format=args.cert_format,
            verify_rule=args.verify_rule,
        ),
    )
    parser = bench.run()
    record = parser.to_dict()
    record["consensus_protocol"] = args.consensus_protocol
    record["crypto_backend"] = args.crypto_backend
    record["dag_backend"] = args.dag_backend
    record["dag_shards"] = args.dag_shards
    # Self-describing A/B rows: W, the crash-fault count, and the
    # certificate wire form / accept rule are part of the experiment's
    # identity (the reference bench records `faults` too; cert_format moves
    # the wire floor the same way W moves the payload plane).
    record["workers_per_node"] = args.workers
    record["faults"] = args.faults
    record["cert_format"] = args.cert_format
    record["verify_rule"] = args.verify_rule
    # Socket-wall axis: worst per-process open-fd count across the fleet,
    # sampled at steady state (pooled transport target: O(N) per node).
    record["peak_fds_per_node"] = max(
        bench.child_fd_counts.values(), default=None
    )
    # Node 0's Telemetry.Scrape (gRPC, taken while the fleet was alive):
    # counters/gauges + histogram sums embedded so each sweep row is
    # self-contained for later A/Bs; other nodes' scrapes stay out to keep
    # rows bounded.
    record["telemetry_scrape"] = {
        "primary-0": bench.telemetry_scrapes.get("primary-0", {})
    }
    print(
        f"  rate {rate:>8,}: TPS {record['consensus_tps']:>10,.0f}  "
        f"lat {record['consensus_latency_ms']:>8,.0f} ms  "
        f"e2e {record['end_to_end_latency_ms']:>8,.0f} ms"
    )
    return record


def run_fault_rows(args) -> list[dict]:
    """The faults>0 axis, exercised: each row replays one seeded FaultPlan
    (narwhal_tpu.simnet.fuzz.generate_plan) on the simnet fabric — virtual
    clock, in-memory network — under the safety/liveness oracles. The seed
    IS the experiment's identity: the same seed replays the same schedule
    bit-identically, so a row here is reproducible where a wall-clock crash
    bench is not."""
    from narwhal_tpu.simnet import fuzz

    rows: list[dict] = []
    for seed in args.fault_seeds:
        plan = fuzz.generate_plan(
            seed, nodes=args.nodes, duration=args.fault_duration
        )
        ok, violation, result = fuzz.check_plan(
            plan,
            nodes=args.nodes,
            duration=args.fault_duration,
            load_rate=args.fault_load_rate,
            workers=args.workers,
        )
        rows.append(
            {
                "fault_plan_seed": seed,
                "plan": fuzz.describe_plan(plan),
                "faults": len(plan.events),
                "oracles_ok": ok,
                "violation": violation,
                "nodes": args.nodes,
                "duration_virtual_s": args.fault_duration,
                "load_rate": args.fault_load_rate,
                "rounds": list(result.rounds) if result else None,
                "commits": [len(c) for c in result.commits] if result else None,
                "event_log_digest": result.event_log_digest if result else None,
            }
        )
        events = [type(e).__name__ for e in plan.events]
        peak = max(result.rounds) if result and result.rounds else "-"
        print(
            f"  fault seed {seed}: {'ok' if ok else 'VIOLATION'}  "
            f"events {events}  peak round {peak}"
        )
    return rows


def sweep(args) -> list[dict]:
    results: list[dict] = []
    if args.auto:
        # Geometric ramp until TPS stops improving by >10% (the knee).
        rate = args.start_rate
        best = 0.0
        while True:
            try:
                record = run_once(rate, args)
            except ParseError as e:
                print(f"  rate {rate:,}: run failed ({e}); stopping sweep")
                break
            tps = record["consensus_tps"]
            if tps <= 0:
                print(f"  rate {rate:,}: no commits parsed; stopping sweep")
                break
            results.append(record)
            if tps < best * 1.1:
                break  # saturated: no meaningful gain from more input
            best = max(best, tps)
            rate *= 2
    else:
        for rate in args.rates:
            try:
                results.append(run_once(rate, args))
            except ParseError as e:
                print(f"  rate {rate:,}: run failed ({e})")
    return results


def render_table(results: list[dict]) -> str:
    lines = [
        "| input rate | consensus TPS | consensus lat | e2e lat |",
        "|---|---|---|---|",
    ]
    # FaultPlan rows have no rate axis; they are printed as they run.
    results = [r for r in results if "fault_plan_seed" not in r]
    for r in results:
        lines.append(
            f"| {r['input_rate']:,} | {r['consensus_tps']:,.0f} "
            f"| {r['consensus_latency_ms']:,.0f} ms "
            f"| {r['end_to_end_latency_ms']:,.0f} ms |"
        )
    if results:
        knee = max(results, key=lambda r: r["consensus_tps"])
        lines.append(
            f"\nknee: ~{knee['consensus_tps']:,.0f} tx/s "
            f"at input rate {knee['input_rate']:,}"
        )
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmark.sweep")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--tx-size", type=int, default=512)
    ap.add_argument("--duration", type=int, default=20)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--consensus-protocol", choices=("bullshark", "tusk"),
                    default="bullshark")
    ap.add_argument("--crypto-backend", choices=("cpu", "pool", "tpu"),
                    default="cpu")
    ap.add_argument("--dag-backend", choices=("cpu", "tpu"), default="cpu")
    ap.add_argument("--dag-shards", type=int, default=1)
    ap.add_argument("--cert-format", choices=("full", "compact"),
                    default="compact",
                    help="certificate wire form (committee-wide axis)")
    ap.add_argument("--verify-rule", choices=("strict", "cofactored"),
                    default="strict",
                    help="per-item ed25519 accept set (cofactored requires "
                    "--crypto-backend tpu)")
    ap.add_argument("--max-header-delay", type=float, default=0.1)
    ap.add_argument("--max-batch-delay", type=float, default=0.1)
    ap.add_argument("--rates", type=int, nargs="*", default=[5_000, 15_000, 30_000])
    ap.add_argument(
        "--fault-seeds", type=int, nargs="*", default=[],
        help="additionally run one simnet row per seed, each under the "
        "seeded FaultPlan that narwhal_tpu.simnet.fuzz.generate_plan "
        "derives from it (safety/liveness oracles applied)",
    )
    ap.add_argument(
        "--fault-load-rate", type=int, default=100,
        help="client tx/s injected during each FaultPlan row (virtual time)",
    )
    ap.add_argument(
        "--fault-duration", type=float, default=2.5,
        help="virtual seconds per FaultPlan row",
    )
    ap.add_argument("--auto", action="store_true", help="geometric ramp to the knee")
    ap.add_argument("--start-rate", type=int, default=2_000)
    ap.add_argument("--out", default=".bench/sweep.json")
    args = ap.parse_args()

    results = sweep(args) if (args.rates or args.auto) else []
    if args.fault_seeds:
        print("fault-plan rows (simnet, virtual clock):")
        results.extend(run_fault_rows(args))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"\nwrote {len(results)} records to {args.out}\n")
    print(render_table(results))


if __name__ == "__main__":
    main()
