"""Committee-scale liveness sweep: boot N in-process nodes, sample commit
progress over time, and account the control-plane wire cost per round.

Two transports:

* **Real sockets** (default) — loopback TCP. A committee's vote mesh costs
  ~2·N·(N-1) in-process fds, which hard-caps this mode near N=90 under the
  container's RLIMIT_NOFILE (an N=100 run died of EMFILE mid-run); a
  preflight fails fast with the arithmetic instead.
* **simnet** (`--simnet`) — the virtual-clock in-memory fabric
  (narwhal_tpu/simnet): zero sockets, zero fds on the mesh, hundreds of
  nodes in one process, `--duration` measured in *virtual* seconds (wall
  cost is CPU only). This is the mode for N>90 committees.

    python -m benchmark.liveness --nodes 50 --duration 240
    python -m benchmark.liveness --nodes 200 --simnet --duration 10 \
        --out .bench/simnet_n200_liveness.json

No injected load: at these committee sizes each round is thousands of
signed control messages, so the assertion is liveness (lockstep commits
advancing on every node) and the headline wire metric is bytes per
committed round.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import resource
import sys
import time


def pooling_enabled() -> bool:
    """Mirror config.connection_pool_effective's NARWHAL_POOL kill-switch
    without importing narwhal_tpu (the preflight must stay import-light)."""
    return os.environ.get("NARWHAL_POOL", "1").strip().lower() not in (
        "0", "false", "off",
    )


def estimate_required_fds(nodes: int, workers: int, pooled: bool = True) -> int:
    """Upper-bound fd demand of an N-node, W-worker in-process committee
    over real sockets. Every in-process TCP connection burns TWO fds (both
    endpoints live here).

    Pooled (connection_pool=True, the default): ONE multiplexed link per
    unordered node pair carries every lane — primary votes and all W
    worker meshes — so connections = N·(N-1)/2 pair links + N self links
    (primary<->own-worker control rides a node's link to itself). Crossed
    dials transiently double a pair's sockets until the loser
    linger-closes, so the socket term gets 25% boot-burst headroom.

    Legacy (NARWHAL_POOL=0): primary vote mesh N·(N-1) connections, one
    same-id worker mesh per lane N·(N-1)·W, primary<->own-worker control
    2·N·W. Either way add listeners (primary, typed api, grpc api = 3 per
    node; worker mesh + tx + grpc tx = 3 per worker) and a flat allowance
    for stores/logs/jax."""
    listeners = nodes * (3 + 3 * workers)
    if pooled:
        connections = nodes * (nodes - 1) // 2 + nodes
        return int(2 * connections * 1.25) + listeners + 256
    connections = nodes * (nodes - 1) * (1 + workers) + 2 * nodes * workers
    return 2 * connections + listeners + 256


def preflight_fd_check(
    nodes: int, workers: int, pooled: bool | None = None
) -> None:
    """Fail fast (and actionably) instead of mid-run EMFILE."""
    if pooled is None:
        pooled = pooling_enabled()
    soft, _hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    needed = estimate_required_fds(nodes, workers, pooled)
    if needed > soft:
        model = (
            "≈N·(N-1)/2+N pooled pair links ×2 fds, + headroom + listeners"
            if pooled
            else "≈2·N·(N-1)·(1+W) legacy mesh sockets + listeners"
        )
        raise SystemExit(
            f"liveness preflight: N={nodes} W={workers} needs ~{needed:,} "
            f"fds ({model}) but "
            f"RLIMIT_NOFILE is {soft:,}. Raise `ulimit -n`, shrink the "
            "committee, or run this committee socket-free with --simnet "
            "(virtual-clock in-memory transport; no fd cost, N=200+ fits)."
        )


def process_fd_count() -> int:
    """Open fds in THIS process right now (the whole committee lives here,
    so this is the number the rlimit actually judges)."""
    try:
        return len(os.listdir("/proc/self/fd"))
    except OSError:  # non-procfs platform
        return -1


def _pool_link_peaks(cluster) -> list[int]:
    """Per-node peak live pooled-link counts, one entry per booted node.

    ``cluster.authorities[i].primary`` is the PrimaryNode assembly; the
    Primary role that owns the LanePool sits one level in at ``.primary``.
    """
    peaks = []
    for a in cluster.authorities:
        node = a.primary
        if node is None:
            continue
        role = getattr(node, "primary", node)
        pool = getattr(role, "pool", None)
        if pool is not None:
            peaks.append(pool.peak_links)
    return peaks


async def run_liveness(args) -> dict:
    from narwhal_tpu.cluster import Cluster
    from narwhal_tpu.config import Parameters
    from narwhal_tpu.network.rpc import WireStats

    preflight_fd_check(args.nodes, args.workers)
    cluster = Cluster(
        size=args.nodes,
        workers=args.workers,
        parameters=Parameters(
            max_header_delay=args.max_header_delay,
            max_batch_delay=args.max_batch_delay,
            cert_format=args.cert_format,
            verify_rule=args.verify_rule,
        ),
    )
    fd_baseline = process_fd_count()
    t0 = time.time()
    await cluster.start(args.nodes - args.faults)
    boot_s = time.time() - t0
    peak_fds = process_fd_count()
    print(f"booted {args.nodes - args.faults} nodes in {boot_s:.0f}s "
          f"({peak_fds} fds open)", file=sys.stderr)

    def committed() -> list[float]:
        return [
            a.metric("consensus_last_committed_round")
            for a in cluster.authorities
            if a.primary is not None
        ]

    def primary_sent_by_type() -> dict[str, float]:
        out: dict[str, float] = {}
        for a in cluster.authorities:
            if a.primary is None:
                continue
            m = a.primary.registry.get("wire_bytes_sent_total")
            if m is None:
                continue
            for k, c in m._children.items():
                out[k[0]] = out.get(k[0], 0.0) + c.value
        return out

    samples = []
    wire0 = WireStats.snapshot()
    egress0 = primary_sent_by_type()
    rounds0 = committed()
    t_start = time.time()
    try:
        while time.time() - t_start < args.duration:
            await asyncio.sleep(args.sample_interval)
            peak_fds = max(peak_fds, process_fd_count())
            rounds = committed()
            samples.append(
                {
                    "t_s": round(time.time() - t_start, 1),
                    "committed_min": min(rounds),
                    "committed_max": max(rounds),
                }
            )
            print(f"  t={samples[-1]['t_s']}s committed "
                  f"[{min(rounds)}, {max(rounds)}]", file=sys.stderr)
    finally:
        peak_fds = max(peak_fds, process_fd_count())
        link_peaks = _pool_link_peaks(cluster)
        wire1 = WireStats.snapshot()
        egress1 = primary_sent_by_type()
        rounds1 = committed()
        telemetry = _scrape_node0(cluster)
        await cluster.shutdown()

    window = time.time() - t_start
    alive = args.nodes - args.faults
    record = _record(
        args, "in-process liveness", boot_s, samples, window,
        rounds0, rounds1, wire0, wire1, egress0, egress1,
        alive=alive,
    )
    record["telemetry_scrape"] = telemetry
    # Socket-wall accounting: the committee shares one process, so the
    # process-wide peak divided by booted nodes is the per-node fd story
    # (pooled target: O(N); legacy mesh: O(N·W)).
    record["fd_baseline"] = fd_baseline
    record["peak_process_fds"] = peak_fds
    record["peak_fds_per_node"] = (
        round((peak_fds - fd_baseline) / alive, 1) if peak_fds >= 0 else None
    )
    record["peak_pool_links_per_node"] = max(link_peaks, default=None)
    record["connection_pool"] = bool(link_peaks)
    return record


def _scrape_node0(cluster) -> dict:
    """Node 0's parsed scrape (buckets dropped) for the results record —
    the same surface Telemetry.Scrape serves over RPC, captured in-process
    because the committee lives in this process anyway."""
    from narwhal_tpu.metrics import scrape_snapshot

    for a in cluster.authorities:
        if a.primary is not None:
            return {"primary-0": scrape_snapshot(a.primary.registry)}
    return {}


def run_liveness_simnet(args) -> dict:
    """The same measurement over the simnet fabric: one process, zero
    sockets, virtual time. Boots the committee, lets `--duration` VIRTUAL
    seconds elapse, and reports the usual liveness/wire record plus the
    wall cost and the fabric's event count."""
    from narwhal_tpu.network import transport
    from narwhal_tpu.network.rpc import WireStats
    from narwhal_tpu.simnet import SimCluster, SimFabric, SimLoop

    loop = SimLoop()
    asyncio.set_event_loop(loop)
    fabric = SimFabric(seed=args.seed)
    transport.install(fabric)
    t_wall = time.time()

    async def drive() -> dict:
        from narwhal_tpu.config import Parameters

        cluster = SimCluster(
            size=args.nodes,
            fabric=fabric,
            workers=args.workers,
            auth=not args.no_auth,
            parameters=Parameters(
                max_header_delay=args.max_header_delay,
                max_batch_delay=args.max_batch_delay,
                cert_format=args.cert_format,
                verify_rule=args.verify_rule,
            ),
        )
        t0 = time.time()
        await cluster.start(args.nodes - args.faults)
        boot_s = time.time() - t0
        print(
            f"booted {args.nodes - args.faults} simnet nodes in {boot_s:.0f}s "
            f"(wall)",
            file=sys.stderr,
        )

        def committed() -> list[float]:
            return [
                a.metric("consensus_last_committed_round")
                for a in cluster.authorities
                if a.primary is not None
            ]

        def primary_sent_by_type() -> dict[str, float]:
            out: dict[str, float] = {}
            for a in cluster.authorities:
                if a.primary is None:
                    continue
                m = a.primary.registry.get("wire_bytes_sent_total")
                if m is None:
                    continue
                for k, c in m._children.items():
                    out[k[0]] = out.get(k[0], 0.0) + c.value
            return out

        samples = []
        wire0 = WireStats.snapshot()
        egress0 = primary_sent_by_type()
        rounds0 = committed()
        v_start = loop.time()
        ticks = max(1, int(args.duration / args.sample_interval))
        for _ in range(ticks):
            await asyncio.sleep(args.sample_interval)
            rounds = committed()
            samples.append(
                {
                    "t_virtual_s": round(loop.time() - v_start, 1),
                    "committed_min": min(rounds),
                    "committed_max": max(rounds),
                    "wall_s": round(time.time() - t_wall, 1),
                }
            )
            print(
                f"  t={samples[-1]['t_virtual_s']}s(virtual) committed "
                f"[{min(rounds)}, {max(rounds)}] wall={samples[-1]['wall_s']}s",
                file=sys.stderr,
            )
        window = loop.time() - v_start
        link_peaks = _pool_link_peaks(cluster)
        wire1 = WireStats.snapshot()
        egress1 = primary_sent_by_type()
        rounds1 = committed()
        telemetry = _scrape_node0(cluster)
        await cluster.shutdown()
        record = _record(
            args, "simnet liveness (virtual clock)", boot_s, samples, window,
            rounds0, rounds1, wire0, wire1, egress0, egress1,
            alive=args.nodes - args.faults,
        )
        record["telemetry_scrape"] = telemetry
        record["virtual_duration_s"] = round(window, 1)
        record["wall_s"] = round(time.time() - t_wall, 1)
        record["real_sockets"] = 0
        record["fabric_events"] = len(fabric.log)
        record["transport_auth"] = not args.no_auth
        record["seed"] = args.seed
        # Virtual analogue of the fd story: peak simultaneous fabric
        # connections, committee-wide and per booted node.
        alive = args.nodes - args.faults
        peak_conns = fabric.counters["peak_conns"]
        record["peak_fabric_conns"] = peak_conns
        record["peak_fds_per_node"] = round(2 * peak_conns / alive, 1)
        record["peak_pool_links_per_node"] = max(link_peaks, default=None)
        record["connection_pool"] = bool(link_peaks)
        return record

    try:
        return loop.run_until_complete(drive())
    finally:
        transport.uninstall()
        pending = [t for t in asyncio.all_tasks(loop) if not t.done()]
        for t in pending:
            t.cancel()
        if pending:
            loop.run_until_complete(asyncio.wait(pending, timeout=15.0))
        asyncio.set_event_loop(None)
        loop.close()


def _record(
    args, mode, boot_s, samples, window, rounds0, rounds1, wire0, wire1,
    egress0, egress1, alive,
) -> dict:
    progressed = max(r1 - r0 for r0, r1 in zip(rounds0, rounds1))
    min_progress = min(r1 - r0 for r0, r1 in zip(rounds0, rounds1))
    wire_bytes = wire1["bytes_sent"] - wire0["bytes_sent"]
    by_type = {
        k: round(egress1.get(k, 0.0) - egress0.get(k, 0.0), 1)
        for k in sorted(set(egress0) | set(egress1))
    }
    return {
        "mode": mode,
        "committee_size": args.nodes,
        "workers_per_node": args.workers,
        "faults": args.faults,
        # First-class experiment axes like W and faults: the certificate
        # wire form moves the control-plane byte floor, the accept rule
        # names the verification semantics the row ran under.
        "cert_format": args.cert_format,
        "verify_rule": args.verify_rule,
        "alive_nodes": alive,
        "parameters": {
            "max_header_delay_s": args.max_header_delay,
            "max_batch_delay_s": args.max_batch_delay,
        },
        "relay_fanout": os.environ.get("NARWHAL_RELAY_FANOUT", "default"),
        "header_wire": os.environ.get("NARWHAL_HEADER_WIRE", "default"),
        "boot_s": round(boot_s, 1),
        "samples": samples,
        "committed_rounds_in_window": round(progressed, 1),
        "committed_rounds_per_s": round(progressed / window, 4) if window else None,
        # The liveness gate: every node advanced, and min==max lockstep at
        # the final sample means nobody was left behind.
        "all_nodes_progressed": min_progress > 0,
        "all_nodes_lockstep": min(rounds1) == max(rounds1),
        "wire_bytes_sent_in_window": wire_bytes,
        "wire_bytes_per_round": (
            round(wire_bytes / progressed, 1) if progressed else None
        ),
        # Per-primary egress per round (committee aggregate / N / rounds):
        # the wire-diet acceptance metric, from the per-link counters.
        "primary_egress_bytes_per_round": (
            round(sum(by_type.values()) / alive / progressed, 1)
            if progressed
            else None
        ),
        "primary_egress_bytes_by_msg_type": by_type,
    }


def main() -> None:
    ap = argparse.ArgumentParser(prog="benchmark.liveness")
    ap.add_argument("--nodes", type=int, default=50)
    ap.add_argument("--workers", type=int, default=1)
    ap.add_argument("--faults", type=int, default=0,
                    help="boot N-faults nodes (reference bench parity)")
    ap.add_argument("--duration", type=float, default=240.0,
                    help="measurement window; VIRTUAL seconds under --simnet")
    ap.add_argument("--sample-interval", type=float, default=20.0)
    ap.add_argument("--max-header-delay", type=float, default=1.0)
    ap.add_argument("--max-batch-delay", type=float, default=0.5)
    ap.add_argument("--cert-format", choices=("full", "compact"),
                    default="compact",
                    help="certificate wire form (committee-wide axis; "
                    "compact = half-aggregated default, full = opt-out)")
    ap.add_argument("--verify-rule", choices=("strict", "cofactored"),
                    default="strict",
                    help="per-item ed25519 accept set")
    ap.add_argument("--simnet", action="store_true",
                    help="socket-free virtual-clock transport: no fd "
                    "ceiling, N=200+ committees fit in one process")
    ap.add_argument("--seed", type=int, default=0,
                    help="simnet determinism seed")
    ap.add_argument("--no-auth", action="store_true",
                    help="simnet only: skip transport handshakes/AEAD "
                    "(trusted in-memory medium; saves 2N(N-1) pure-Python "
                    "X25519 exchanges at boot)")
    ap.add_argument("--note", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.simnet:
        record = run_liveness_simnet(args)
    else:
        record = asyncio.run(run_liveness(args))
    if args.note:
        record["note"] = args.note
    print(json.dumps(record, indent=1))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(record, f, indent=1)


if __name__ == "__main__":
    main()
