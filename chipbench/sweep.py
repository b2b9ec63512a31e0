"""The sweep that finds a configuration's knee, once, on the chip.

    python3 -m chipbench.sweep --workload <cell> --rates 1000,1250,... --seconds 15

One process, one warm-up; a fresh committee per rate. One JSON line per
rate. The knee is the highest rate at which no burst is shed and the backlog
(acknowledged and due, not yet executed everywhere) does not grow over the
second half of the window; PERF.md keeps the whole table and the
configuration's file the knee.
"""

from __future__ import annotations

import time

_T_PROC = time.monotonic()

import argparse
import json
import os
import sys


def main(argv: list[str] | None = None) -> int:
    from . import run as runner
    from .__main__ import parse

    ap = argparse.ArgumentParser(prog="python3 -m chipbench.sweep")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=2_300_000_001)
    own = ap.parse_args(argv)
    args = parse(["--workload", own.workload, "--seed", str(own.seed),
                  "--seconds", str(own.seconds), "--trace", "0"])
    ctx = runner.prepare(args, _T_PROC)
    for k, rate in enumerate(float(r) for r in own.rates.split(",")):
        args.seed = own.seed + k
        rec = runner.measure(ctx, args, rate)
        obs = rec["obs"]
        lat, late = obs["latencies_ms"], obs["late_ms"]
        print(json.dumps({
            "sweep": own.workload, "rate": rate, "seed": args.seed, "correct": rec["correct"],
            "nonzero_checks": {k: v for k, (v, _) in rec["checks"].items() if v}, "notes": rec["notes"],
            "attempted": rec["attempted"], "failed": rec["failed"],
            "executed_again": obs["executed_again"],
            "shed_share": obs["shed_tx"] / max(1, rec["attempted"]),
            "executed_tx_per_s": obs["executed_in_window"] / obs["seconds"],
            "p50_ms": runner.percentile(lat, 0.5) if lat else None,
            "p95_ms": runner.percentile(lat, 0.95) if lat else None,
            "backlog_mid": obs["backlog_mid"], "backlog_end": obs["backlog_end"],
            "late_p95_ms": runner.percentile(late, 0.95) if late else None,
            "rounds": obs["window"]["rounds"], "drain_s": obs["drain_s"],
            "boot_s": rec["setup"].get("boot_s"),
            "first_dispatches_in_window": obs["first_dispatches_in_window"],
            "device": ctx.device["kind"],
        }), flush=True)
    runner.stop_device_plane()
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
