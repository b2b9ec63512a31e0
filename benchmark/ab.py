"""Self-calibrating interleaved A/B driver: the one way to claim a perf
delta on this host.

The host-capacity-swing rule (ROADMAP): this 1-core container varies
10-20x day to day, so BASE and HEAD must run interleaved in the same
minutes and every leg must carry the capacity it measured under. Every
prior PR hand-rolled that ritual (pacing_ab_r8, worker_shard_ab_r9,
compact_wire_ab_r10, trace_ab_r13 — four bespoke schemas); this driver
is the ritual as a tool:

  python -m benchmark.ab --base <rev> --bench inprocess --pairs 2 \
      -- --duration 10 --rate 300

- BASE legs run from a detached `git worktree` of --base; HEAD legs run
  from the working tree. Legs alternate base/head then head/base per
  pair so a monotone capacity drift cancels instead of biasing one side.
- A pinned CPU calibration probe (tools/perf/calibrate) brackets every
  leg; if the slowest probe of the run differs from the fastest by more
  than --calibration-gate the run REFUSES a verdict (`no-verdict`) —
  a number measured across a capacity cliff is not a measurement.
- The noise band is estimated from same-side repeat spread:
  max((max-min)/median) over the base legs and over the head legs. A
  head/base ratio inside the band is `null`; outside it is `win` or
  `regression` per --lower-is-better.
- The canonical verdict record lands in the perf ledger (kind "ab") and
  optionally --out; leg subprocesses run with the ledger disabled so one
  A/B run appends exactly one record.

An A/A run (`--base HEAD` on a clean tree) must come out `null`: that is
the self-test pinned by tests/test_perf_observatory.py fixtures and the
checked-in ab_aa_r14 artifact.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.perf import calibrate, ledger  # noqa: E402

BENCHES = ("inprocess", "liveness", "microbench")
DEFAULT_METRIC = {
    "inprocess": "executed_tps",
    "liveness": "committed_rounds_per_s",
    "microbench": None,  # rows differ per sub-bench: --metric is required
}


def extract_metric(doc, metric: str, select: str | None):
    """Pull the metric out of a leg's --out document.

    inprocess appends to an array (take the LAST record), liveness writes
    one object, microbench writes rows — `--select key=value` picks the
    row. `metric` is a dotted path into the chosen object.
    """
    if isinstance(doc, list):
        if select:
            k, _, v = select.partition("=")
            matches = [r for r in doc if str(r.get(k)) == v]
            if not matches:
                raise KeyError(f"no row matches --select {select!r}")
            doc = matches[-1]
        else:
            doc = doc[-1]
    for part in metric.split("."):
        if not isinstance(doc, dict) or part not in doc:
            raise KeyError(f"metric path {metric!r} missing at {part!r}")
        doc = doc[part]
    if not isinstance(doc, (int, float)) or isinstance(doc, bool):
        raise TypeError(f"metric {metric!r} is {type(doc).__name__}, not a number")
    return float(doc)


def run_leg(
    side: str,
    cwd: Path,
    bench: str,
    bench_args: list[str],
    metric: str,
    select: str | None,
    timeout_s: float,
) -> dict:
    """One subprocess bench leg, bracketed by calibration probes."""
    probe_before = calibrate.calibration_probe()
    env = dict(os.environ)
    env.setdefault("NARWHAL_TPU_PREWARM", "0")
    env["NARWHAL_PERF_LEDGER"] = "0"  # the driver appends the one record
    with tempfile.NamedTemporaryFile(suffix=".json", delete=False) as tmp:
        out_path = tmp.name
    try:
        os.unlink(out_path)  # inprocess treats an existing file as an array to extend
        cmd = [sys.executable, "-m", f"benchmark.{bench}", *bench_args, "--out", out_path]
        t0 = time.monotonic()
        proc = subprocess.run(
            cmd, cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout_s
        )
        wall_s = time.monotonic() - t0
        if proc.returncode != 0:
            raise RuntimeError(
                f"{side} leg failed ({proc.returncode}): "
                f"{proc.stderr[-2000:] or proc.stdout[-2000:]}"
            )
        with open(out_path) as fh:
            doc = json.load(fh)
    finally:
        try:
            os.unlink(out_path)
        except OSError:
            pass
    probe_after = calibrate.calibration_probe()
    leg = {
        "side": side,
        "value": extract_metric(doc, metric, select),
        "wall_s": round(wall_s, 2),
        "calibration_before": probe_before,
        "calibration_after": probe_after,
    }
    # Socket-wall accounting rides along when the leg's bench records it
    # (liveness does), so pooled-vs-mesh fd pressure lands in the ledger.
    if isinstance(doc, dict) and doc.get("peak_fds_per_node") is not None:
        leg["peak_fds_per_node"] = doc["peak_fds_per_node"]
    return leg


def same_side_band(values: list[float]) -> float:
    """(max-min)/median over one side's repeats — the spread that same
    code on this same host produces, i.e. the floor under any claim."""
    if len(values) < 2:
        return float("inf")
    med = statistics.median(values)
    if med == 0:
        return float("inf")
    return (max(values) - min(values)) / abs(med)


def decide(
    base_values: list[float],
    head_values: list[float],
    probes: list[dict],
    *,
    lower_is_better: bool = False,
    calibration_gate: float = 0.5,
    min_band: float = 0.02,
) -> dict:
    """The verdict: win/null/regression, or no-verdict when the host
    drifted through the run. Pure so the fixtures can pin every branch."""
    if not base_values or not head_values:
        return {"verdict": "no-verdict", "reason": "a side produced no legs"}
    drift = 0.0
    for p in probes:
        for q in probes:
            drift = max(drift, calibrate.drift(p, q))
    band = max(same_side_band(base_values), same_side_band(head_values), min_band)
    base_med = statistics.median(base_values)
    head_med = statistics.median(head_values)
    verdict: dict = {
        "base_median": base_med,
        "head_median": head_med,
        "base_values": base_values,
        "head_values": head_values,
        "noise_band": band if band != float("inf") else None,
        "calibration_drift": round(drift, 4),
        "lower_is_better": lower_is_better,
    }
    if drift > calibration_gate:
        verdict["verdict"] = "no-verdict"
        verdict["reason"] = (
            f"host capacity swung {drift:.0%} mid-run "
            f"(gate {calibration_gate:.0%}): rerun when the host is quiet"
        )
        return verdict
    if band == float("inf") or base_med == 0:
        verdict["verdict"] = "no-verdict"
        verdict["reason"] = "need >=2 repeats per side for a noise band"
        return verdict
    ratio = head_med / base_med
    verdict["ratio"] = round(ratio, 4)
    delta = ratio - 1.0
    if abs(delta) <= band:
        verdict["verdict"] = "null"
        verdict["reason"] = (
            f"|{delta:+.1%}| inside the {band:.1%} same-side noise band"
        )
    else:
        improved = delta < 0 if lower_is_better else delta > 0
        verdict["verdict"] = "win" if improved else "regression"
        verdict["reason"] = (
            f"{delta:+.1%} vs a {band:.1%} noise band"
        )
    return verdict


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        epilog="bench args after `--` are passed through to the leg, e.g. "
        "`-- --duration 10 --rate 300`",
    )
    ap.add_argument("--base", required=True, help="git rev for the BASE legs")
    ap.add_argument("--bench", required=True, choices=BENCHES)
    ap.add_argument("--pairs", type=int, default=2,
                    help="interleaved base/head pairs (>=2 for a noise band)")
    ap.add_argument("--metric", default=None,
                    help="dotted path into the leg record (default per bench)")
    ap.add_argument("--select", default=None,
                    help="key=value row selector for list-shaped records")
    ap.add_argument("--lower-is-better", action="store_true",
                    help="the metric is a latency, not a throughput")
    ap.add_argument("--calibration-gate", type=float, default=0.5,
                    help="max relative capacity swing before refusing a verdict")
    ap.add_argument("--leg-timeout", type=float, default=900.0)
    ap.add_argument("--out", default=None, help="also write the verdict record here")
    ap.add_argument("bench_args", nargs="*",
                    help="passed through to `python -m benchmark.<bench>`")
    args = ap.parse_args(argv)

    metric = args.metric or DEFAULT_METRIC[args.bench]
    if not metric:
        ap.error(f"--metric is required for --bench {args.bench}")

    base_rev = subprocess.run(
        ["git", "rev-parse", args.base], cwd=REPO,
        capture_output=True, text=True, check=True,
    ).stdout.strip()
    head_rev = ledger.git_rev(REPO)

    worktree = Path(tempfile.mkdtemp(prefix="ab-base-"))
    subprocess.run(
        ["git", "worktree", "add", "--detach", str(worktree), base_rev],
        cwd=REPO, check=True, capture_output=True,
    )
    legs: list[dict] = []
    try:
        for pair in range(args.pairs):
            # Alternate leg order per pair so monotone drift cancels.
            order = ("base", "head") if pair % 2 == 0 else ("head", "base")
            for side in order:
                cwd = worktree if side == "base" else REPO
                print(
                    f"[pair {pair + 1}/{args.pairs}] {side} leg "
                    f"({base_rev[:10] if side == 'base' else head_rev[:10]}) ...",
                    flush=True,
                )
                leg = run_leg(
                    side, cwd, args.bench, list(args.bench_args),
                    metric, args.select, args.leg_timeout,
                )
                print(
                    f"  {metric}={leg['value']:.4g}  wall={leg['wall_s']}s  "
                    f"cal={leg['calibration_before']['ops_per_s']:.0f}->"
                    f"{leg['calibration_after']['ops_per_s']:.0f} ops/s",
                    flush=True,
                )
                legs.append(leg)
    finally:
        subprocess.run(
            ["git", "worktree", "remove", "--force", str(worktree)],
            cwd=REPO, capture_output=True,
        )

    probes = [leg["calibration_before"] for leg in legs] + [
        leg["calibration_after"] for leg in legs
    ]
    verdict = decide(
        [leg["value"] for leg in legs if leg["side"] == "base"],
        [leg["value"] for leg in legs if leg["side"] == "head"],
        probes,
        lower_is_better=args.lower_is_better,
        calibration_gate=args.calibration_gate,
    )
    verdict.update(
        {
            "metric": metric,
            "bench": args.bench,
            "base_rev": base_rev,
            "head_rev": head_rev,
            "pairs": args.pairs,
        }
    )
    record = {
        "verdict": verdict,
        "legs": legs,
        "bench_args": list(args.bench_args),
    }
    print(json.dumps(verdict, indent=1, sort_keys=True))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(record, fh, indent=1, sort_keys=True)
            fh.write("\n")
    ledger.append(
        "ab",
        record,
        verdict=verdict,
        argv=["benchmark.ab", f"--base={args.base}", f"--bench={args.bench}"]
        + list(args.bench_args),
        rev=head_rev,
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
