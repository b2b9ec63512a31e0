"""The commit-keyed perf ledger: benchmark/results/ledger.jsonl.

Before this module, the repo's perf trajectory was reconstructable only
from CHANGES.md prose: every bench run wrote an ad-hoc JSON file with its
own shape (pacing_ab_r8.json, worker_shard_ab_r9.json, trace_ab_r13.json
all differ). The ledger replaces that with ONE append-only JSONL file
where every bench/A/B entry point appends a schema-validated record
keyed by the git revision it measured, carrying the host calibration it
measured UNDER, and (for A/B runs) the canonical verdict.

The schema is deliberately small and closed: unknown top-level keys are
hard errors, so a drive-by bench that invents a field fails the tier-1
schema gate (tests/test_perf_observatory.py) instead of silently forking
the record shape — the exact failure mode the ad-hoc files had.

Environment:
  NARWHAL_PERF_LEDGER=0        disable appends entirely (tests default
                               to this via conftest so suite runs never
                               dirty the checked-in ledger);
  NARWHAL_PERF_LEDGER_PATH=... append somewhere else (ab.py uses this to
                               keep base-leg subprocesses out of the
                               head ledger).

Pre-ledger artifacts in benchmark/results/*.json remain valid history:
`classify_results_dir` tags anything without a `schema` stamp as
`legacy` and only flags unparseable files — the tolerance contract the
legacy-results test pins.
"""

from __future__ import annotations

import json
import os
import subprocess
import time
from pathlib import Path

SCHEMA = "narwhal-perf-ledger/1"

# Every entry point that may append. A record with a kind outside this
# set is an unregistered shape: extend the set (and the test) on purpose.
KINDS = frozenset(
    {
        "inprocess",
        "liveness",
        "sweep",
        "microbench",
        "multichip",
        "ab",
        "simnet_profile",
        "fuzz",
    }
)

_REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_PATH = _REPO_ROOT / "benchmark" / "results" / "ledger.jsonl"

# The closed top-level surface: name -> (required, type check).
_FIELDS: dict[str, tuple[bool, object]] = {
    "schema": (True, str),
    "kind": (True, str),
    "git_rev": (True, str),
    "recorded_unix": (True, (int, float)),
    "host": (True, dict),
    "payload": (True, (dict, list)),
    "verdict": (False, dict),
    "scrape": (False, dict),
    "argv": (False, list),
    "note": (False, str),
}


def validate_record(record: object) -> list[str]:
    """Return every schema violation (empty list == valid)."""
    if not isinstance(record, dict):
        return [f"record must be an object, got {type(record).__name__}"]
    errors: list[str] = []
    for name, (required, typ) in _FIELDS.items():
        if name not in record:
            if required:
                errors.append(f"missing required field {name!r}")
            continue
        if not isinstance(record[name], typ):
            errors.append(
                f"field {name!r} must be {typ}, got {type(record[name]).__name__}"
            )
    for name in record:
        if name not in _FIELDS:
            errors.append(f"unregistered field {name!r} (the schema is closed)")
    if record.get("schema") not in (None, SCHEMA):
        errors.append(f"unknown schema {record.get('schema')!r}, want {SCHEMA!r}")
    kind = record.get("kind")
    if isinstance(kind, str) and kind not in KINDS:
        errors.append(f"unregistered kind {kind!r}, want one of {sorted(KINDS)}")
    host = record.get("host")
    if isinstance(host, dict) and "calibration" not in host:
        errors.append("host snapshot missing 'calibration' probe")
    if isinstance(record.get("verdict"), dict):
        v = record["verdict"]
        if v.get("verdict") not in {"win", "null", "regression", "no-verdict"}:
            errors.append(
                f"verdict.verdict must be win/null/regression/no-verdict, "
                f"got {v.get('verdict')!r}"
            )
    return errors


def git_rev(cwd: str | os.PathLike | None = None) -> str:
    """The commit key. Appends '-dirty' when the working tree differs, so
    a record measured on uncommitted code never masquerades as the rev."""
    try:
        rev = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=cwd or _REPO_ROOT, capture_output=True, text=True, timeout=10,
        ).stdout.strip()
        if not rev:
            return "unknown"
        dirty = subprocess.run(
            ["git", "status", "--porcelain", "--untracked-files=no"],
            cwd=cwd or _REPO_ROOT, capture_output=True, text=True, timeout=10,
        ).stdout.strip()
        return rev + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"


def ledger_path() -> Path:
    override = os.environ.get("NARWHAL_PERF_LEDGER_PATH")
    return Path(override) if override else DEFAULT_PATH


def enabled() -> bool:
    return os.environ.get("NARWHAL_PERF_LEDGER", "1") not in {"0", "false", "no"}


def build_record(
    kind: str,
    payload: dict | list,
    *,
    verdict: dict | None = None,
    scrape: dict | None = None,
    argv: list | None = None,
    note: str | None = None,
    host: dict | None = None,
    rev: str | None = None,
) -> dict:
    """Assemble (and validate) one ledger record. Runs the calibration
    probe unless a host snapshot is supplied (A/B legs probe themselves
    so the record reflects the leg's bracket, not append time)."""
    from . import calibrate

    record: dict = {
        "schema": SCHEMA,
        "kind": kind,
        "git_rev": rev if rev is not None else git_rev(),
        "recorded_unix": time.time(),
        "host": host
        if host is not None
        else {"calibration": calibrate.calibration_probe()},
        "payload": payload,
    }
    if verdict is not None:
        record["verdict"] = verdict
    if scrape is not None:
        record["scrape"] = scrape
    if argv is not None:
        record["argv"] = [str(a) for a in argv]
    if note is not None:
        record["note"] = note
    errors = validate_record(record)
    if errors:
        raise ValueError(f"refusing to build invalid ledger record: {errors}")
    return record


def append(kind: str, payload: dict | list, **kwargs) -> dict | None:
    """Append one validated record; returns it, or None when the ledger
    is disabled. Bench entry points call this exactly once per run, after
    their own --out artifact is written — the ledger is additive, never a
    replacement for the detailed per-bench record."""
    if not enabled():
        return None
    record = build_record(kind, payload, **kwargs)
    path = ledger_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "a") as fh:
        fh.write(json.dumps(record, sort_keys=True) + "\n")
    return record


def read_ledger(path: str | os.PathLike | None = None) -> list[dict]:
    """Parse every line; raises on a malformed line (the ledger is a
    gated artifact — a bad line is a bug, not data)."""
    p = Path(path) if path is not None else ledger_path()
    records: list[dict] = []
    if not p.exists():
        return records
    with open(p) as fh:
        for lineno, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                record = json.loads(line)
            except json.JSONDecodeError as exc:
                raise ValueError(f"{p}:{lineno}: malformed ledger line: {exc}")
            errors = validate_record(record)
            if errors:
                raise ValueError(f"{p}:{lineno}: invalid record: {errors}")
            records.append(record)
    return records


def classify_results_dir(results_dir: str | os.PathLike | None = None) -> list[dict]:
    """Walk benchmark/results/ and classify every artifact:

      ledger  — a JSONL/JSON record carrying the `schema` stamp (validated);
      legacy  — pre-ledger JSON without a `schema` stamp (accepted as-is);
      error   — unreadable/unparseable, or a stamped record that fails
                validation (the only hard failures).
    """
    root = (
        Path(results_dir)
        if results_dir is not None
        else _REPO_ROOT / "benchmark" / "results"
    )
    report: list[dict] = []
    for path in sorted(root.iterdir()):
        if path.suffix == ".jsonl":
            try:
                n = len(read_ledger(path))
                report.append({"file": path.name, "status": "ledger", "records": n})
            except ValueError as exc:
                report.append({"file": path.name, "status": "error", "detail": str(exc)})
            continue
        if path.suffix != ".json":
            continue
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except (OSError, json.JSONDecodeError) as exc:
            report.append({"file": path.name, "status": "error", "detail": str(exc)})
            continue
        if isinstance(doc, dict) and "schema" in doc:
            errors = validate_record(doc)
            if errors:
                report.append(
                    {"file": path.name, "status": "error", "detail": str(errors)}
                )
            else:
                report.append({"file": path.name, "status": "ledger", "records": 1})
        else:
            report.append({"file": path.name, "status": "legacy"})
    return report


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--path", default=None, help="ledger file (default: checked-in)")
    parser.add_argument(
        "--classify", action="store_true",
        help="classify every benchmark/results artifact instead",
    )
    args = parser.parse_args()
    if args.classify:
        report = classify_results_dir()
        for row in report:
            print(f"{row['status']:7s} {row['file']}" + (
                f"  ({row['detail']})" if "detail" in row else ""))
        errors = [r for r in report if r["status"] == "error"]
        return 1 if errors else 0
    records = read_ledger(args.path)
    for r in records:
        v = r.get("verdict", {}).get("verdict", "-")
        print(
            f"{r['git_rev'][:12]:12s} {r['kind']:16s} {v:10s} "
            f"ops/s={r['host']['calibration'].get('ops_per_s', 0):.0f}"
        )
    print(f"{len(records)} record(s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
