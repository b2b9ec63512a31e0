"""Perf observatory gates: calibration probe, commit-keyed ledger schema,
A/B verdict logic, simnet profiler attribution,
waterfall edge cases, and the TELEMETRY_ADDR boot-line contract.

The ledger schema tests here ARE the tier-1 gate the ledger docstring
promises: an unregistered record shape (new field, new kind, malformed
line) fails here instead of silently forking benchmark/results/."""

import json
import time
from pathlib import Path

import pytest

from benchmark import ab
from benchmark.local import parse_telemetry_addr
from narwhal_tpu import tracing
from tools.perf import calibrate, ledger, simnet_profile

REPO = Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------- calibrate


def test_calibration_probe_shape():
    probe = calibrate.calibration_probe(budget_s=0.02)
    for key in (
        "unix_time", "probe_s", "chain_ops", "ops_per_s",
        "loadavg_1m", "loadavg_5m", "loadavg_15m", "cpu_count",
    ):
        assert key in probe
    assert probe["ops_per_s"] > 0
    assert probe["chain_ops"] >= 1
    assert probe["probe_s"] == pytest.approx(0.02, rel=2.0)
    json.dumps(probe)  # JSON-ready by contract


def test_drift_is_symmetric_and_guards_nonpositive():
    a = {"ops_per_s": 100.0}
    b = {"ops_per_s": 150.0}
    assert calibrate.drift(a, a) == 0.0
    assert calibrate.drift(a, b) == pytest.approx(0.5)
    assert calibrate.drift(b, a) == pytest.approx(0.5)
    assert calibrate.drift(a, {"ops_per_s": 0.0}) == float("inf")
    assert calibrate.drift({}, b) == float("inf")


def test_host_context_snapshot():
    ctx = calibrate.host_context(probe_budget_s=0.01)
    assert "calibration" in ctx and ctx["calibration"]["ops_per_s"] > 0
    assert isinstance(ctx["concurrent"], list)
    # This test runs under pytest, so the self-excluding scan must not
    # count US — but a concurrent suite (the known flake source) would
    # flip the bool. Only the type is pinnable here.
    assert isinstance(ctx["concurrent_pytest"], bool)


# ------------------------------------------------------------------ ledger


def _valid_record(**overrides):
    record = {
        "schema": ledger.SCHEMA,
        "kind": "microbench",
        "git_rev": "deadbeef",
        "recorded_unix": time.time(),
        "host": {"calibration": {"ops_per_s": 1000.0}},
        "payload": {"x": 1},
    }
    record.update(overrides)
    return record


def test_ledger_accepts_valid_record():
    assert ledger.validate_record(_valid_record()) == []


def test_ledger_schema_is_closed():
    errors = ledger.validate_record(_valid_record(extra_field=1))
    assert any("unregistered field 'extra_field'" in e for e in errors)


def test_ledger_rejects_unregistered_kind():
    errors = ledger.validate_record(_valid_record(kind="bogus_bench"))
    assert any("unregistered kind" in e for e in errors)


def test_ledger_rejects_missing_required_and_bad_types():
    record = _valid_record()
    del record["git_rev"]
    record["payload"] = "not a dict"
    errors = ledger.validate_record(record)
    assert any("missing required field 'git_rev'" in e for e in errors)
    assert any("field 'payload'" in e for e in errors)
    assert ledger.validate_record("not even a dict")
    assert ledger.validate_record(
        _valid_record(schema="narwhal-perf-ledger/999")
    )


def test_ledger_requires_host_calibration():
    errors = ledger.validate_record(_valid_record(host={"loadavg": 1.0}))
    assert any("calibration" in e for e in errors)


def test_ledger_pins_verdict_vocabulary():
    ok = _valid_record(verdict={"verdict": "null"})
    assert ledger.validate_record(ok) == []
    bad = _valid_record(verdict={"verdict": "maybe-faster"})
    assert any("verdict.verdict" in e for e in ledger.validate_record(bad))


def test_ledger_append_read_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setenv("NARWHAL_PERF_LEDGER_PATH", str(path))
    monkeypatch.setenv("NARWHAL_PERF_LEDGER", "1")
    rec = ledger.append(
        "microbench", {"rows": 3}, argv=["--fast"], note="unit test"
    )
    assert rec is not None and rec["kind"] == "microbench"
    ledger.append("ab", {"legs": []}, verdict={"verdict": "win"})
    # "fuzz" is a registered kind (deliberate KINDS extension): one record
    # per FaultPlan-fuzzer campaign, payload = the campaign summary.
    ledger.append("fuzz", {"count": 3, "ok": True, "failures": []})
    records = ledger.read_ledger(path)
    assert [r["kind"] for r in records] == ["microbench", "ab", "fuzz"]
    assert records[0]["argv"] == ["--fast"]
    assert records[1]["verdict"]["verdict"] == "win"
    # Every appended record carries the host calibration it measured under.
    assert all(r["host"]["calibration"]["ops_per_s"] > 0 for r in records)


def test_ledger_disabled_appends_nothing(tmp_path, monkeypatch):
    path = tmp_path / "ledger.jsonl"
    monkeypatch.setenv("NARWHAL_PERF_LEDGER_PATH", str(path))
    monkeypatch.setenv("NARWHAL_PERF_LEDGER", "0")
    assert ledger.append("microbench", {}) is None
    assert not path.exists()


def test_ledger_build_refuses_invalid():
    with pytest.raises(ValueError, match="unregistered kind"):
        ledger.build_record("bogus_bench", {})


def test_ledger_read_raises_on_malformed_line(tmp_path):
    path = tmp_path / "ledger.jsonl"
    path.write_text(json.dumps(_valid_record()) + "\nnot json\n")
    with pytest.raises(ValueError, match="malformed ledger line"):
        ledger.read_ledger(path)


def test_checked_in_ledger_is_schema_valid():
    """The gate over the real artifact: every line of the checked-in
    ledger must parse and validate (read_ledger raises otherwise)."""
    records = ledger.read_ledger(ledger.DEFAULT_PATH)
    for r in records:
        assert r["schema"] == ledger.SCHEMA
        assert r["kind"] in ledger.KINDS


def test_legacy_results_tolerated():
    """Pre-ledger benchmark/results/*.json stay loadable: the classifier
    must tag them `legacy`, never `error` — and stamped records must
    validate. Zero hard failures over the whole directory."""
    report = ledger.classify_results_dir()
    assert report, "benchmark/results/ should not be empty"
    errors = [r for r in report if r["status"] == "error"]
    assert errors == []
    assert all(r["status"] in {"ledger", "legacy"} for r in report)


# ------------------------------------------------------------ benchmark.ab


def test_extract_metric_paths():
    doc = {"a": {"b": 2.5}, "flat": 7}
    assert ab.extract_metric(doc, "a.b", None) == 2.5
    assert ab.extract_metric(doc, "flat", None) == 7.0
    rows = [{"bench": "x", "v": 1}, {"bench": "y", "v": 2}]
    assert ab.extract_metric(rows, "v", None) == 2.0  # last row
    assert ab.extract_metric(rows, "v", "bench=x") == 1.0
    with pytest.raises(KeyError):
        ab.extract_metric(rows, "v", "bench=zzz")
    with pytest.raises(KeyError):
        ab.extract_metric(doc, "a.missing", None)
    with pytest.raises(TypeError):
        ab.extract_metric({"s": "fast"}, "s", None)


def test_same_side_band():
    assert ab.same_side_band([100.0]) == float("inf")
    assert ab.same_side_band([100.0, 110.0]) == pytest.approx(10 / 105)
    assert ab.same_side_band([0.0, 0.0]) == float("inf")


_QUIET = [{"ops_per_s": 1000.0}, {"ops_per_s": 1010.0}]


def test_decide_null_on_aa():
    v = ab.decide([100.0, 102.0], [101.0, 100.0], _QUIET)
    assert v["verdict"] == "null"
    assert v["noise_band"] >= 0.02


def test_decide_win_and_regression():
    v = ab.decide([100.0, 101.0], [140.0, 141.0], _QUIET)
    assert v["verdict"] == "win"
    v = ab.decide([100.0, 101.0], [60.0, 61.0], _QUIET)
    assert v["verdict"] == "regression"


def test_decide_lower_is_better_flips_sides():
    latency_drop = ab.decide(
        [100.0, 101.0], [60.0, 61.0], _QUIET, lower_is_better=True
    )
    assert latency_drop["verdict"] == "win"
    latency_rise = ab.decide(
        [100.0, 101.0], [140.0, 141.0], _QUIET, lower_is_better=True
    )
    assert latency_rise["verdict"] == "regression"


def test_decide_refuses_verdict_on_calibration_drift():
    cliff = [{"ops_per_s": 1000.0}, {"ops_per_s": 100.0}]
    v = ab.decide([100.0, 101.0], [200.0, 201.0], cliff)
    assert v["verdict"] == "no-verdict"
    assert "capacity swung" in v["reason"]


def test_decide_refuses_verdict_without_repeats():
    v = ab.decide([100.0], [140.0], _QUIET)
    assert v["verdict"] == "no-verdict"
    assert ab.decide([], [1.0], _QUIET)["verdict"] == "no-verdict"


def test_decide_noise_band_swallows_small_delta():
    # Same-side spread of 20% must swallow a 10% head/base delta.
    v = ab.decide([100.0, 120.0], [110.0, 132.0], _QUIET)
    assert v["verdict"] == "null"


# ------------------------------------------------------ simnet profiler


def test_simnet_profile_classify_table():
    cases = {
        ("narwhal_tpu/simnet/fabric.py", "_deliver"): "fabric_deliver",
        ("narwhal_tpu/simnet/fabric.py", "append"): "event_log",
        ("narwhal_tpu/simnet/clock.py", "run_until"): "sim_clock",
        ("narwhal_tpu/network/auth.py", "seal"): "auth_aead",
        ("narwhal_tpu/crypto.py", "verify"): "signing",
        ("narwhal_tpu/network/rpc.py", "send"): "wire_rpc",
        ("narwhal_tpu/codec.py", "encode"): "codec",
        ("narwhal_tpu/primary/core.py", "process"): "protocol",
        ("/usr/lib/python3.11/asyncio/events.py", "run"): "asyncio_loop",
        ("/some/random/lib.py", "f"): "other",
    }
    for (filename, func), want in cases.items():
        assert simnet_profile.classify(filename, func) == want, (filename, func)


@pytest.mark.slow
def test_simnet_profile_attributes_hot_path():
    report = simnet_profile.profile_scenario(
        nodes=4, duration=1.5, load_rate=60, seed=11
    )
    assert report["total_self_s"] > 0
    # The acceptance floor: the component table must name >=80% of the
    # self time, or it has drifted from the code.
    assert report["attributed_share"] >= 0.8, report["components"]
    components = report["components"]
    # Ranked by share, descending; shares decompose (sum to ~1 with other).
    shares = [c["share"] for c in components]
    assert shares == sorted(shares, reverse=True)
    assert sum(shares) == pytest.approx(1.0, abs=0.01)
    counters = report["scenario"]["fabric_counters"]
    assert counters["delivers"] > 0 and counters["bytes_delivered"] > 0
    assert counters["transmits"] >= counters["delivers"]
    table = simnet_profile.render_table(report)
    assert "fabric" in table


# ------------------------------------------------- waterfall edge cases


def _span(stage, key, t0, t1):
    return ("span", stage, key, t0, t1, None)


def test_waterfall_orphan_span_becomes_root():
    wf = tracing.waterfall([{"events": [_span("seal", "aa", 0.0, 1.0)]}])
    assert "aa" in wf and wf["aa"]["stages"]["seal"] == [0.0, 1.0]
    assert wf["aa"]["ancestors"] == []


def test_waterfall_missing_link_yields_partial_chain():
    # The batch->header link dump was lost (node down): the certificate
    # still surfaces, just without the batch's seal stage.
    events = [
        _span("seal", "batch1", 0.0, 1.0),
        _span("commit", "cert1", 2.0, 3.0),
    ]
    wf = tracing.waterfall([{"events": events}])
    assert "cert1" in wf and "seal" not in wf["cert1"]["stages"]
    assert "batch1" in wf  # orphan root, not silently dropped


def test_waterfall_self_link_is_ignored():
    events = [
        ("link", "propose", "aa", "aa"),
        _span("commit", "aa", 0.0, 1.0),
    ]
    wf = tracing.waterfall([{"events": events}])
    assert wf["aa"]["ancestors"] == []


def test_waterfall_cyclic_links_terminate():
    # Two nodes disagreeing about link direction: a <-> b. Must neither
    # hang nor blow the stack; each root sees the other as lineage once.
    events = [
        ("link", "propose", "aa", "bb"),
        ("link", "propose", "bb", "aa"),
        _span("commit", "aa", 0.0, 1.0),
        _span("commit", "bb", 0.0, 1.0),
        _span("seal", "cc", 0.0, 0.5),
    ]
    wf = tracing.waterfall([{"events": events}])
    assert wf["aa"]["ancestors"] == ["bb"]
    assert wf["bb"]["ancestors"] == ["aa"]
    assert "cc" in wf


def test_waterfall_skips_malformed_events():
    events = [
        ("span", "seal"),            # too short for a span
        ("link", "propose", "aa"),   # too short for a link
        ("span",),                   # degenerate
        _span("commit", "dd", 0.0, 1.0),
    ]
    wf = tracing.waterfall([{"events": events}])
    assert list(wf) == ["dd"]


def test_waterfall_keeps_earliest_opening_span():
    events = [
        _span("seal", "aa", 5.0, 6.0),
        _span("seal", "aa", 1.0, 2.0),
        _span("commit", "aa", 7.0, 8.0),
    ]
    wf = tracing.waterfall([{"events": events}])
    assert wf["aa"]["stages"]["seal"] == [1.0, 2.0]


# --------------------------------------------- TELEMETRY_ADDR contract


def test_parse_telemetry_addr_units():
    assert parse_telemetry_addr("") is None
    assert parse_telemetry_addr("INFO nothing machine readable\n") is None
    assert parse_telemetry_addr("TELEMETRY_ADDR=127.0.0.1:9\n") == "127.0.0.1:9"
    # Last occurrence wins (a restarted node rebinds).
    two = "TELEMETRY_ADDR=127.0.0.1:9\nnoise\nTELEMETRY_ADDR=127.0.0.1:10\n"
    assert parse_telemetry_addr(two) == "127.0.0.1:10"
    # Empty value = no gRPC plane mounted.
    assert parse_telemetry_addr("TELEMETRY_ADDR=\n") is None
    # Leading whitespace tolerated; the '=' split keeps IPv6-ish colons.
    assert parse_telemetry_addr("  TELEMETRY_ADDR=[::1]:50\n") == "[::1]:50"


def test_parse_telemetry_addr_real_boot_log():
    """Pin the contract against a REAL primary boot log (captured from
    `python -m narwhal_tpu run ... primary` — see tests/artifacts/). If
    the node stops printing the machine-readable line, this fails before
    benchmark/local.py silently loses its telemetry scrapes."""
    log = (REPO / "tests" / "artifacts" / "primary_boot.log").read_text()
    addr = parse_telemetry_addr(log)
    assert addr is not None
    host, _, port = addr.rpartition(":")
    assert host and int(port) > 0
    # The legacy human log line also present -> both planes agree.
    assert f"gRPC public API listening on {addr}" in log
