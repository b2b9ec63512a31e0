"""Trace tools: simnet profiler attribution, waterfall edge cases, and the
TELEMETRY_ADDR boot-line contract."""

from pathlib import Path

import pytest

from benchmark.local import parse_telemetry_addr
from narwhal_tpu import tracing
from tools.perf import simnet_profile

REPO = Path(__file__).resolve().parent.parent


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
