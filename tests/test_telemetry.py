"""The telemetry plane: causal tracing, the flight recorder, and the
scrape/dump export surface.

Covers the observability contracts end to end:

- the checked-in metrics catalog gate (tools/metrics_catalog.json must match
  what the live registries register — rename/add/drop fails here, in review);
- StageTimer/span equivalence (stage histograms are DERIVED from span
  closes: one close site, two sinks, counts provably equal);
- deterministic digest sampling (every node traces the same certificates);
- scrape golden (render() parses back via parse_exposition, counters are
  monotone, histogram series fold under their base name);
- waterfall stitching across the digest chain (batch -> header -> cert);
- the Telemetry RPC pair over the simnet fabric (typed messages, zero
  sockets) and over a LIVE 4-node cluster (typed RPC + raw-bytes gRPC);
- trace determinism: same simnet seed => bit-identical flight dumps.
"""

import asyncio
import json

import pytest

from narwhal_tpu import tracing
from narwhal_tpu.metrics import Registry, parse_exposition
from narwhal_tpu.pacing import StageTimer
from narwhal_tpu.tracing import Tracer


# ---------------------------------------------------------------------------
# Satellite: the metrics-catalog gate
# ---------------------------------------------------------------------------


def test_metrics_catalog_matches_registries():
    """tools/metrics_catalog.json is the reviewed contract for the scrape
    surface: re-extract the live registries and diff. On drift, regenerate
    with `python -m tools.metrics_catalog --write` and review the diff."""
    from tools.metrics_catalog import extract_catalog, load_catalog

    live = {r["name"]: r for r in extract_catalog()}
    checked = {r["name"]: r for r in load_catalog()}
    undocumented = sorted(set(live) - set(checked))
    stale = sorted(set(checked) - set(live))
    changed = sorted(n for n in set(live) & set(checked) if live[n] != checked[n])
    assert not undocumented, f"undocumented metrics: {undocumented}"
    assert not stale, f"catalog lists dropped metrics: {stale}"
    assert not changed, f"metrics changed shape: {changed}"
    # The catalog is non-trivial and catalog rows carry the full contract.
    assert len(checked) >= 60
    assert all(
        {"name", "type", "labels", "help", "roles"} <= set(r) for r in checked.values()
    )


# ---------------------------------------------------------------------------
# Satellite: StageTimer histograms are derived from span closes
# ---------------------------------------------------------------------------


def _stage_setup(**tracer_kwargs):
    registry = Registry()
    hist = registry.histogram(
        "node_stage_latency_seconds", "per stage", labels=("stage",)
    )
    tracer = Tracer(node="n0", ring=256, **tracer_kwargs)
    t = [0.0]

    def clock():
        t[0] += 0.25
        return t[0]

    timer = StageTimer(hist, "propose", clock=clock, tracer=tracer)
    return registry, tracer, timer


def test_stage_timer_close_is_both_span_and_observation():
    """One close(), two sinks: with tracing enabled every stop() emits
    exactly one span AND one histogram observation — same count, and the
    histogram sum equals the summed span widths."""
    registry, tracer, timer = _stage_setup(enabled=True, sample=1.0)
    keys = [bytes([i]) * 32 for i in range(7)]
    for k in keys:
        timer.start(k)
        timer.stop(k)
    spans = [e for e in tracer.events if e[0] == "span" and e[1] == "propose"]
    assert len(spans) == 7
    assert registry.value("node_stage_latency_seconds", "propose") == 7
    hist_sum = registry.get("node_stage_latency_seconds").labels("propose").sum
    span_sum = sum(t1 - t0 for _, _, _, t0, t1, _ in spans)
    assert hist_sum == pytest.approx(span_sum)
    assert all(e[2] in {k.hex() for k in keys} for e in spans)


def test_stage_timer_disabled_or_unsampled_still_observes():
    """Trace off (or the key sampled out): the histogram keeps recording —
    metrics never degrade when tracing is disabled — and the ring stays
    free of spans."""
    for kwargs in (dict(enabled=False), dict(enabled=True, sample=0.0)):
        registry, tracer, timer = _stage_setup(**kwargs)
        for i in range(5):
            k = bytes([0xF0 + i]) * 32
            timer.start(k)
            timer.stop(k)
        assert registry.value("node_stage_latency_seconds", "propose") == 5
        assert not [e for e in tracer.events if e[0] == "span"]


def test_straggler_restart_cannot_mint_second_span():
    """The certify/commit inversion, pinned at the span layer: after a
    key's stage closes, a straggler re-start + re-stop must not emit a
    second span. With one span per key per stage, waterfall()'s
    earliest-t0 pick can never land on a late re-opened window, even
    after the true span would have been evicted from the ring."""
    registry, tracer, timer = _stage_setup(enabled=True, sample=1.0)
    key = bytes([7]) * 32
    timer.start(key)
    timer.stop(key)  # the true certify window
    timer.start(key)  # straggler vote re-delivers after the close
    assert timer.stop(key) is None
    spans = [e for e in tracer.events if e[0] == "span"]
    assert len(spans) == 1
    # And the surviving span is the FIRST window, not the straggler's.
    _, _, _, t0, t1, _ = spans[0]
    assert (t0, t1) == (0.25, 0.5)


def test_sampling_is_deterministic_and_digest_keyed():
    """sampled() reads only the digest's first 4 bytes: two independent
    tracers (two nodes) always agree, so sampled runs never produce
    partial waterfalls; sample=1.0 admits everything."""
    a = Tracer(node="a", enabled=True, sample=0.5, ring=16)
    b = Tracer(node="b", enabled=True, sample=0.5, ring=16)
    keys = [i.to_bytes(4, "big") + b"\x00" * 28 for i in range(0, 2**32, 2**28)]
    assert [a.sampled(k) for k in keys] == [b.sampled(k) for k in keys]
    assert a.sampled(b"\x00" * 32) and not a.sampled(b"\xff" * 32)
    full = Tracer(node="c", enabled=True, sample=1.0, ring=16)
    assert all(full.sampled(k) for k in keys)


# ---------------------------------------------------------------------------
# Scrape golden: render() -> parse_exposition round trip
# ---------------------------------------------------------------------------


def test_scrape_parses_and_counters_are_monotone():
    registry = Registry()
    c = registry.counter("worker_tx_received", "client transactions")
    g = registry.gauge("node_backpressure_level", "admission level")
    h = registry.histogram(
        "primary_propose_latency_seconds", "propose stage", labels=("stage",)
    )
    c.inc(3)
    g.set(0.25)
    h.labels("propose").observe(0.02)
    first = parse_exposition(registry.render())
    assert first["worker_tx_received"]["type"] == "counter"
    assert first["worker_tx_received"]["help"] == "client transactions"
    assert first["worker_tx_received"]["samples"][""] == 3.0
    assert first["node_backpressure_level"]["samples"][""] == 0.25
    # Histogram series fold under the base name: _bucket/_sum/_count keys.
    hsamples = first["primary_propose_latency_seconds"]["samples"]
    assert any(k.startswith("_bucket") for k in hsamples)
    assert hsamples['_count{stage="propose"}'] == 1.0
    # Monotonicity across scrapes: the counter only moves up.
    c.inc(2)
    h.labels("propose").observe(0.04)
    second = parse_exposition(registry.render())
    assert second["worker_tx_received"]["samples"][""] == 5.0
    assert second["primary_propose_latency_seconds"]["samples"][
        '_count{stage="propose"}'
    ] == 2.0
    for name, entry in first.items():
        if entry["type"] != "counter":
            continue
        for series, value in entry["samples"].items():
            assert second[name]["samples"][series] >= value


# ---------------------------------------------------------------------------
# Waterfall stitching across the digest chain (pure unit)
# ---------------------------------------------------------------------------


def test_waterfall_stitches_batch_header_cert_chain():
    """Spans recorded under three different causal keys (batch digest,
    header digest, certificate digest) merge into ONE waterfall under the
    certificate via the recorded link chain — the zero-wire-bytes trace
    context."""
    batch, header, cert = b"\x01" * 32, b"\x02" * 32, b"\x03" * 32
    t = Tracer(node="n0", enabled=True, sample=1.0, ring=64)
    t.span("seal", batch, 0.0, 0.1)
    t.link("propose", batch, header)
    t.span("propose", header, 0.1, 0.3)
    t.link("certify", header, cert)
    t.span("certify", header, 0.3, 0.5)
    t.span("commit", cert, 0.5, 0.8)
    t.span("execute", cert, 0.8, 0.9)
    falls = tracing.waterfall([t.dump()])
    assert set(falls) == {cert.hex()}
    stages = falls[cert.hex()]["stages"]
    assert set(stages) == {"seal", "propose", "certify", "commit", "execute"}
    assert stages["seal"] == [0.0, 0.1]
    assert stages["execute"] == [0.8, 0.9]
    assert set(falls[cert.hex()]["ancestors"]) == {batch.hex(), header.hex()}
    # The summary table sees every span.
    pct = tracing.stage_percentiles([t.dump()])
    assert set(pct) == {"seal", "propose", "certify", "commit", "execute"}
    assert pct["commit"]["count"] == 1
    assert pct["commit"]["p50_ms"] == pytest.approx(300.0)


def test_anomaly_archives_every_live_ring():
    """on_anomaly snapshots all live tracers into the bounded archive,
    tagged with the reason — what oracles and the commit-stall detector
    call so the pytest hook can attach evidence post-teardown."""
    t1 = Tracer(node="p0", enabled=True, sample=1.0, ring=32)
    t2 = Tracer(node="w0", enabled=True, sample=1.0, ring=32)
    t1.instant("backpressure", level=0.5)
    dumps = tracing.on_anomaly("commit_stall test")
    assert {d["node"] for d in dumps} >= {"p0", "w0"}
    archived = [d for d in tracing.ARCHIVE if d.get("anomaly") == "commit_stall test"]
    assert {d["node"] for d in archived} >= {"p0", "w0"}
    assert "commit_stall test" in t1.anomalies and "commit_stall test" in t2.anomalies
    # all_dumps = archive + live; entries are self-contained JSON.
    json.dumps(tracing.all_dumps(max_events=50), sort_keys=True)
    tracing.clear_archive()
    assert len(tracing.ARCHIVE) == 0


def test_tracer_registry_is_scoped_per_cluster_incarnation():
    """A tracer from a previous cluster incarnation kept alive (a leaked
    ring, a node a test forgot to drop) must not bleed spans into the next
    incarnation's live view: successive in-process clusters reuse node
    labels and — with seeded fixtures — certificate digests, so without
    generation scoping `live_dumps()` merged a prior cluster's spans into
    the next one's waterfalls (the live-cluster waterfall test's flake)."""
    from narwhal_tpu.cluster import Cluster

    stale = Tracer(node="primary-0", enabled=True, sample=1.0, ring=32)
    stale.span("commit", b"\x07" * 32, 0.0, 1.0)
    assert any(
        d["node"] == "primary-0" and d["events"] for d in tracing.live_dumps()
    )

    # Constructing the cluster opens the new incarnation; no boot needed.
    Cluster(size=4, workers=1)
    assert not any(
        d["node"] == "primary-0" and d["events"] for d in tracing.live_dumps()
    )
    # Anomaly snapshots are scoped the same way: the stale ring is neither
    # archived nor tagged.
    tracing.on_anomaly("incarnation test")
    assert "incarnation test" not in stale.anomalies
    tracing.clear_archive()


# ---------------------------------------------------------------------------
# The Telemetry RPC pair over the simnet fabric (zero sockets)
# ---------------------------------------------------------------------------


def test_telemetry_rpc_over_simnet_fabric():
    """Scrape + flight-dump served by ConsensusApi through the in-memory
    fabric: the surface the simnet observability contract requires (grpc
    binds real sockets and is skipped under simnet)."""
    from narwhal_tpu.messages import (
        FlightDumpMsg,
        TelemetryScrapeMsg,
    )
    from narwhal_tpu.network import NetworkClient, transport
    from narwhal_tpu.primary.api_server import ConsensusApi
    from narwhal_tpu.simnet import LinkSpec, SimFabric, SimLoop

    loop = SimLoop()
    asyncio.set_event_loop(loop)
    fabric = SimFabric(seed=1, default_link=LinkSpec(latency=0.005))
    transport.install(fabric)
    fabric.register_node("api-node", ["telemetry-host:1"])

    registry = Registry()
    registry.counter("consensus_commits", "committed certs").inc(4)
    tracer = Tracer(node="primary-test", enabled=True, sample=1.0, ring=64)
    tracer.span("commit", b"\x07" * 32, 1.0, 1.5)
    tracer.instant("backpressure", level=0.1)
    api = ConsensusApi(
        b"\x00" * 32, None, None, None, registry=registry, tracer=tracer
    )

    async def main():
        await api.spawn("telemetry-host:1")
        client = NetworkClient()
        try:
            scrape = await client.request(
                "telemetry-host:1", TelemetryScrapeMsg(), timeout=5.0
            )
            assert scrape.text == registry.render()
            parsed = parse_exposition(scrape.text)
            assert parsed["consensus_commits"]["samples"][""] == 4.0

            resp = await client.request(
                "telemetry-host:1", FlightDumpMsg(), timeout=5.0
            )
            dump = json.loads(resp.payload.decode())
            assert dump["node"] == "primary-test"
            kinds = [e[0] for e in dump["events"]]
            assert "span" in kinds and "instant" in kinds

            # max_events bounds the reply payload from the requester side.
            bounded = await client.request(
                "telemetry-host:1", FlightDumpMsg(max_events=1), timeout=5.0
            )
            assert len(json.loads(bounded.payload.decode())["events"]) == 1
        finally:
            client.close()
            await api.shutdown()

    try:
        loop.run_until_complete(main())
    finally:
        transport.uninstall()
        for t in asyncio.all_tasks(loop):
            t.cancel()
        loop.run_until_complete(asyncio.sleep(0))
        asyncio.set_event_loop(None)
        loop.close()


# ---------------------------------------------------------------------------
# Simnet: same seed => bit-identical traced event log; waterfalls exist
# ---------------------------------------------------------------------------


def test_simnet_trace_determinism_and_waterfall(monkeypatch):
    """With tracing on, a seeded scenario's per-node flight dumps are
    bit-identical across runs (all span timestamps ride the virtual
    clock), and the dumps reconstruct end-to-end commit waterfalls."""
    from narwhal_tpu.config import Parameters
    from narwhal_tpu.simnet import FaultPlan, LinkSpec, run_scenario

    monkeypatch.setenv("NARWHAL_TRACE", "1")
    monkeypatch.setenv("NARWHAL_TRACE_SAMPLE", "1.0")
    params = Parameters(
        max_header_delay=0.1,
        max_batch_delay=0.05,
        header_delay_floor=0.05,
        batch_delay_floor=0.02,
    )

    def go():
        return run_scenario(
            nodes=4,
            duration=2.0,
            load_rate=80,
            parameters=params,
            plan=FaultPlan(seed=11, default_link=LinkSpec(latency=0.002)),
        )

    a = go()
    b = go()
    assert a.flight_dumps, "scenario captured no flight dumps"
    assert all(d["trace_enabled"] for d in a.flight_dumps)
    blob_a = json.dumps(a.flight_dumps, sort_keys=True)
    blob_b = json.dumps(b.flight_dumps, sort_keys=True)
    assert blob_a == blob_b, "same seed must produce a bit-identical trace"

    falls = tracing.waterfall(a.flight_dumps)
    committed = {
        k: v["stages"]
        for k, v in falls.items()
        if {"propose", "certify", "commit"} <= set(v["stages"])
    }
    assert committed, f"no full propose->certify->commit waterfall in {len(falls)}"
    # At least one committed certificate carried payload: its waterfall
    # reaches back through the link chain to a worker's seal span.
    assert any("seal" in stages for stages in committed.values())
    # Stage ordering is causal within every committed waterfall.
    for stages in committed.values():
        assert stages["propose"][0] <= stages["certify"][1] <= stages["commit"][1]
    pct = tracing.stage_percentiles(a.flight_dumps)
    assert {"propose", "certify", "commit"} <= set(pct)
    assert all(v["count"] > 0 for v in pct.values())


# ---------------------------------------------------------------------------
# Live 4-node cluster: the acceptance waterfall + both export surfaces
# ---------------------------------------------------------------------------


def test_live_cluster_scrape_dump_and_waterfall(run, monkeypatch):
    """Boot a real 4-node committee with tracing on, push transactions to
    execution, then reconstruct one certificate's end-to-end waterfall
    purely from the telemetry surface: typed-RPC Telemetry.Scrape (counters
    visible, commit count non-zero) + Telemetry.DumpFlightRecorder from
    every node, and the raw-bytes gRPC mirror of both."""
    import grpc

    from narwhal_tpu.cluster import Cluster
    from narwhal_tpu.messages import (
        FlightDumpMsg,
        SubmitTransactionStreamMsg,
        TelemetryScrapeMsg,
    )
    from narwhal_tpu.network import NetworkClient

    monkeypatch.setenv("NARWHAL_TRACE", "1")
    monkeypatch.setenv("NARWHAL_TRACE_SAMPLE", "1.0")

    async def scenario():
        cluster = Cluster(size=4, workers=1)
        await cluster.start()
        client = NetworkClient()
        channel = None
        try:
            await cluster.assert_progress(commit_threshold=2, timeout=30.0)
            txs = tuple(
                b"\x02" + i.to_bytes(8, "big") + b"\x6b" * 55 for i in range(64)
            )
            await client.request(
                cluster.authorities[0].worker_transactions_address(0),
                SubmitTransactionStreamMsg(txs),
            )
            out = cluster.authorities[0].primary.tx_execution_output
            await asyncio.wait_for(out.recv(), 30.0)

            # -- scrape over the typed RPC plane --------------------------
            a0 = cluster.authorities[0]
            scrape = await client.request(
                a0.primary.api_address, TelemetryScrapeMsg(), timeout=10.0
            )
            parsed = parse_exposition(scrape.text)
            assert parsed["consensus_stage_latency_seconds"]["samples"][
                '_count{stage="commit"}'
            ] > 0

            # -- flight dumps over the typed RPC plane, all four nodes ----
            dumps = []
            for a in cluster.authorities:
                resp = await client.request(
                    a.primary.api_address, FlightDumpMsg(), timeout=10.0
                )
                dumps.append(json.loads(resp.payload.decode()))
            # Worker rings hold the seal spans; workers expose no RPC
            # listener of their own, so take their dumps in-process.
            dumps.extend(
                w.tracer.dump() for a in cluster.authorities for w in a.workers.values()
            )

            # The acceptance bar: one certificate's end-to-end waterfall,
            # reconstructed purely from dumped rings. Poll briefly — the
            # execute span closes a beat after the execution output pops.
            deadline = asyncio.get_event_loop().time() + 30.0
            want = {"seal", "propose", "certify", "commit", "execute"}
            while True:
                falls = tracing.waterfall(dumps)
                full = {
                    k: v for k, v in falls.items() if want <= set(v["stages"])
                }
                if full:
                    break
                if asyncio.get_event_loop().time() > deadline:
                    stages = {k: sorted(v["stages"]) for k, v in falls.items()}
                    raise AssertionError(f"no full waterfall yet: {stages}")
                await asyncio.sleep(0.5)
                dumps = tracing.live_dumps()
            cert, entry = next(iter(full.items()))
            s = entry["stages"]
            assert s["seal"][0] <= s["propose"][1] <= s["certify"][1]
            # commit and execute happen on every validator, and the stitched
            # entry keeps each stage's earliest-opening window, so its two
            # may come from different nodes (the node that took the
            # certificate in first need not be the first to finish). A
            # validator executes only after IT committed: the inequality is
            # held against each validator's own windows.
            held = 0
            for d in dumps:
                own = tracing.waterfall([d]).get(cert, {}).get("stages", {})
                if "commit" in own and "execute" in own:
                    assert s["certify"][0] <= own["commit"][1] <= own["execute"][1]
                    held += 1
            assert held >= 1
            # Across validators: the certificate is certified before any
            # commit window opens, and no execute ends before that.
            assert s["certify"][0] <= s["commit"][1]
            assert s["certify"][1] <= s["commit"][0] <= s["execute"][1]

            # -- the gRPC mirror: raw-bytes unary, any-language clients ---
            addr = a0.primary.grpc_api_address
            if addr:  # grpc plane is mounted outside simnet
                channel = grpc.aio.insecure_channel(addr)
                raw = lambda m: channel.unary_unary(  # noqa: E731
                    f"/narwhal.Telemetry/{m}",
                    request_serializer=lambda b: b,
                    response_deserializer=lambda b: b,
                )
                text = (await raw("Scrape")(b"")).decode()
                gparsed = parse_exposition(text)
                assert gparsed["consensus_stage_latency_seconds"]["samples"][
                    '_count{stage="commit"}'
                ] > 0
                payload = await raw("DumpFlightRecorder")(
                    (50).to_bytes(4, "little")
                )
                gdump = json.loads(payload.decode())
                assert gdump["node"].startswith("primary-")
                assert len(gdump["events"]) <= 50
        finally:
            if channel is not None:
                await channel.close()
            client.close()
            await cluster.shutdown()

    run(scenario(), timeout=120.0)
