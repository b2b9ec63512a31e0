"""The process flight ring (tracing.flight): what the shared verify service,
the verifier stage, the commit walk and the loop heartbeat write into it, and
the ring's own discipline. Stub verifiers and pools: nothing here dispatches a
kernel."""

from __future__ import annotations

import asyncio
import collections
import threading
import time

import pytest

from narwhal_tpu import tracing
from narwhal_tpu.channels import Channel, Watch
from narwhal_tpu.fixtures import CommitteeFixture, make_optimal_certificates
from narwhal_tpu.tpu.verifier import SERVICE_ROWS, SERVICE_WAIT, VerifyService
from narwhal_tpu.types import Certificate, Vote

BUCKET = 32


Handle = collections.namedtuple("Handle", "items padded")


class StubVerifier:
    """Accepts everything; its handle says what it padded the dispatch to,
    as TpuVerifier's does: one bucket."""

    max_bucket = BUCKET

    def __init__(self, collect_s: float = 0.0):
        self.counts = collections.Counter()
        self.collect_s = collect_s
        self.fail_submit = False

    def submit(self, items):
        if self.fail_submit:
            raise RuntimeError("device lost")
        return Handle(list(items), BUCKET)

    submit_groups = submit

    def collect(self, handle):
        time.sleep(self.collect_s)
        return [True] * len(handle.items)

    collect_groups = collect


@pytest.fixture
def ring():
    tracing.new_generation()
    yield tracing.FLIGHT
    tracing.new_generation()


@pytest.fixture
def service():
    svc = VerifyService(StubVerifier(collect_s=0.005), max_batch=BUCKET, max_delay=0.002)
    yield svc
    svc.shutdown()


def records(kind: str) -> list[tuple]:
    return [r for r in tracing.flight_dump()["events"] if r.kind == kind]


def flush_records(service) -> list[tuple]:
    """The service posts a flush's verdicts first and writes its record
    after: wait the instant it takes for every counted flush to have one."""
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline:
        if len(records("flush")) == sum(service.flushes[k] for k in ("singles", "groups", "submit_failed")):
            break
        time.sleep(0.001)
    return records("flush")


def test_one_flush_record_per_flush_with_rows_and_stamps_in_order(ring, service, run):
    group = ([(b"k", b"m", b"r")] * 3, [1, 2, 3], 7)  # three signers: 6 rows

    async def burst():
        return await asyncio.gather(
            *(service.verify(b"k" * 32, b"m%d" % i, b"s" * 64) for i in range(5)),
            service.verify_aggregate(*group),
        )

    t_before = time.monotonic()
    assert run(burst()) == [True] * 6
    flushes = flush_records(service)
    assert len(flushes) == service.flushes["singles"] + service.flushes["groups"] >= 2
    assert [f.seq for f in flushes] == sorted(f.seq for f in flushes)
    by_lane = collections.defaultdict(list)
    for f in flushes:
        by_lane[f.lane].append(f)
    assert sum(f.entries for f in by_lane["singles"]) == 5 == sum(f.useful for f in by_lane["singles"])
    assert sum(f.entries for f in by_lane["groups"]) == 1 and sum(f.useful for f in by_lane["groups"]) == 6
    for f in flushes:
        assert f.padded == BUCKET and f.failure is None
        # One stamp closes a flush: collect returned and the verdicts were posted.
        assert t_before <= f.t_oldest <= f.t_seal <= f.t_dispatched <= f.t_posted
        assert 0 <= f.wait_sum <= f.entries * (f.t_seal - f.t_oldest) + 1e-9
        assert f.t_posted - f.t_dispatched >= 0.004  # the stub's readback
    # The deadline sealed them: the oldest entry waited about max_delay.
    assert max(f.t_seal - f.t_oldest for f in flushes) >= 0.002


def test_flush_sums_feed_the_two_scrape_series(ring, service, run):
    useful = SERVICE_ROWS.labels("singles", "useful").value
    padded = SERVICE_ROWS.labels("singles", "padded").value
    waits = {p: SERVICE_WAIT.labels(p).count for p in ("queue", "turnaround", "wake")}

    async def burst():
        await asyncio.gather(*(service.verify(b"k" * 32, b"m", b"s" * 64) for _ in range(4)))

    run(burst())
    n = len(flush_records(service))
    assert SERVICE_ROWS.labels("singles", "useful").value == useful + 4
    assert SERVICE_ROWS.labels("singles", "padded").value == padded + n * BUCKET
    assert SERVICE_WAIT.labels("queue").count == waits["queue"] + 4
    assert SERVICE_WAIT.labels("turnaround").count == waits["turnaround"] + n
    assert SERVICE_WAIT.labels("wake").count == waits["wake"] + 4


def test_a_failed_submit_is_recorded_with_its_failure(ring, service, run):
    service.verifier.fail_submit = True

    async def one():
        with pytest.raises(RuntimeError, match="device lost"):
            await service.verify(b"k" * 32, b"m", b"s" * 64)

    run(one())
    (f,) = flush_records(service)
    assert f.entries == 1 and f.padded == 0 and "device lost" in f.failure and f.failure.startswith("submit:")
    assert f.t_oldest <= f.t_seal <= f.t_dispatched == f.t_posted
    assert service.flushes["submit_failed"] == 1 and not records("wake")


def test_wake_is_one_record_per_flush_not_per_signature(ring, service, run):
    async def burst():
        await asyncio.gather(*(service.verify(b"k" * 32, b"m%d" % i, b"s" * 64) for i in range(12)))

    run(burst())
    flushes, wakes = flush_records(service), records("wake")
    assert len(wakes) == len(flushes) < 12
    posted = {f.seq: f for f in flushes}
    for w in wakes:
        assert w.entries == posted[w.seq].entries and w.t_posted == posted[w.seq].t_posted
        assert 0 <= w.lag_max <= w.lag_sum <= w.entries * w.lag_max + 1e-9
    assert sum(w.entries for w in wakes) == 12


def test_the_ring_is_bounded_and_a_new_generation_clears_it(monkeypatch):
    monkeypatch.setattr(tracing, "FLIGHT", collections.deque(maxlen=8))
    for i in range(20):
        tracing.flight("lag", float(i), float(i), 0, 0.0)
    dump = tracing.flight_dump()
    assert len(dump["events"]) == 8 == dump["ring_capacity"] and dump["events"][-1].due == 19.0
    assert tracing.flight_dump(max_events=3)["events"] == dump["events"][-3:]
    before = dump["anchor"]
    generation = tracing.new_generation()
    dump = tracing.flight_dump()
    assert dump["events"] == [] and dump["generation"] == generation
    # The pair that lays the ring on a wall clock is taken anew, together.
    assert dump["anchor"]["monotonic"] >= before["monotonic"]
    assert abs((dump["anchor"]["time_ns"] / 1e9 - dump["anchor"]["monotonic"])
               - (time.time() - time.monotonic())) < 0.05
    assert tracing.FLIGHT_RING >= 200 * 1000  # a traced run: a window under load, then minutes idle at ~1,000 a second


def test_the_layout_is_the_programs_and_a_site_out_of_step_raises(ring):
    """tracing.py owns the records' layout: a record reads by field name, a
    JSON dump of it is the plain row, and a site that passes another number
    of fields than FLIGHT_FIELDS gives its kind fails where it stands."""
    import json

    assert set(tracing.FLIGHT_FIELDS) == {
        "flush", "wake", "stage", "certify", "walk", "lag", "loop", "owner", "compile", "kernel_load",
        "wal_flush", "ingest_first"}
    tracing.flight("walk", "primary-x", 3, 5, 1.0, 1.5)
    (w,) = records("walk")
    assert w._fields == ("kind",) + tuple(tracing.FLIGHT_FIELDS["walk"].split())
    assert (w.kind, w.certs, w.outputs, w.t_done - w.t_start) == ("walk", 3, 5, 0.5)
    assert json.loads(json.dumps(tracing.flight_dump()["events"])) == [["walk", "primary-x", 3, 5, 1.0, 1.5]]
    with pytest.raises(TypeError):
        tracing.flight("walk", "primary-x", 3, 5, 1.0, 1.5, None)  # the field this PR dropped
    with pytest.raises(KeyError):
        tracing.flight("no_such_kind", 1.0)
    assert len(records("walk")) == 1


def test_appends_from_two_threads_lose_nothing(ring):
    def writer(who: int) -> None:
        for i in range(20_000):
            tracing.flight("wal_flush", who, i, 0.0)

    threads = [threading.Thread(target=writer, args=(w,)) for w in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = records("wal_flush")
    assert len(got) == 40_000
    for who in (0, 1):
        assert [r.flush_s for r in got if r.ops == who] == list(range(20_000))


def test_all_dumps_carry_the_process_ring(ring):
    tracing.flight("ingest_first", "worker-x", 1.5)
    process = [d for d in tracing.all_dumps() if d["node"] == "process"]
    assert len(process) == 1 and ("ingest_first", "worker-x", 1.5) in process[0]["events"]
    assert tracing.waterfall(tracing.all_dumps()) == {}  # other kinds stitch nothing


# ---------------------------------------------------------------------------
# the verifier stage
# ---------------------------------------------------------------------------


class StubPool:
    def __init__(self, verdict: bool = True, wait_s: float = 0.01):
        self.verdict, self.wait_s = verdict, wait_s
        self.singles = self.groups = 0

    async def verify(self, pk, msg, sig) -> bool:
        self.singles += 1
        await asyncio.sleep(self.wait_s)
        return self.verdict

    async def verify_aggregate(self, items, zs, s_agg) -> bool:
        self.groups += 1
        await asyncio.sleep(self.wait_s)
        return self.verdict


def stage_over(fx, pool, tracer=None):
    from narwhal_tpu.primary.verifier_stage import VerifierStage

    out = Channel(64)
    return VerifierStage(fx.committee, fx.worker_cache, pool, out, tracer=tracer), out


def compact_certificate(fx, header):
    signers = list(range(len(fx.authorities)))
    sigs = [Vote.for_header(header, fx.authorities[s].public, fx.authorities[s].keypair).signature
            for s in signers]
    idx = [fx.committee.index_of(fx.authorities[s].public) for s in signers]
    order = sorted(range(len(idx)), key=lambda i: idx[i])
    return Certificate.compact_from_votes(
        header, tuple(idx[i] for i in order), tuple(sigs[i] for i in order))


def test_a_stage_record_per_message_keyed_by_the_header_it_is_about(ring, run, monkeypatch):
    from narwhal_tpu import types
    from narwhal_tpu.bounded_cache import BoundedCache
    from narwhal_tpu.primary.verifier_stage import PreVerified

    monkeypatch.setattr(types, "_AGG_VERDICT_CACHE", BoundedCache(1 << 20))
    fx = CommitteeFixture(size=4)
    header = fx.header(author=1, round=1)
    vote = fx.votes(header)[0]
    cert = compact_certificate(fx, header)
    pool = StubPool()
    tracer = tracing.Tracer(node="primary-test", enabled=True, sample=1.0, ring=64)
    stage, out = stage_over(fx, pool, tracer)

    async def go():
        for msg in (header, vote, cert):
            await stage._verify(msg)
        await stage._verify("not a protocol message")  # passed through, no record
        return [out.try_recv() for _ in range(4)]

    forwarded = run(go())
    assert [type(m) for m in forwarded] == [PreVerified, PreVerified, PreVerified, str]
    stages = records("stage")
    assert [s.msg for s in stages] == ["header", "vote", "certificate"]
    for s in stages:
        assert s.key == header.digest.hex() and s.node == "primary-test" and s.outcome == "verified"
        assert s.t_verdict - s.t_in >= 0.009 and s.t_verdict <= s.t_forwarded
    assert pool.singles == 3 and pool.groups == 1  # header, vote, the certificate's header; one proof
    # With NARWHAL_TRACE on the same interval is a span under the header's
    # digest, and a certificate leaves the header -> certificate edge.
    spans = [e for e in tracer.events if e[0] == "span"]
    assert [(e[1], e[2]) for e in spans] == [("verify_stage", header.digest.hex())] * 3
    assert [e for e in tracer.events if e[0] == "link"] == [
        ("link", "verify_stage", header.digest.hex(), cert.digest.hex())]
    tracer.span("commit", cert.digest, 0.0, 1.0)  # what the consensus runner closes
    falls = tracing.waterfall([tracer.dump()])
    assert {"verify_stage", "commit"} <= set(falls[cert.digest.hex()]["stages"])

    # The same certificate again: its proof's verdict is cached now, so only
    # its header's signature is asked of the pool.
    run(stage._verify(cert))
    assert pool.groups == 1 and pool.singles == 4
    assert records("stage")[-1].outcome == "verified"


def test_a_message_nothing_is_asked_about_has_its_verdict_at_once(ring, run):
    fx = CommitteeFixture(size=4)
    pool = StubPool()
    stage, out = stage_over(fx, pool)
    genesis = Certificate.genesis(fx.committee)[0]
    run(stage._verify(genesis))
    (s,) = records("stage")
    assert s.msg == "certificate" and s.outcome == "nothing_to_ask" and s.node == ""
    assert s.t_verdict == s.t_in <= s.t_forwarded
    assert pool.singles == pool.groups == 0 and out.try_recv() is not None


def test_a_rejected_message_is_recorded_and_not_forwarded(ring, run):
    fx = CommitteeFixture(size=4)
    stage, out = stage_over(fx, StubPool(verdict=False))
    run(stage._verify(fx.header(author=2, round=1)))
    (s,) = records("stage")
    assert s.outcome == "rejected" and s.t_in < s.t_verdict <= s.t_forwarded and out.try_recv() is None


# ---------------------------------------------------------------------------
# the commit walk and the core's certify window
# ---------------------------------------------------------------------------


def test_every_call_into_the_ordering_engine_leaves_a_walk_record(ring, run):
    from narwhal_tpu.consensus import Bullshark
    from narwhal_tpu.consensus.runner import Consensus
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.types import ReconfigureNotification

    fx = CommitteeFixture(size=4)
    storage = NodeStorage(None)
    genesis = {c.digest for c in Certificate.genesis(fx.committee)}
    certs, _ = make_optimal_certificates(fx.committee, 1, 5, genesis)
    rx, tx_primary, tx_output = Channel(256), Channel(256), Channel(256)
    watch = Watch(ReconfigureNotification("boot"))
    consensus = Consensus(
        fx.committee, Bullshark(fx.committee, storage.consensus_store, 50),
        storage.consensus_store, storage.certificate_store, rx, tx_primary, tx_output, watch, 50,
    )

    async def go():
        task = consensus.spawn()
        for c in certs:
            await rx.send(c)
        committed = 0
        while committed < 5:  # the round-2 leader and its four round-1 parents
            await tx_output.recv()
            committed += 1
        while len(records("walk")) < len(certs):
            await asyncio.sleep(0.01)
        task.cancel()

    run(go())
    walks = records("walk")
    assert len(walks) == len(certs) == 20
    assert sum(w.certs for w in walks) == 20 and sum(w.outputs for w in walks) >= 5
    for w in walks:
        assert w.certs == 1 and w.t_start <= w.t_done and w.node == ""
    assert [w.t_start for w in walks] == sorted(w.t_start for w in walks)


def test_a_burst_is_one_call_into_a_device_engine_and_one_walk_record(ring, run):
    from narwhal_tpu.consensus.runner import Consensus
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.types import ReconfigureNotification

    class Engine:
        calls: list = []

        async def process_batch_async(self, state, index, certs):
            self.calls.append(len(certs))
            await asyncio.sleep(0.01)  # the readback, awaited: the record spans it
            return []

        async def process_certificate_async(self, state, index, cert):
            self.calls.append(1)
            return []

    fx = CommitteeFixture(size=4)
    storage = NodeStorage(None)
    consensus = Consensus(
        fx.committee, Engine(), storage.consensus_store, storage.certificate_store,
        Channel(8), Channel(8), Channel(8), Watch(ReconfigureNotification("boot")), 50,
    )
    certs = Certificate.genesis(fx.committee)

    async def go():
        await consensus._walk(certs[:3])
        await consensus._walk(certs[:1])

    run(go())
    burst, single = records("walk")
    assert Engine.calls == [3, 1] and (burst.certs, single.certs) == (3, 1)
    assert burst.t_done - burst.t_start >= 0.009 and burst.outputs == single.outputs == 0


# ---------------------------------------------------------------------------
# the loop heartbeat
# ---------------------------------------------------------------------------


def test_the_heartbeat_reads_a_planted_block_of_the_loop(ring, run):
    count = tracing.LOOP_LAG.labels().count

    async def go():
        tracing.heartbeat_acquire()
        tracing.heartbeat_acquire()  # a second node on the loop joins the one task
        assert len(tracing._HEARTBEATS) == 1
        try:
            await asyncio.sleep(0.1)
            # The planted stall, on purpose: nothing on this loop runs.
            time.sleep(0.2)  # lint: allow(no-blocking-in-async)
            await asyncio.sleep(0.1)
        finally:
            tracing.heartbeat_release()
            assert len(tracing._HEARTBEATS) == 1
            tracing.heartbeat_release()
        assert not tracing._HEARTBEATS

    run(go())
    lags = records("lag")
    late = [r.woke - r.due for r in lags if r.woke - r.due > tracing.HEARTBEAT_PERIOD]
    assert late and 0.18 <= max(late) < 0.5
    assert sum(r.quiet for r in lags) >= 3  # the quiet wakes before it rode along as a count
    assert tracing.LOOP_LAG.labels().count >= count + 8  # the histogram takes every wake


def test_a_node_under_simnet_starts_no_heartbeat():
    """Lateness is zero by construction on the virtual clock, and a 20 ms
    timer would multiply the events of a seeded scenario."""
    from narwhal_tpu.simnet import run_scenario

    tracing.new_generation()
    r = run_scenario(nodes=4, duration=1.0, load_rate=40)
    assert r.rounds and not tracing._HEARTBEATS
    kinds = collections.Counter(e.kind for e in tracing.flight_dump()["events"])
    assert kinds["lag"] == 0 and kinds["stage"] > 0 and kinds["walk"] > 0 and kinds["certify"] > 0
