"""Where the shared verify service runs a flush: sealed, packed and
dispatched on the event loop that asked (no submit thread), collected on
one thread, never blocking a loop on the in-flight bound. A stub verifier:
nothing here dispatches a kernel."""

from __future__ import annotations

import asyncio
import collections
import threading
import time

import pytest

from narwhal_tpu import tracing
from narwhal_tpu.tpu.verifier import SERVICE_EVENTS, VerifyService

BUCKET = 4
Handle = collections.namedtuple("Handle", "items padded")
ITEM = (b"k" * 32, b"m", b"s" * 64)


class StubVerifier:
    """Accepts everything, remembers which thread ran each half."""

    max_bucket = BUCKET

    def __init__(self, collect_s: float = 0.0, fail_submits: int = 0):
        self.counts = collections.Counter()
        self.collect_s = collect_s
        self.fail_submits = fail_submits
        self.submit_threads: list = []
        self.collect_threads: list = []
        self.inflight = self.inflight_max = 0

    def submit(self, items):
        self.submit_threads.append(threading.current_thread())
        if self.fail_submits:
            self.fail_submits -= 1
            raise RuntimeError("device lost")
        self.inflight += 1
        self.inflight_max = max(self.inflight_max, self.inflight)
        return Handle(list(items), BUCKET)

    submit_groups = submit

    def collect(self, handle):
        self.collect_threads.append(threading.current_thread())
        time.sleep(self.collect_s)
        self.inflight -= 1
        return [True] * len(handle.items)

    collect_groups = collect


def service(**kw) -> VerifyService:
    stub = StubVerifier(kw.pop("collect_s", 0.0), kw.pop("fail_submits", 0))
    return VerifyService(stub, max_batch=BUCKET, **{"max_delay": 0.002, **kw})


def flush_records(svc) -> list:
    """A flush's record is written after its verdicts are posted: wait the
    instant it takes for every counted flush to have one."""
    want = sum(svc.flushes[k] for k in ("singles", "groups", "submit_failed"))
    deadline = time.monotonic() + 2.0
    while True:
        got = [r for r in tracing.flight_dump()["events"] if r.kind == "flush"]
        if len(got) >= want or time.monotonic() > deadline:
            return got
        time.sleep(0.001)


@pytest.fixture
def ring():
    tracing.new_generation()
    yield
    tracing.new_generation()


def test_a_flush_is_packed_and_dispatched_on_the_loop_that_asked(ring, run):
    svc = service(collect_s=0.002)
    try:
        async def burst():
            loop_thread = threading.current_thread()
            got = await asyncio.gather(
                *(svc.verify(*ITEM) for _ in range(3)),
                svc.verify_aggregate([(b"k", b"m", b"r")] * 2, [1, 2], 7),
            )
            return loop_thread, got

        loop_thread, got = run(burst())
        assert got == [True] * 4
        stub = svc.verifier
        assert stub.submit_threads and set(stub.submit_threads) == {loop_thread}
        assert {t.name for t in stub.collect_threads} == {"verify-collect"}
        names = [t.name for t in threading.enumerate()]
        assert "verify-submit" not in names and "verify-collect" in names
        assert svc.flushes["singles"] >= 1 and svc.flushes["groups"] == 1 and svc.flushes["deferred"] == 0
    finally:
        assert svc.shutdown()
    assert not any(t.name == "verify-collect" and t.is_alive() for t in stub.collect_threads)


def test_a_full_bucket_seals_at_once_and_the_deadline_seals_the_rest(ring, run):
    svc = service(max_delay=0.2)
    try:
        async def go():
            t0 = time.monotonic()
            await asyncio.gather(*(svc.verify(*ITEM) for _ in range(BUCKET)))
            quick = time.monotonic() - t0
            await svc.verify(*ITEM)  # alone: waits for its deadline
            return quick, time.monotonic() - t0 - quick

        quick, slow = run(go())
        assert quick < 0.1 and 0.2 <= slow < 0.4
        seals = [r.t_seal - r.t_oldest for r in flush_records(svc)]
        assert len(seals) == 2 and seals[0] < 0.1 and seals[1] >= 0.2
    finally:
        svc.shutdown()


def test_the_loop_never_waits_on_the_in_flight_bound(ring, run):
    """Twelve buckets sealed against three slots and a collect of 30 ms each:
    the seals that find the bound full leave their entries queued, each
    completion arms the next, and a 20 ms heartbeat on the same loop is
    never over 50 ms late."""
    svc = service(collect_s=0.03, inflight=3)
    deferred = SERVICE_EVENTS.labels("deferred").value
    try:
        async def heartbeat(late: list, stop: asyncio.Event):
            due = time.monotonic()
            while not stop.is_set():
                due += 0.02
                await asyncio.sleep(max(0.0, due - time.monotonic()))
                late.append(time.monotonic() - due)

        async def go():
            late, stop = [], asyncio.Event()
            beat = asyncio.ensure_future(heartbeat(late, stop))
            got = await asyncio.gather(*(svc.verify(*ITEM) for _ in range(12 * BUCKET)))
            stop.set()
            await beat
            return got, late

        got, late = run(go())
        assert got == [True] * (12 * BUCKET)
        assert late and max(late) < 0.05
        assert svc.flushes["singles"] == 12 and svc.flushes["deferred"] >= 1
        assert SERVICE_EVENTS.labels("deferred").value == deferred + svc.flushes["deferred"]
        assert svc.verifier.inflight_max == 3
        flushes = flush_records(svc)
        assert [f.seq for f in sorted(flushes, key=lambda f: f.t_seal)] == list(range(1, 13))
        assert all(f.entries == BUCKET and f.failure is None for f in flushes)
    finally:
        svc.shutdown()


def test_entries_from_two_loops_one_after_the_other_both_resolve(ring):
    """asyncio-loop agnostic: the second loop is served like the first, and
    a seal armed on a loop that closed before it ran strands nothing."""
    svc = service(max_delay=0.05)
    try:
        async def burst():
            return await asyncio.gather(*(svc.verify(*ITEM) for _ in range(3)))

        assert asyncio.run(burst()) == [True] * 3
        assert asyncio.run(burst()) == [True] * 3

        async def gives_up():
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(svc.verify(*ITEM), 0.005)

        asyncio.run(gives_up())  # its entry is queued, its seal armed on a closed loop
        assert len(svc._pending) == 1 and svc._armed[0].is_closed()
        assert asyncio.run(burst()) == [True] * 3
        assert not svc._pending and svc.flushes["singles"] == 3
    finally:
        svc.shutdown()


def test_two_loops_at_once_share_flushes(ring):
    svc = service(max_delay=0.02)
    try:
        async def burst(n):
            return await asyncio.gather(*(svc.verify(*ITEM) for _ in range(n)))

        results = {}
        others = [threading.Thread(target=lambda i=i: results.__setitem__(i, asyncio.run(burst(3)))) for i in range(2)]
        for t in others:
            t.start()
        for t in others:
            t.join(10.0)
        assert results == {0: [True] * 3, 1: [True] * 3}
        assert 2 <= svc.flushes["singles"] <= 4
    finally:
        svc.shutdown()


def test_shutdown_fails_what_is_queued_and_refuses_what_comes_after(ring, run):
    svc = service(max_delay=5.0)

    async def go():
        waiter = asyncio.ensure_future(svc.verify(*ITEM))
        await asyncio.sleep(0.01)
        assert len(svc._pending) == 1
        stopped = await asyncio.get_running_loop().run_in_executor(None, svc.shutdown)
        with pytest.raises(RuntimeError, match="shut down"):
            await waiter
        with pytest.raises(RuntimeError, match="shut down"):
            await svc.verify(*ITEM)
        return stopped

    assert run(go()) is True
    assert not svc._pending and svc.flushes["singles"] == 0
    assert svc.shutdown() is True  # again (atexit does): nothing left to stop


def test_a_failed_submit_gives_its_slot_back(ring, run):
    """More failed dispatches than slots, then sound ones: every waiter of a
    failed flush gets the error, and the bound is not used up."""
    svc = service(fail_submits=5, inflight=2)
    try:
        async def one():
            return await asyncio.gather(*(svc.verify(*ITEM) for _ in range(BUCKET)), return_exceptions=True)

        async def go():
            return [await one() for _ in range(7)]

        rounds = run(go())
        assert all(isinstance(r, RuntimeError) and "device lost" in str(r) for got in rounds[:5] for r in got)
        assert rounds[5:] == [[True] * BUCKET] * 2
        assert svc.flushes["submit_failed"] == 5 and svc.flushes["singles"] == 2 and svc._sealed == 0
        failed = [r for r in flush_records(svc) if r.failure]
        assert len(failed) == 5 and all(f.failure.startswith("submit:") and f.padded == 0 for f in failed)
    finally:
        svc.shutdown()
