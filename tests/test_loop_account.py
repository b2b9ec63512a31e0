"""The loop account (tracing.py): every callback a loop with a heartbeat runs
while the account keeps a stretch is timed on the loop's own thread and
charged to the task, handler or label that ran it; at the end of each stretch
it keeps, and at the last release, the heartbeat writes a `loop` record and
its `owner` rows. Planted work is `time.sleep` on the loop, on purpose: what
is charged is wall time inside a callback."""

from __future__ import annotations

import asyncio
import collections
import functools
import threading
import time

import pytest

from narwhal_tpu import tracing

HERE = "tests/test_loop_account.py"
ORIGINAL_RUN = asyncio.events.Handle._run


@pytest.fixture(autouse=True)
def ring(monkeypatch):
    # The first stretch outlasts the test, but where a test says otherwise:
    # what is charged, and to whom, is one path at any length of stretch.
    monkeypatch.setattr(tracing, "ACCOUNT_KEEP_S", 60.0)
    tracing.new_generation()
    yield tracing.FLIGHT
    tracing.new_generation()
    assert asyncio.events.Handle._run is ORIGINAL_RUN and not tracing._ACCOUNTS and not tracing._BY_THREAD
    assert tracing.ACCOUNTING is False


def records(kind: str) -> list:
    return [r for r in tracing.flight_dump()["events"] if r.kind == kind]


def block(seconds: float) -> None:
    time.sleep(seconds)  # lint: allow(no-blocking-in-async)


def accounted(body, *args) -> dict:
    """Run `body` on a fresh loop under a heartbeat; the seconds each owner
    was charged, over every record the run left."""
    async def go():
        tracing.heartbeat_acquire()
        try:
            await asyncio.sleep(0)  # the account times callbacks from the next one on
            await body(*args)
            await asyncio.sleep(0)  # and the callback that releases is the closed account's
        finally:
            tracing.heartbeat_release()

    asyncio.run(go())
    seconds: dict = collections.defaultdict(float)
    for r in records("owner"):
        seconds[r.owner] += r.seconds
    return seconds


async def worker(seconds: float) -> None:
    await asyncio.sleep(0)
    block(seconds)


def test_a_task_step_is_charged_to_its_coroutine_and_a_named_task_to_its_name():
    async def body():
        plain = asyncio.ensure_future(worker(0.03))
        named = asyncio.ensure_future(worker(0.05))
        named.set_name("rpc:HeaderMsg")
        await asyncio.gather(plain, named)

    seconds = accounted(body)
    assert 0.03 <= seconds[f"{HERE}:worker"] < 0.05
    assert 0.05 <= seconds["rpc:HeaderMsg"] < 0.08
    rows = {r.owner: r for r in records("owner")}
    assert rows["rpc:HeaderMsg"].family == "network" and rows[f"{HERE}:worker"].family == "other"
    assert rows["rpc:HeaderMsg"].calls == 2  # its two steps
    assert 0.05 <= rows["rpc:HeaderMsg"].longest <= rows["rpc:HeaderMsg"].seconds


def test_a_label_holds_for_the_rest_of_the_callback_only():
    async def handler():
        block(0.02)  # the task's own
        assert tracing.charge("core:vote") is None
        block(0.03)
        await asyncio.sleep(0)  # suspends: the label ends with the callback
        block(0.04)

    seconds = accounted(handler)
    assert 0.03 <= seconds["core:vote"] < 0.06
    # Its own 0.02 before the label and 0.04 after the await; `go` and
    # `handler` are one task, whose coroutine is `accounted.<locals>.go`.
    assert 0.06 <= seconds[f"{HERE}:accounted.<locals>.go"] < 0.12
    (vote,) = [r for r in records("owner") if r.owner == "core:vote"]
    assert vote.family == "primary" and vote.calls == 1


def wal(seconds: float) -> None:
    """A site that times itself: one clock read before, `nested` after."""
    t0 = time.perf_counter()
    block(seconds)
    tracing.nested("storage:wal", t0)


def test_a_nested_stretch_returns_to_what_it_interrupted():
    async def handler():
        tracing.charge("execute:certificate")
        block(0.02)
        wal(0.03)
        block(0.01)  # the executor's again
        wal(0.01)
        await asyncio.sleep(0)
        wal(0.01)  # nested in the task's own
        block(0.02)

    seconds = accounted(handler)
    assert 0.05 <= seconds["storage:wal"] < 0.1
    assert 0.03 <= seconds["execute:certificate"] < 0.06
    assert 0.02 <= seconds[f"{HERE}:accounted.<locals>.go"] < 0.04
    rows = {r.owner: r for r in records("owner")}
    assert rows["storage:wal"].calls == 3 and rows["storage:wal"].family == "storage"
    assert 0.03 <= rows["storage:wal"].longest < 0.05  # its longest single stretch
    assert rows["execute:certificate"].calls == 1 and rows["execute:certificate"].family == "execute"


class Handler:
    def on_timer(self, seconds: float) -> None:
        block(seconds)


def test_plain_callbacks_are_charged_to_their_code():
    async def body():
        loop = asyncio.get_running_loop()
        done = loop.create_future()
        loop.call_soon(Handler().on_timer, 0.02)  # a bound method: by its function
        loop.call_later(0.01, functools.partial(block, 0.02))  # a partial: by what it calls
        loop.call_later(0.05, done.set_result, None)  # a builtin: by its name
        await done

    seconds = accounted(body)
    assert 0.02 <= seconds[f"{HERE}:Handler.on_timer"] < 0.04
    assert 0.02 <= seconds[f"{HERE}:block"] < 0.04
    assert any(owner.endswith("Future.set_result") for owner in seconds)


def test_the_rows_of_a_stretch_sum_to_its_busy_seconds_and_the_rest_is_folded_by_family(monkeypatch):
    monkeypatch.setattr(tracing, "OWNER_ROWS", 3)
    monkeypatch.setattr(tracing, "ACCOUNT_KEEP_S", 0.15)

    async def body():
        tasks = []
        for i in range(6):
            t = asyncio.ensure_future(worker(0.005 * (i + 1)))
            t.set_name(("rpc:", "core:")[i % 2] + f"M{i}")
            tasks.append(t)
        await asyncio.gather(*tasks)
        await asyncio.sleep(0.3)  # the heartbeat writes the stretch itself, and the account rests

    busy = tracing.LOOP_BUSY.labels("network").value
    accounted(body)
    (kept,) = records("loop")  # the release found the account resting: nothing more to write
    rows = records("owner")
    assert 0.15 <= kept.t1 - kept.t0 < 0.6 and {(r.loop, r.t1) for r in rows} == {(kept.loop, kept.t1)}
    assert sum(r.seconds for r in rows) == pytest.approx(kept.busy_s, rel=1e-9)
    assert kept.handles >= sum(r.calls for r in rows if r.owner != "rest" and ":" not in r.owner[:5])
    assert kept.longest_s == max(r.longest for r in rows)
    assert 0 < kept.busy_s < kept.t1 - kept.t0 and kept.cpu_s >= 0
    named = [r for r in rows if r.owner != "rest"]
    assert [r.owner for r in named] == ["core:M5", "rpc:M4", "core:M3"]  # the three with the most seconds
    assert kept.longest_owner == "core:M5"
    rest = {r.family: r for r in rows if r.owner == "rest"}
    assert set(rest) >= {"network", "primary", "other"}
    assert rest["network"].seconds == pytest.approx(0.005 + 0.015, abs=0.01) and rest["network"].calls == 4
    assert {r.family for r in rows} <= set(tracing.FAMILIES)
    # The first stretch stands for itself alone in the series.
    assert tracing.LOOP_BUSY.labels("network").value - busy == pytest.approx(
        sum(r.seconds for r in rows if r.family == "network"), rel=1e-9)


def test_a_sites_label_keeps_a_row_of_its_own_past_the_largest_owners(monkeypatch):
    """Tasks past `OWNER_ROWS` fold into their family's `rest`; a label a
    site passed to `nested` or `charge` never does, however small, since a
    reader asks for it by name."""
    monkeypatch.setattr(tracing, "OWNER_ROWS", 1)

    async def body():
        tasks = []
        for i in range(3):
            t = asyncio.ensure_future(worker(0.01 * (i + 1)))
            t.set_name(f"rpc:M{i}")
            tasks.append(t)
        await asyncio.gather(*tasks)
        t0 = time.perf_counter()
        block(0.001)
        tracing.nested("net:codec", t0)
        tracing.charge("core:vote")
        block(0.001)

    accounted(body)
    rows = {(r.owner, r.family): r for r in records("owner")}
    assert {"rpc:M2", "net:codec", "core:vote"} <= {owner for owner, _ in rows}
    assert "rpc:M1" not in {owner for owner, _ in rows}
    assert rows[("rest", "network")].calls == 4  # M0 and M1: two steps each
    (kept,) = records("loop")
    assert sum(r.seconds for r in rows.values()) == pytest.approx(kept.busy_s, rel=1e-9)


def test_the_threads_cpu_time_tells_sleeping_from_working():
    async def sleeper():
        block(0.2)

    async def spinner():
        until = time.thread_time() + 0.15  # the thread's own CPU time: a crowded host only stretches the wall
        while time.thread_time() < until:
            pass

    accounted(sleeper)
    (slept,) = records("loop")
    tracing.new_generation()
    accounted(spinner)
    (spun,) = records("loop")
    assert slept.busy_s >= 0.2 and slept.busy_s - slept.cpu_s >= 0.15  # off the core inside a callback
    assert spun.busy_s >= 0.15 and spun.cpu_s >= 0.15


def test_a_loop_without_a_heartbeat_is_not_charged_and_the_last_release_puts_handle_run_back():
    seen = {}

    async def bystander(started: threading.Event, stop: threading.Event):
        started.set()
        while not stop.is_set():
            block(0.001)
            tracing.charge("core:vote")  # a thread whose loop keeps no account: nothing
            await asyncio.sleep(0)

    async def body():
        started, stop = threading.Event(), threading.Event()
        other = threading.Thread(target=lambda: asyncio.run(bystander(started, stop)))
        tracing.heartbeat_acquire()
        tracing.heartbeat_acquire()  # a second node on the loop
        try:
            seen["installed"] = asyncio.events.Handle._run
            other.start()
            started.wait(2.0)
            await asyncio.sleep(0.05)
        finally:
            tracing.heartbeat_release()
            seen["one left"] = asyncio.events.Handle._run
            stop.set()
            other.join(2.0)
            tracing.heartbeat_release()
        seen["none left"] = asyncio.events.Handle._run

    assert asyncio.events.Handle._run is ORIGINAL_RUN
    asyncio.run(body())
    assert seen["installed"] is tracing._run_charged is seen["one left"] and seen["none left"] is ORIGINAL_RUN
    assert len({r.loop for r in records("loop")}) == 1
    owners = {r.owner for r in records("owner")}
    assert "core:vote" not in owners and not any("bystander" in o for o in owners)


def test_a_label_from_a_thread_that_runs_no_loop_is_nothing():
    async def body():
        out = []
        await asyncio.get_running_loop().run_in_executor(
            None, lambda: out.append((tracing.charge("core:vote"), tracing.nested("storage:wal", time.perf_counter() - 1.0))))
        t0 = time.perf_counter()
        await asyncio.sleep(0)
        tracing.nested("verify:seal", t0)  # began in another callback: not this one's to give
        assert out == [(None, None)]

    seconds = accounted(body)
    assert not {"storage:wal", "core:vote", "verify:seal"} & set(seconds)


def test_nothing_is_installed_under_simnet():
    from narwhal_tpu.simnet import run_scenario

    r = run_scenario(nodes=4, duration=1.0, load_rate=40)
    assert r.rounds and asyncio.events.Handle._run is ORIGINAL_RUN and not tracing._ACCOUNTS
    assert not records("loop") and not records("owner")


def test_the_two_kinds_are_laid_out_as_flight_fields_says():
    assert tracing.FLIGHT_FIELDS["loop"].split() == [
        "loop", "t0", "t1", "handles", "busy_s", "cpu_s", "longest_s", "longest_owner"]
    assert tracing.FLIGHT_FIELDS["owner"].split() == ["loop", "t1", "owner", "family", "calls", "seconds", "longest"]

    async def body():
        await worker(0.01)

    accounted(body)
    for kind in ("loop", "owner"):
        (first, *_) = records(kind)
        assert first._fields == ("kind",) + tuple(tracing.FLIGHT_FIELDS[kind].split())
    with pytest.raises(TypeError):
        tracing.flight("owner", 1, 1.0, "rpc:X", "network", 1, 0.1)  # a field short


@pytest.mark.parametrize("owner,family", [
    ("rpc:CertificateMsg", "network"),
    ("narwhal_tpu/network/rpc.py:FrameSender._drain_loop", "network"),
    ("narwhal_tpu/network/pool.py:LanePool.dispatch", "network"),
    ("asyncio/selector_events.py:_SelectorSocketTransport._read_ready", "network"),
    ("asyncio/streams.py:StreamReader.readexactly", "network"),
    ("asyncio/tasks.py:sleep", "other"),
    ("core:header", "primary"),
    ("narwhal_tpu/primary/core.py:Core.run", "primary"),
    ("narwhal_tpu/primary/proposer.py:Proposer.run", "primary"),
    ("narwhal_tpu/primary/verifier_stage.py:VerifierStage._verify", "verify"),
    ("narwhal_tpu/tpu/verifier.py:VerifyService._on_seal", "verify"),
    ("stage:vote", "verify"),
    ("verify:deliver", "verify"),
    ("narwhal_tpu/worker/batch_maker.py:BatchMaker.run", "worker"),
    ("consensus:walk", "execute"),
    ("execute:certificate", "execute"),
    ("narwhal_tpu/consensus/runner.py:Consensus.run", "execute"),
    ("narwhal_tpu/executor/core.py:Core.run", "execute"),
    ("narwhal_tpu/node.py:SimpleExecutionState.handle_consensus_transaction", "execute"),
    ("narwhal_tpu/node.py:PrimaryNode.spawn", "other"),
    ("storage:wal", "storage"),
    ("net:aead", "network"),
    ("net:write", "network"),
    ("net:codec", "network"),
    ("narwhal_tpu/network/rpc.py:FrameSender._drain_loop", "network"),
    ("narwhal_tpu/storage.py:StorageEngine._run_committer", "storage"),
    ("chipbench/run.py:serve.<locals>.submit", "harness"),
    ("chipbench/traffic.py:Generator.run", "harness"),
    ("narwhal_tpu/tracing.py:_heartbeat", "other"),
    ("_asyncio.Future.set_result", "other"),
])
def test_an_owners_family_is_its_first_matching_prefix(owner, family):
    assert tracing._owner(owner) == (owner, family) and family in tracing.FAMILIES


def test_code_is_named_by_its_path_from_the_checkout_or_the_library():
    from narwhal_tpu.primary.core import Core

    assert tracing._owner(Core.run.__code__) == ("narwhal_tpu/primary/core.py:Core.run", "primary")
    assert tracing._owner(asyncio.sleep.__code__) == ("asyncio/tasks.py:sleep", "other")
    assert tracing._owner(worker.__code__) == (f"{HERE}:worker", "other")


def test_the_sites_of_the_served_path_name_themselves():
    """The labels the program's own sites pass, on calls that stand for the
    sites: a dispatch task's name, and a write to the WAL."""
    from narwhal_tpu.messages import REGISTRY
    from narwhal_tpu.network import rpc
    from narwhal_tpu.storage import StorageEngine

    tag = next(iter(sorted(REGISTRY)))
    assert rpc.dispatch_task_name(tag) == f"rpc:{REGISTRY[tag].__name__}" == rpc.dispatch_task_name(tag)
    assert rpc.dispatch_task_name(65_000) == "rpc:65000"

    async def body():
        StorageEngine(None).column_family("t").put(b"k", b"v")

    accounted(body)
    (wal,) = [r for r in records("owner") if r.owner == "storage:wal"]
    assert wal.calls == 1 and wal.family == "storage"


def test_the_verify_service_charges_its_seal_and_its_delivery():
    from narwhal_tpu.tpu.verifier import VerifyService

    class Stub:
        """Accepts everything; `submit` stands on the loop as a pack and
        dispatch does."""

        max_bucket = 32
        counts = collections.Counter()

        def submit(self, items):
            block(0.01)
            return collections.namedtuple("Handle", "items padded")(list(items), 32)

        def collect(self, handle):
            return [True] * len(handle.items)

    service = VerifyService(Stub(), max_batch=32, max_delay=0.002)

    async def body():
        assert all(await asyncio.gather(*(service.verify(b"k" * 32, b"m%d" % i, b"s" * 64) for i in range(4))))

    try:
        seconds = accounted(body)
    finally:
        service.shutdown()
    assert 0.01 <= seconds["verify:seal"] < 0.03 and 0 < seconds["verify:deliver"] < 0.01
    assert {r.family for r in records("owner") if r.owner.startswith("verify:")} == {"verify"}


class _SlowSession:
    """Seals and opens as `auth.Session` does (a 16-byte tag), each in 10 ms."""

    def seal_body(self, kind, rid, tag, body, lane=0) -> bytes:
        block(0.01)
        return bytes(body) + bytes(16)

    def open_body(self, kind, rid, tag, body, lane=0) -> bytes:
        block(0.01)
        return bytes(body[:-16])


class _OpenTransport:
    def is_closing(self) -> bool:
        return False


class _SlowWriter:
    """A StreamWriter whose every `writelines` stands for a socket `sendmsg`
    of 10 ms."""

    def __init__(self):
        self.transport = _OpenTransport()
        self.chunks: list[bytes] = []

    def writelines(self, data) -> None:
        block(0.01)
        self.chunks.extend(bytes(d) for d in data)

    async def drain(self) -> None:
        pass


async def request_and_reply(monkeypatch) -> list:
    """One request through `RpcServer._dispatch` in its dispatch task (a
    decode and the reply's encode of 10 ms each), the reply sealed and
    written by the connection's drainer (10 ms, and one writelines of 10), then
    read back and opened (10 ms); the clock readings the network's sites
    took meanwhile."""
    from narwhal_tpu.messages import SubmitTransactionMsg
    from narwhal_tpu.network import rpc
    from narwhal_tpu.network.auth import Peer

    decode, encode = rpc.decode_message, rpc.encode_message
    reads = []

    class Clock:
        @staticmethod
        def perf_counter() -> float:
            reads.append(1)
            return time.perf_counter()

    def slow_decode(tag, body):
        block(0.01)
        return decode(tag, body)

    def slow_encode(msg):
        block(0.01)
        return encode(msg)

    monkeypatch.setattr(rpc, "decode_message", slow_decode)
    monkeypatch.setattr(rpc, "encode_message", slow_encode)
    monkeypatch.setattr(rpc, "time", Clock)
    server = rpc.RpcServer()

    async def on_tx(msg, peer):
        return None

    server.route(SubmitTransactionMsg, on_tx)
    writer = _SlowWriter()
    sender = rpc.FrameSender(writer, _SlowSession())
    tag, body = encode(SubmitTransactionMsg(b"tx"))
    await asyncio.get_running_loop().create_task(
        server._dispatch(sender, 1, tag, body, Peer("peer")), name=rpc.dispatch_task_name(tag))
    await sender._task  # the drainer the reply started
    reader = asyncio.StreamReader()
    reader.feed_data(b"".join(writer.chunks))
    reader.feed_eof()
    kind, rid, _, _, reply = await rpc._read_frame(reader, _SlowSession())
    assert (kind, rid) == (rpc.KIND_RESP, 1) and decode(*encode(rpc.Ack())) == decode(rpc.Ack.TAG, reply)
    return reads


def test_the_network_family_by_part_comes_off_the_drainer_and_the_dispatch_task(monkeypatch):
    """The seal and the open are `net:aead`'s, the transport's writelines
    `net:write`'s, the request's decode and the reply's encode `net:codec`'s,
    all three under `network`; what they take comes off the drainer and the
    dispatch task they interrupted, and the stretch's rows still sum to its
    busy seconds."""
    monkeypatch.setattr(tracing, "OWNER_ROWS", 1000)  # every owner a row of its own
    reads = []

    async def body():
        reads.extend(await request_and_reply(monkeypatch))

    seconds = accounted(body)
    rows = {r.owner: r for r in records("owner")}
    assert {rows[o].family for o in ("net:aead", "net:write", "net:codec")} == {"network"}
    assert (rows["net:aead"].calls, rows["net:write"].calls, rows["net:codec"].calls) == (2, 1, 2)
    assert 0.02 <= seconds["net:aead"] < 0.03  # the seal and the open
    assert 0.01 <= seconds["net:write"] < 0.02  # the one writelines of header and ciphertext
    assert 0.02 <= seconds["net:codec"] < 0.03  # the request's decode and the reply's encode
    assert seconds["narwhal_tpu/network/rpc.py:FrameSender._drain_loop"] < 0.005
    assert seconds["rpc:SubmitTransactionMsg"] < 0.005
    assert len(reads) == 5  # one reading a site: decode, encode, seal, writelines, open
    (kept,) = records("loop")
    assert sum(r.seconds for r in records("owner")) == pytest.approx(kept.busy_s, rel=1e-9)


def test_a_resting_account_takes_no_clock_reading_at_the_network_sites(monkeypatch):
    monkeypatch.setattr(tracing, "ACCOUNT_KEEP_S", 0.0)  # the heartbeat's first wake ends the stretch
    monkeypatch.setattr(tracing, "ACCOUNT_REST_S", 60.0)
    reads = [None]

    async def body():
        await asyncio.sleep(0.1)
        assert tracing.ACCOUNTING is False and tracing._ACCOUNTS
        reads[:] = await request_and_reply(monkeypatch)

    seconds = accounted(body)
    assert reads == [] and not {"net:aead", "net:write", "net:codec"} & set(seconds)


def test_a_committee_on_one_loop_labels_its_own_sites(monkeypatch):
    """A four-validator `Cluster` on real loopback sockets, host crypto: the
    node's own heartbeat opens the account, and the labels of the served
    path's sites are among a few rounds' owners, each under its family."""
    from narwhal_tpu.cluster import Cluster

    monkeypatch.setattr(tracing, "OWNER_ROWS", 1000)  # every owner a row of its own

    async def drive():
        cluster = Cluster(size=4, workers=1)
        await cluster.start()
        try:
            assert asyncio.events.Handle._run is tracing._run_charged
            await cluster.assert_progress(commit_threshold=2, timeout=30.0)
        finally:
            await cluster.shutdown()

    asyncio.run(drive())
    family = {r.owner: r.family for r in records("owner")}
    assert {"core:header", "core:vote", "core:certificate"} <= set(family)
    assert {"stage:header", "stage:vote", "stage:certificate"} <= set(family)
    assert {"consensus:walk", "execute:certificate", "storage:wal"} <= set(family)
    assert all(family.get(o) == "network" for o in ("net:aead", "net:write", "net:codec"))
    rpc = {o for o in family if o.startswith("rpc:")}
    assert rpc and all(family[o] == "network" for o in rpc) and not any(o[4:].isdigit() for o in rpc)
    assert family["narwhal_tpu/primary/proposer.py:Proposer.run"] == "primary"
    assert family["consensus:walk"] == "execute" and family["stage:vote"] == "verify"
    loops = records("loop")
    assert len({r.loop for r in loops}) == 1 and sum(r.handles for r in loops) > 1000
    by_second = collections.defaultdict(float)
    for r in records("owner"):
        by_second[r.t1] += r.seconds
    assert all(by_second[r.t1] == pytest.approx(r.busy_s, rel=1e-9) for r in loops)


def test_what_a_callback_raises_reaches_the_loops_handler_as_it_always_did():
    """The account calls the callback without `Handle._run`'s frame, so it
    reports an exception itself: the same context, once, and the callback
    is charged all the same."""
    def boom(seconds: float) -> None:
        block(seconds)
        raise ValueError("planted")

    def contexts(with_account: bool) -> list[dict]:
        seen: list[dict] = []

        async def body():
            loop = asyncio.get_running_loop()
            loop.set_exception_handler(lambda _loop, context: seen.append(context))
            loop.call_soon(boom, 0.01)
            await asyncio.sleep(0.03)

        if with_account:
            assert accounted(body)[f"{HERE}:test_what_a_callback_raises_reaches_the_loops_handler_as_it_always_did.<locals>.boom"] >= 0.01
        else:
            asyncio.run(body())
        return seen

    (plain,), (charged,) = contexts(False), contexts(True)
    assert set(plain) == set(charged) >= {"message", "exception", "handle"}
    assert charged["message"].split(" at ")[0] == plain["message"].split(" at ")[0]
    assert charged["message"].startswith("Exception in callback") and "boom(0.01)" in charged["message"]
    assert isinstance(charged["exception"], ValueError) and isinstance(charged["handle"], asyncio.Handle)


def test_the_account_keeps_a_tenth_of_a_second_and_rests_between_stretches(monkeypatch):
    """The duty cycle as shipped (`ACCOUNT_KEEP_S`, `ACCOUNT_REST_S`): a kept
    stretch is written as it ends; while the account rests `Handle._run` is
    asyncio's own, `ACCOUNTING` is false, a site's label is nothing, and the
    next stretch starts clean. The series takes each stretch for all the
    time since the one before."""
    monkeypatch.undo()  # the module's own constants
    assert (tracing.ACCOUNT_KEEP_S, tracing.ACCOUNT_REST_S) == (0.1, 2.4)
    tracing.new_generation()

    turns = []  # (when, `Handle._run` was the account's, ACCOUNTING, what `charge` returned)

    async def body():
        start = time.monotonic()
        while time.monotonic() < start + 3.2:
            t0 = tracing.ACCOUNTING and time.perf_counter()
            block(0.004)
            if t0:
                tracing.nested("storage:wal", t0)
            turns.append((time.monotonic(), asyncio.events.Handle._run is tracing._run_charged,
                          tracing.ACCOUNTING, tracing.charge("core:vote")))
            await asyncio.sleep(0.001)

    series = tracing.LOOP_BUSY.labels("storage").value
    accounted(body)
    first, second = records("loop")  # about [0, 0.1] and [2.5, 2.6] of 3.2 s; the rest it rested
    assert all(0.1 <= r.t1 - r.t0 < 0.4 for r in (first, second)) and 2.4 <= second.t0 - first.t1 < 2.8
    margin = 0.005  # the stamps are the heartbeat's wakes, on the clock the turns read
    kept = [(t, patched, flag) for t, patched, flag, _ in turns
            if any(r.t0 + margin < t < r.t1 - margin for r in (first, second))]
    rested = [(t, patched, flag, held) for t, patched, flag, held in turns
              if all(t < r.t0 - margin or t > r.t1 + margin for r in (first, second))]
    assert len(kept) >= 10 and all(patched and flag for _, patched, flag in kept)
    assert len(rested) >= 100 and not any(patched or flag or held for _, patched, flag, held in rested)
    assert all(patched == flag for _, patched, flag, _ in turns)
    wal_s = []
    for stretch in (first, second):
        rows = [r for r in records("owner") if r.t1 == stretch.t1]
        assert sum(r.seconds for r in rows) == pytest.approx(stretch.busy_s, rel=1e-9)
        (wal_row,) = [r for r in rows if r.owner == "storage:wal"]
        # One stretch's writes of 4 ms each, not the 2.4 s before it.
        assert 0.01 < wal_row.seconds < stretch.t1 - stretch.t0 and wal_row.calls <= (stretch.t1 - stretch.t0) / 0.004 + 1
        wal_s.append(wal_row.seconds)
    stands_for = (second.t1 - first.t1) / (second.t1 - second.t0)
    assert 6 < stands_for < 29
    assert tracing.LOOP_BUSY.labels("storage").value - series == pytest.approx(wal_s[0] + stands_for * wal_s[1], rel=1e-6)


def test_a_tick_ends_a_stretch_and_begins_the_next_on_the_accounts_own_clock(monkeypatch):
    """`tick` alone, at planted times: no loop runs and nothing sleeps."""
    monkeypatch.undo()  # the module's own constants
    keep, rest = tracing.ACCOUNT_KEEP_S, tracing.ACCOUNT_REST_S
    acct = tracing._LoopAccount(7, 100.0)
    with tracing._ACCOUNT_LOCK:
        tracing._ACCOUNTS["planted"] = acct
    try:
        acct.tick(100.0 + keep / 2)
        assert acct.on and not records("loop")  # the stretch is not over
        acct.row("storage:wal")[1] += 0.02
        t1 = 100.0 + keep + 0.03  # the first wake past the stretch's end: the heartbeat is late
        acct.tick(t1)
        (kept,) = records("loop")
        assert (kept.loop, kept.t0, kept.t1, kept.busy_s) == (7, 100.0, t1, 0.02) and not acct.on
        acct.tick(t1 + rest - 0.01)
        assert not acct.on and tracing.ACCOUNTING is False and asyncio.events.Handle._run is ORIGINAL_RUN
        acct.tick(t1 + rest)
        assert acct.on and acct.tally == {} and (acct.t_before, acct.t_flushed) == (t1, t1 + rest)
        assert tracing.ACCOUNTING is True and asyncio.events.Handle._run is tracing._run_charged
    finally:
        with tracing._ACCOUNT_LOCK:
            del tracing._ACCOUNTS["planted"]
        tracing._patch_handle_run()
