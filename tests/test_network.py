"""RPC mesh tests — real loopback sockets like the reference's mock servers
(/root/reference/test_utils/src/lib.rs:176-359)."""

import asyncio

import pytest

from narwhal_tpu.channels import Channel
from narwhal_tpu.messages import (
    Ack,
    CertificateMsg,
    SubmitTransactionMsg,
    WorkerBatchMsg,
    WorkerBatchRequest,
    WorkerBatchResponse,
)
from narwhal_tpu.network import (
    NetworkClient,
    RetryConfig,
    RpcError,
    RpcServer,
    RpcTimeout,
)
from narwhal_tpu.fixtures import CommitteeFixture
from narwhal_tpu.types import Batch


def test_request_response(run):
    async def scenario():
        server = RpcServer()
        received = Channel(100)

        async def on_batch(msg, peer):
            await received.send(msg)
            return None  # ack

        async def on_batch_request(msg: WorkerBatchRequest, peer):
            return WorkerBatchResponse((b"batch-bytes",))

        server.route(WorkerBatchMsg, on_batch)
        server.route(WorkerBatchRequest, on_batch_request)
        port = await server.start("127.0.0.1", 0)

        net = NetworkClient()
        addr = f"127.0.0.1:{port}"
        batch = Batch((b"tx",))

        # oneway + ack
        ok = await net.unreliable_send(addr, WorkerBatchMsg(batch.to_bytes()))
        assert ok
        got = await asyncio.wait_for(received.recv(), 1.0)
        assert got.batch() == batch

        # typed rpc
        resp = await net.request(addr, WorkerBatchRequest((batch.digest,)))
        assert isinstance(resp, WorkerBatchResponse)
        assert resp.batches == (b"batch-bytes",)

        net.close()
        await server.stop()

    run(scenario())


def test_reliable_send_escalates_deadline_for_slow_peer(run):
    """A slow-but-alive handler must not be retried into congestion
    collapse: the reliable send escalates its per-attempt deadline, so the
    handler runs a couple of times, not once per backoff tick (the N=50
    frame-storm fence)."""

    async def scenario():
        server = RpcServer()
        calls = 0

        async def slow(msg, peer):
            nonlocal calls
            calls += 1
            await asyncio.sleep(0.35)  # beyond the first two deadlines
            return None

        server.route(WorkerBatchMsg, slow)
        port = await server.start("127.0.0.1", 0)
        net = NetworkClient(RetryConfig(initial=0.01, max_elapsed=None))
        handle = net.send(
            f"127.0.0.1:{port}", WorkerBatchMsg(Batch((b"t",)).to_bytes()),
            timeout=0.1,  # first deadlines miss; escalation must kick in
        )
        assert await asyncio.wait_for(handle.task, 10.0)
        # Fixed 0.1 s deadlines would need ~4+ handler executions before
        # luck; escalation (0.1 -> 0.2 -> 0.4) succeeds by the third.
        assert calls <= 3, calls
        net.close()
        await server.stop()

    run(scenario())


class _ScriptedPeer:
    """PeerClient stand-in: raises the scripted failures in order, then
    acks, recording the per-attempt deadline the client chose."""

    def __init__(self, script):
        self.script = list(script)
        self.timeouts = []

    async def request(self, msg, timeout):
        self.timeouts.append(timeout)
        if self.script:
            raise self.script.pop(0)
        return Ack()

    def close(self):
        pass


def test_reliable_send_does_not_escalate_on_connection_refused(run):
    """Connection-refused fails instantly — it says nothing about the
    peer's speed, so a restarting peer must keep getting the configured
    deadline, not an ever-doubling one."""

    async def scenario():
        net = NetworkClient(RetryConfig(initial=0.001, max_elapsed=None, jitter=0))
        peer = _ScriptedPeer([ConnectionRefusedError("refused")] * 4)
        net._peers["127.0.0.1:9"] = peer
        handle = net.send("127.0.0.1:9", Ack(), timeout=1.0)
        assert await asyncio.wait_for(handle.task, 5.0)
        assert peer.timeouts == [1.0] * 5  # never inflated
        net.close()

    run(scenario())


def test_reliable_send_resets_deadline_after_timeout_escalation(run):
    """Only timeout-class failures escalate, and any non-timeout failure
    resets the deadline: timeout, timeout -> 1x, 2x, 4x; then a refused
    connect drops the next attempt back to the configured 1x."""

    async def scenario():
        net = NetworkClient(RetryConfig(initial=0.001, max_elapsed=None, jitter=0))
        peer = _ScriptedPeer(
            [
                RpcTimeout("slow"),
                RpcTimeout("slow"),
                ConnectionRefusedError("restarting"),
                RpcTimeout("slow"),
            ]
        )
        net._peers["127.0.0.1:9"] = peer
        handle = net.send("127.0.0.1:9", Ack(), timeout=1.0)
        assert await asyncio.wait_for(handle.task, 5.0)
        assert peer.timeouts == [1.0, 2.0, 4.0, 1.0, 2.0]
        net.close()

    run(scenario())


def test_unreliable_send_to_dead_peer(run):
    async def scenario():
        net = NetworkClient()
        ok = await net.unreliable_send("127.0.0.1:1", SubmitTransactionMsg(b"x"), timeout=1.0)
        assert not ok
        net.close()

    run(scenario())


def test_reliable_send_retries_until_server_appears(run):
    async def scenario():
        from narwhal_tpu.config import get_available_port

        port = get_available_port()
        addr = f"127.0.0.1:{port}"
        net = NetworkClient(RetryConfig(initial=0.02, max_elapsed=None))
        received = Channel(10)

        handle = net.send(addr, SubmitTransactionMsg(b"hello"))
        await asyncio.sleep(0.1)  # several failed attempts

        server = RpcServer()

        async def on_tx(msg, peer):
            await received.send(msg)
            return None

        server.route(SubmitTransactionMsg, on_tx)
        await server.start("127.0.0.1", port)

        assert await asyncio.wait_for(handle, 5.0) is True
        got = await asyncio.wait_for(received.recv(), 1.0)
        assert got.transaction == b"hello"
        net.close()
        await server.stop()

    run(scenario())


def test_reliable_send_cancel(run):
    async def scenario():
        net = NetworkClient(RetryConfig(initial=0.02, max_elapsed=None))
        handle = net.send("127.0.0.1:1", SubmitTransactionMsg(b"x"))
        await asyncio.sleep(0.05)
        handle.cancel()
        with pytest.raises(asyncio.CancelledError):
            await handle
        net.close()

    run(scenario())


def test_handler_error_becomes_rpc_error(run):
    async def scenario():
        server = RpcServer()

        async def boom(msg, peer):
            raise ValueError("kaboom")

        server.route(SubmitTransactionMsg, boom)
        port = await server.start("127.0.0.1", 0)
        net = NetworkClient()
        with pytest.raises(RpcError, match="kaboom"):
            await net.request(f"127.0.0.1:{port}", SubmitTransactionMsg(b"x"))
        # connection survives an error response
        with pytest.raises(RpcError):
            await net.request(f"127.0.0.1:{port}", SubmitTransactionMsg(b"y"))
        net.close()
        await server.stop()

    run(scenario())


def test_broadcast_and_lucky(run):
    async def scenario():
        servers, addrs, chans = [], [], []
        for _ in range(4):
            s = RpcServer()
            ch = Channel(10)

            async def make(ch_):
                async def on(msg, peer):
                    await ch_.send(msg)

                return on

            s.route(CertificateMsg, await make(ch))
            port = await s.start("127.0.0.1", 0)
            servers.append(s)
            addrs.append(f"127.0.0.1:{port}")
            chans.append(ch)

        f = CommitteeFixture(size=4)
        cert = f.certificate(f.header(author=0, round=1))
        net = NetworkClient()

        handles = net.broadcast(addrs, CertificateMsg(cert))
        results = await asyncio.gather(*handles)
        assert results == [True] * 4
        for ch in chans:
            got = await asyncio.wait_for(ch.recv(), 1.0)
            assert got.certificate == cert

        oks = await net.lucky_broadcast(addrs, CertificateMsg(cert), nodes=2)
        assert sum(oks) == 2

        net.close()
        for s in servers:
            await s.stop()

    run(scenario())


def test_large_frame(run):
    async def scenario():
        server = RpcServer()

        async def echo(msg: WorkerBatchMsg, peer):
            return WorkerBatchResponse((msg.serialized_batch,))

        server.route(WorkerBatchMsg, echo)
        port = await server.start("127.0.0.1", 0)
        net = NetworkClient()
        big = Batch(tuple(bytes([i % 256]) * 512 for i in range(2000)))  # ~1MB
        resp = await net.request(
            f"127.0.0.1:{port}", WorkerBatchMsg(big.to_bytes()), timeout=10.0
        )
        assert resp.batches[0] == big.to_bytes()
        net.close()
        await server.stop()

    run(scenario())


class _MockTransportWriter:
    """StreamWriter stand-in recording every write, writelines and drain."""

    def __init__(self):
        self.chunks: list[bytes] = []
        self.writelines_calls = 0
        self.drains = 0

    def write(self, data: bytes) -> None:
        self.chunks.append(bytes(data))

    def writelines(self, data) -> None:
        self.writelines_calls += 1
        self.chunks.extend(bytes(d) for d in data)

    async def drain(self) -> None:
        self.drains += 1


def test_frame_sender_coalesces_one_drain_byte_identical(run):
    """K frames enqueued in one loop turn must reach the transport as ONE
    drain and ONE `writelines` whose bytes are exactly the K sequentially-
    written frames, in enqueue order (the coalescer must never reorder or
    re-frame)."""
    from narwhal_tpu.network.rpc import _FRAME_HDR, KIND_REQ, FrameSender, _write_frame

    async def scenario():
        mock = _MockTransportWriter()
        sender = FrameSender(mock)
        frames = [(KIND_REQ, rid, 7, b"body-%d" % rid) for rid in range(1, 9)]
        for f in frames:
            sender.send(*f)
        # Nothing hits the transport until the drainer task runs.
        assert mock.chunks == [] and mock.drains == 0
        await asyncio.sleep(0)  # let the drainer run once
        assert mock.drains == 1, "8 same-turn frames must share one drain"
        assert mock.writelines_calls == 1, "and one writelines: one system call"

        sequential = _MockTransportWriter()
        for f in frames:
            _write_frame(sequential, *f)
        assert sequential.writelines_calls == len(frames)
        assert b"".join(mock.chunks) == b"".join(sequential.chunks)
        by_hand = b"".join(
            _FRAME_HDR.pack(len(body), kind, rid, tag, 0) + body for kind, rid, tag, body in frames
        )
        assert b"".join(mock.chunks) == by_hand

    run(scenario())


def test_a_sealed_multi_lane_burst_opens_in_wire_order(run):
    """A burst over three lanes, sealed, is one `writelines` whose frames
    come round-robin by lane, and the peer opens every one in wire order:
    the counter nonces were drawn in the order the frames lie on the wire."""
    from narwhal_tpu.network.auth import Session
    from narwhal_tpu.network.rpc import KIND_REQ, FrameSender, _read_frame

    async def scenario():
        mock = _MockTransportWriter()
        sender = FrameSender(mock, Session(b"a" * 32, b"b" * 32))
        queued = {0: [b"v0", b"v1", b"v2"], 1: [b"B" * 3200], 2: [b"c0", b""]}
        rid = 0
        for lane, bodies in queued.items():
            for body in bodies:
                rid += 1
                sender.send(KIND_REQ, rid, 5, body, lane)
        await asyncio.sleep(0)
        assert (mock.drains, mock.writelines_calls) == (1, 1)

        reader = asyncio.StreamReader()
        reader.feed_data(b"".join(mock.chunks))
        reader.feed_eof()
        peer = Session(b"b" * 32, b"a" * 32)
        got = [(lane, body) for _, _, _, lane, body in [await _read_frame(reader, peer) for _ in range(rid)]]
        assert got == [(0, b"v0"), (1, b"B" * 3200), (2, b"c0"), (0, b"v1"), (2, b""), (0, b"v2")]
        assert reader.at_eof()

    run(scenario())


def test_rpc_coalescing_equivalence_concurrent_vs_sequential(run):
    """K concurrent sends through one connection must deliver frames that
    are byte-identical (tag+body), complete, and rid-ordered relative to
    the frames a sequential run delivers — coalescing only changes how
    many socket flushes carry them."""
    from narwhal_tpu.network import rpc as rpc_mod

    async def scenario():
        received: list[tuple[int, int, bytes]] = []
        orig_read = rpc_mod._read_frame

        async def spy_read(reader, session=None, counters=None):
            kind, rid, tag, lane, body = await orig_read(reader, session, counters)
            received.append((kind, tag, bytes(body)))
            return kind, rid, tag, lane, body

        rpc_mod._read_frame = spy_read
        try:
            server = RpcServer()

            async def on_tx(msg, peer):
                return None  # ack

            server.route(SubmitTransactionMsg, on_tx)
            port = await server.start("127.0.0.1", 0)
            net = NetworkClient()
            addr = f"127.0.0.1:{port}"
            msgs = [SubmitTransactionMsg(b"tx-%d" % i) for i in range(8)]

            # Concurrent: one connection, 8 requests in flight together.
            assert all(
                await asyncio.gather(
                    *(net.unreliable_send(addr, m) for m in msgs)
                )
            )
            concurrent = [r for r in received if r[0] == 0]  # REQ frames
            received.clear()

            # Sequential baseline on a fresh connection.
            net.peer(addr).close()
            for m in msgs:
                assert await net.unreliable_send(addr, m)
            sequential = [r for r in received if r[0] == 0]

            assert concurrent == sequential  # byte-identical, same order
            net.close()
            await server.stop()
        finally:
            rpc_mod._read_frame = orig_read

    run(scenario())


def test_wire_stats_records_frames_per_drain(run):
    """The coalescing instrumentation: four frames of one turn are one
    drain, and the frames sent over the drains made is the mean a drain
    carries (what the benchmark's `wire.frames_per_drain` reads)."""
    from narwhal_tpu.network.rpc import KIND_REQ, FrameSender, WireStats

    async def scenario():
        before = WireStats.snapshot()
        mock = _MockTransportWriter()
        sender = FrameSender(mock)
        for rid in range(4):
            sender.send(KIND_REQ, rid, 1, b"x")
        await asyncio.sleep(0)
        after = WireStats.snapshot()
        assert after["drains"] == before["drains"] + 1
        assert after["frames_sent"] == before["frames_sent"] + 4
        assert "frames_per_drain" not in after  # the histogram is gone: the two counts give its mean

    run(scenario())


class _BufferedTransport:
    """A transport that holds whatever `pending` says, and is open."""

    def __init__(self, pending: int = 0):
        self.pending = pending

    def get_write_buffer_size(self) -> int:
        return self.pending

    def is_closing(self) -> bool:
        return False


class _TransportWriter(_MockTransportWriter):
    def __init__(self, transport):
        super().__init__()
        self.transport = transport


def test_a_send_is_a_write_that_found_the_buffer_empty(run):
    """`WireStats.sends` counts the system calls asyncio makes at once: one
    `writelines` a frame written on its own (header and body together, or
    header and ciphertext), one where bytes were pending too (Python 3.12's
    socket transport calls `sendmsg` there whatever its buffer holds), none
    where no transport stands behind the writer; and one a drain."""
    from narwhal_tpu.network.auth import Session
    from narwhal_tpu.network.rpc import KIND_REQ, WireStats, _write_frame

    def sends(writer, body=b"body", session=None) -> int:
        before = WireStats.snapshot()
        _write_frame(writer, KIND_REQ, 1, 7, body, session)
        after = WireStats.snapshot()
        assert after["frames_sent"] == before["frames_sent"] + 1
        return after["sends"] - before["sends"]

    assert sends(_TransportWriter(_BufferedTransport())) == 1
    assert sends(_TransportWriter(_BufferedTransport()), body=b"") == 1
    assert sends(_TransportWriter(_BufferedTransport(pending=100))) == 1
    assert sends(_MockTransportWriter()) == 0
    session = Session(b"k" * 32, b"k" * 32)
    assert sends(_TransportWriter(_BufferedTransport()), session=session) == 1  # header and ciphertext

    async def on_loopback():
        server = RpcServer()

        async def on_tx(msg, peer):
            return None

        server.route(SubmitTransactionMsg, on_tx)
        port = await server.start("127.0.0.1", 0)
        net = NetworkClient()
        before = WireStats.snapshot()
        assert await net.unreliable_send(f"127.0.0.1:{port}", SubmitTransactionMsg(b"tx"))
        after = WireStats.snapshot()
        net.close()
        await server.stop()
        # A request and its Ack on an idle link: one drain each, one
        # `sendmsg` each.
        assert after["frames_sent"] - before["frames_sent"] == 2
        assert after["drains"] - before["drains"] == 2
        assert after["sends"] - before["sends"] == 2

    run(on_loopback())


class _CountingSocket:
    """Stands in for a socket transport's socket: forwards every call and
    records the names of the send calls."""

    def __init__(self, sock):
        self._sock = sock
        self.calls: list[str] = []

    def send(self, data, *args):
        self.calls.append("send")
        return self._sock.send(data, *args)

    def sendmsg(self, buffers, *args):
        self.calls.append("sendmsg")
        return self._sock.sendmsg(buffers, *args)

    def __getattr__(self, name):
        return getattr(self._sock, name)


async def _read_frames(reader, n: int) -> list[tuple[int, int, bytes]]:
    from narwhal_tpu.network.rpc import _read_frame

    out = []
    for _ in range(n):
        _, rid, _, lane, body = await _read_frame(reader)
        out.append((rid, lane, bytes(body)))
    return out


def test_a_loopback_burst_of_k_frames_costs_one_sendmsg(run):
    """On a real loopback socket a burst of K frames enqueued in one turn
    reaches the socket as ONE `sendmsg` (no `send` at all), counted as one
    send, and the peer reads the K frames whole and in order."""
    from narwhal_tpu.network.rpc import KIND_REQ, FrameSender, WireStats

    async def scenario():
        got = asyncio.get_running_loop().create_future()
        k = 8

        async def on_conn(reader, writer):
            got.set_result(await _read_frames(reader, k))
            writer.close()

        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        _, writer = await asyncio.open_connection("127.0.0.1", server.sockets[0].getsockname()[1])
        transport = writer.transport
        real = transport._sock
        transport._sock = spy = _CountingSocket(real)
        try:
            sender = FrameSender(writer)
            sent = [(rid, rid % 2, bytes([rid]) * (21 if rid % 2 else 3200)) for rid in range(1, k + 1)]
            before = WireStats.snapshot()
            for rid, lane, body in sent:
                sender.send(KIND_REQ, rid, 3, body, lane)
            await sender._task
            after = WireStats.snapshot()
        finally:
            transport._sock = real
        assert spy.calls == ["sendmsg"]
        assert (after["drains"] - before["drains"], after["sends"] - before["sends"]) == (1, 1)
        # Lanes 1 and 0 alternate, so the round-robin keeps the enqueue order.
        assert await asyncio.wait_for(got, 5.0) == sent
        writer.close()
        server.close()
        await server.wait_closed()

    run(scenario())


def test_a_drain_the_socket_takes_in_part_arrives_whole_and_in_order(run):
    """A drain far larger than the socket's buffers: the one `sendmsg` takes
    part of it, asyncio holds the rest and writes it as the peer reads, and
    every frame arrives whole, in the order it was written."""
    import socket

    from narwhal_tpu.network.rpc import KIND_REQ, FrameSender

    async def scenario():
        listener = socket.create_server(("127.0.0.1", 0))
        listener.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 8192)  # inherited by the accepted socket
        release = asyncio.Event()
        got = asyncio.get_running_loop().create_future()
        sent = [(rid, rid % 3, bytes([rid]) * size)
                for rid, size in enumerate((500_000, 21, 3200, 500_000, 0, 64_000, 500_000, 7), start=1)]

        async def on_conn(reader, writer):
            await release.wait()  # the peer reads nothing until the drain is under way
            got.set_result(await _read_frames(reader, len(sent)))
            writer.close()

        server = await asyncio.start_server(on_conn, sock=listener)
        _, writer = await asyncio.open_connection("127.0.0.1", listener.getsockname()[1])
        writer.transport.get_extra_info("socket").setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 8192)
        sender = FrameSender(writer)
        for rid, lane, body in sent:
            sender.send(KIND_REQ, rid, 3, body, lane)
        await asyncio.sleep(0)  # the drainer ran its one writelines and waits in drain()
        assert writer.transport.get_write_buffer_size() > 0, "the socket took only part of the drain"
        release.set()
        frames = await asyncio.wait_for(got, 10.0)
        await asyncio.wait_for(sender._task, 5.0)
        lanes = {lane: [f for f in sent if f[1] == lane] for lane in (1, 2, 0)}
        round_robin = [q[i] for i in range(3) for q in lanes.values() if i < len(q)]
        assert frames == round_robin
        writer.close()
        server.close()
        await server.wait_closed()

    run(scenario())


def test_a_drain_on_a_closing_transport_fails_without_writing(run):
    """A drainer that finds its transport closing writes nothing (asyncio's
    `writelines` would hold the bytes of a lost connection) and reports the
    connection as failed, as its `drain()` would have."""
    from narwhal_tpu.network.rpc import KIND_REQ, FrameSender, RpcError

    class _Closing(_BufferedTransport):
        def is_closing(self) -> bool:
            return True

    async def scenario():
        errors = []
        writer = _TransportWriter(_Closing())
        sender = FrameSender(writer, on_error=errors.append)
        sender.send(KIND_REQ, 1, 3, b"x")
        await sender._task
        assert writer.chunks == [] and writer.writelines_calls == 0
        assert [type(e) for e in errors] == [ConnectionResetError]
        with pytest.raises(RpcError):
            sender.send(KIND_REQ, 2, 3, b"y")

    run(scenario())


def test_a_drainer_start_is_a_burst_that_found_no_drainer_running(run):
    """`WireStats.drainer_starts` counts the drainer tasks `send` started:
    one for a burst of frames enqueued in one turn, one more for a burst
    after that drainer ended, none for a transport that drains inline."""
    from narwhal_tpu.network.rpc import KIND_REQ, FrameSender, WireStats

    class _InlineWriter(_MockTransportWriter):
        sync_drain = True

    async def scenario():
        before = WireStats.snapshot()
        sender = FrameSender(_MockTransportWriter())
        for rid in range(3):
            sender.send(KIND_REQ, rid, 1, b"x")
        await asyncio.sleep(0)
        await asyncio.sleep(0)  # the drainer's drain() returned and the task ended
        sender.send(KIND_REQ, 9, 1, b"x")
        await asyncio.sleep(0)
        FrameSender(_InlineWriter()).send(KIND_REQ, 10, 1, b"x")
        after = WireStats.snapshot()
        assert after["drainer_starts"] - before["drainer_starts"] == 2
        assert after["drains"] - before["drains"] == 3  # two by the drainers, one inline

    run(scenario())


def test_duplicate_server_fails_fast_without_placeholder(run):
    """Two RpcServers on the same explicit port must NOT silently co-bind
    (reuse_port splitting connections nondeterministically): a port that no
    allocator placeholder reserves is bound plainly, so the duplicate gets
    EADDRINUSE (ADVICE r3). Ports actually placeheld by
    config.get_available_port still co-bind through the placeholder."""
    from narwhal_tpu.config import get_available_port, port_is_placeheld
    from narwhal_tpu.network.rpc import RpcServer

    async def scenario():
        port = get_available_port()
        assert port_is_placeheld(port)
        a = RpcServer()
        await a.start("127.0.0.1", port)  # binds through the placeholder
        assert not port_is_placeheld(port)  # placeholder released on bind
        b = RpcServer()
        try:
            with pytest.raises(OSError):
                await b.start("127.0.0.1", port)
        finally:
            await a.stop()

    run(scenario(), timeout=30.0)


def test_placeheld_ports_env_enables_cobind(run, monkeypatch):
    """A harness parent that assigned the ports advertises its placeholders
    via NARWHAL_PLACEHELD_PORTS; children then co-bind with reuse_port."""
    from narwhal_tpu.config import port_is_placeheld

    monkeypatch.setenv("NARWHAL_PLACEHELD_PORTS", "all")
    assert port_is_placeheld(12345)
    monkeypatch.setenv("NARWHAL_PLACEHELD_PORTS", "7001, 7002")
    assert port_is_placeheld(7002)
    assert not port_is_placeheld(7003)

    async def noop():
        pass

    run(noop(), timeout=5.0)


def test_env_advertised_port_not_reusable_after_first_bind(run, monkeypatch):
    """The parent's NARWHAL_PLACEHELD_PORTS advertisement is spawn-time
    static; once a server in this process binds an advertised port, a
    second server on the same port (same node started twice, one port
    assigned to two roles) must fail fast instead of co-binding through
    the stale advertisement."""
    from narwhal_tpu.config import get_available_port, release_port
    from narwhal_tpu.network.rpc import RpcServer

    async def scenario():
        port = get_available_port()
        release_port(port)  # simulate: the placeholder lives in a parent
        monkeypatch.setenv("NARWHAL_PLACEHELD_PORTS", str(port))
        a = RpcServer()
        await a.start("127.0.0.1", port)
        b = RpcServer()
        try:
            with pytest.raises(OSError):
                await b.start("127.0.0.1", port)
        finally:
            await a.stop()
        # After stop, the advertisement applies again (node restart flow).
        c = RpcServer()
        await c.start("127.0.0.1", port)
        await c.stop()

    run(scenario(), timeout=30.0)
