"""Executor exactly-once across crashes at every cursor position.

Mirrors /root/reference/executor/src/tests/ replay tests: the application
persists ExecutionIndices atomically with each transaction's effects; after a
crash anywhere — mid-batch, exactly on a batch boundary, or between
certificates — a restarted Core re-executes the same consensus output and
every transaction is applied exactly once.
"""

import asyncio

import pytest

from chipbench.reference.formats import read_wal
from narwhal_tpu.channels import Channel
from narwhal_tpu.executor.core import ExecutorCore
from narwhal_tpu.executor.state import ExecutionIndices
from narwhal_tpu.executor import ExecutionState, get_restored_consensus_output
from narwhal_tpu.fixtures import CommitteeFixture, mock_certificate
from narwhal_tpu.node import SimpleExecutionState
from narwhal_tpu.stores import NodeStorage
from narwhal_tpu.types import Batch, Certificate, ConsensusOutput


class Crash(Exception):
    pass


class JournalState(ExecutionState):
    """Applies transactions to an append-only journal, persisting the cursor
    atomically with each effect (the ExecutionState contract); can be armed
    to crash BEFORE applying the Nth call (a crash after persisting the
    previous transaction, i.e. at an arbitrary cursor position)."""

    def __init__(self):
        self.journal: list[bytes] = []
        self.indices = ExecutionIndices()
        self.crash_at: int | None = None
        self.calls = 0

    async def handle_consensus_transaction(self, output, indices, transaction):
        if self.crash_at is not None and self.calls >= self.crash_at:
            raise Crash()
        self.calls += 1
        # Atomic effect+cursor persistence.
        self.journal.append(bytes(transaction))
        self.indices = indices
        return b""

    async def load_execution_indices(self) -> ExecutionIndices:
        return self.indices


def _output(f: CommitteeFixture, payload: dict) -> ConsensusOutput:
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    cert = mock_certificate(f.committee, f.authorities[0].public, 1, genesis, payload)
    return ConsensusOutput(certificate=cert, consensus_index=0)


def _run(coro):
    return asyncio.run(asyncio.wait_for(coro, 30))


@pytest.mark.parametrize("crash_at", list(range(0, 7)))
def test_exactly_once_output_stream_across_crash_points(crash_at):
    """The burst-drain core's output channel is exactly-once too: results
    applied before a mid-batch crash are flushed (never lost in the burst
    buffer), and replay — which skips below the watermark — never re-emits
    them. The full stream after restart is each tx once, in order."""

    async def scenario():
        f = CommitteeFixture(size=4)
        batches = {
            b"\x01" * 32: Batch(tuple(b"a%d" % i for i in range(4))),
            b"\x02" * 32: Batch(tuple(b"b%d" % i for i in range(2))),
        }
        payload = {d: 0 for d in batches}
        output = _output(f, payload)
        expected = [b"a0", b"a1", b"a2", b"a3", b"b0", b"b1"]

        state = JournalState()
        storage = NodeStorage(None)
        tx_output = Channel(100)
        core = ExecutorCore(
            state,
            storage.temp_batch_store,
            rx_subscriber=Channel(10),
            tx_output=tx_output,
        )
        core.execution_indices = await state.load_execution_indices()
        state.crash_at = crash_at
        try:
            await core.execute_certificate(output, batches)
        except Crash:
            pass
        state.crash_at = None
        recovered = await state.load_execution_indices()
        if recovered.next_certificate_index <= output.consensus_index:
            core2 = ExecutorCore(
                state,
                storage.temp_batch_store,
                rx_subscriber=Channel(10),
                tx_output=tx_output,
            )
            core2.execution_indices = recovered
            await core2.execute_certificate(output, batches)
        assert state.journal == expected
        emitted = []
        while True:
            item = tx_output.try_recv()
            if item is None:
                break
            emitted.append(item[1])
        assert emitted == expected, f"crash at {crash_at}: outputs {emitted}"

    _run(scenario())


@pytest.mark.parametrize("crash_at", list(range(0, 7)))
def test_exactly_once_across_crash_points(crash_at):
    """Two batches (4 + 2 txs, ordered by digest): crash before the Nth
    transaction for every N — including N=4, the batch boundary — restart,
    replay, and require the journal to hold each tx exactly once, in order."""

    async def scenario():
        f = CommitteeFixture(size=4)
        batches = {
            b"\x01" * 32: Batch(tuple(b"a%d" % i for i in range(4))),
            b"\x02" * 32: Batch(tuple(b"b%d" % i for i in range(2))),
        }
        payload = {d: 0 for d in batches}
        output = _output(f, payload)
        expected = [b"a0", b"a1", b"a2", b"a3", b"b0", b"b1"]

        state = JournalState()
        storage = NodeStorage(None)
        core = ExecutorCore(
            state,
            storage.temp_batch_store,
            rx_subscriber=Channel(10),
            tx_output=None,
        )
        core.execution_indices = await state.load_execution_indices()
        state.crash_at = crash_at
        try:
            await core.execute_certificate(output, batches)
            assert crash_at >= len(expected), "must crash before completing"
        except Crash:
            pass
        assert state.journal == expected[:crash_at]

        # "Restart": fresh Core, cursor recovered from the application. The
        # replay layer (get_restored_consensus_output, executor/__init__)
        # only re-delivers certificates at or past the recovered certificate
        # cursor — a fully executed certificate is not replayed.
        state.crash_at = None
        recovered = await state.load_execution_indices()
        if recovered.next_certificate_index <= output.consensus_index:
            core2 = ExecutorCore(
                state,
                storage.temp_batch_store,
                rx_subscriber=Channel(10),
                tx_output=None,
            )
            core2.execution_indices = recovered
            await core2.execute_certificate(output, batches)
        assert state.journal == expected, (
            f"crash at {crash_at}: journal {state.journal}"
        )

    _run(scenario())


class RecordingState(SimpleExecutionState):
    """The node's default state, recording the transactions it is handed;
    armed, it raises before its Nth call, as a process that dies there
    would."""

    def __init__(self, storage, crash_at: int | None = None):
        super().__init__(storage)
        self.handled: list[bytes] = []
        self.crash_at = crash_at

    async def handle_consensus_transaction(self, output, indices, transaction):
        if self.crash_at is not None and len(self.handled) >= self.crash_at:
            raise Crash()
        self.handled.append(bytes(transaction))
        return await super().handle_consensus_transaction(output, indices, transaction)


def _chain(f: CommitteeFixture, layout: list[list[int]]):
    """One certificate per entry of `layout`, each with batches of the
    given sizes, in digest order: (outputs, staged batches, every
    transaction in execution order, each batch's [start, end) in it)."""
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    outputs, batches, flat, spans = [], {}, [], []
    for i, sizes in enumerate(layout):
        payload = {}
        for j, n in enumerate(sizes):
            digest = bytes([16 * i + j + 1]) * 32
            batches[digest] = Batch(tuple(b"c%d.b%d.t%d" % (i, j, k) for k in range(n)))
            payload[digest] = 0
            spans.append((len(flat), len(flat) + n))
            flat.extend(batches[digest].transactions)
        cert = mock_certificate(f.committee, f.authorities[0].public, i + 1, genesis, payload)
        outputs.append(ConsensusOutput(certificate=cert, consensus_index=i))
    return outputs, batches, flat, spans


def _sequence(storage: NodeStorage, outputs) -> None:
    """What consensus leaves on disk for the replay layer to read back."""
    for out in outputs:
        storage.certificate_store.write(out.certificate)
        storage.consensus_store.write_consensus_state({}, out.consensus_index, out.certificate.digest)


def _cursor_writes(path: str) -> list[ExecutionIndices]:
    """Every cursor in the store's log, in the order it was written, read by
    the benchmark's plain WAL reader (it imports nothing of the program)."""
    return [ExecutionIndices.from_bytes(v) for _, v in read_wal(path, {"execution_indices"})["execution_indices"]]


def _boundaries(layout: list[list[int]]) -> list[ExecutionIndices]:
    """The cursor as each batch of `layout` ends."""
    return [
        ExecutionIndices(i, j + 1, 0) if j + 1 < len(sizes) else ExecutionIndices(i + 1, 0, 0)
        for i, sizes in enumerate(layout)
        for j in range(len(sizes))
    ]


def _drain(channel: Channel) -> list[bytes]:
    out = []
    while (item := channel.try_recv()) is not None:
        out.append(item[1])
    return out


async def _replay(path: str, batches) -> tuple[RecordingState, ExecutorCore, list[bytes]]:
    """A restarted node's executor: a store reopened from its log, the
    replay layer's outputs, a fresh output channel."""
    reopened = NodeStorage(path)
    state = RecordingState(reopened)
    restored = await get_restored_consensus_output(
        reopened.consensus_store, reopened.certificate_store, state
    )
    stream = Channel(1000)
    core = ExecutorCore(state, reopened.temp_batch_store, rx_subscriber=Channel(10), tx_output=stream)
    core.execution_indices = await state.load_execution_indices()
    for output in restored:
        await core.execute_certificate(output, batches)
    reopened.close()
    return state, core, _drain(stream)


GRAIN_CASES = {
    "one_batch_of_1": [[1]],
    "one_batch_of_40": [[40]],
    "three_batches_of_7": [[7, 7, 7]],
    "empty_certificate_between_two": [[3, 2], [], [4]],
}


@pytest.mark.parametrize("layout", list(GRAIN_CASES.values()), ids=list(GRAIN_CASES))
def test_default_state_writes_its_cursor_once_per_executed_batch(layout, tmp_path):
    """`SimpleExecutionState` on an on-disk WAL: one `execution_indices`
    record per executed batch; the store reopened from the log loads the
    executor's in-memory cursor; replaying on it applies nothing again."""

    async def scenario():
        f = CommitteeFixture(size=4)
        path = str(tmp_path / "db")
        outputs, batches, flat, _ = _chain(f, layout)
        storage = NodeStorage(path)
        _sequence(storage, outputs)
        state = RecordingState(storage)
        stream = Channel(1000)
        core = ExecutorCore(state, storage.temp_batch_store, rx_subscriber=Channel(10), tx_output=stream)
        core.execution_indices = await state.load_execution_indices()
        for output in outputs:
            await core.execute_certificate(output, batches)
        storage.close()
        assert state.handled == flat and _drain(stream) == flat
        assert core.execution_indices == ExecutionIndices(next_certificate_index=len(layout))

        assert _cursor_writes(path) == _boundaries(layout), "one cursor write per executed batch, at its end"

        reopened = NodeStorage(path)
        assert await SimpleExecutionState(reopened).load_execution_indices() == core.execution_indices
        reopened.close()

        again, _, emitted = await _replay(path, batches)
        assert again.handled == [] and emitted == []
        assert _cursor_writes(path) == _boundaries(layout)

    _run(scenario())


CRASH_LAYOUT = [[3, 2], [3]]  # two certificates: batches of 3 and 2, then of 3


@pytest.mark.parametrize("crash_at", list(range(8)))
def test_default_state_replays_an_interrupted_batch_from_its_first_transaction(crash_at, tmp_path):
    """The process dies before the default state's Nth call, its output
    channel with it. The restart, on the store reopened from the log,
    re-executes the interrupted batch from its first transaction and no
    batch that had completed, so its stream holds that batch once."""

    async def scenario():
        f = CommitteeFixture(size=4)
        path = str(tmp_path / "db")
        outputs, batches, flat, spans = _chain(f, CRASH_LAYOUT)
        storage = NodeStorage(path)
        _sequence(storage, outputs)
        state = RecordingState(storage, crash_at=crash_at)
        core = ExecutorCore(state, storage.temp_batch_store, rx_subscriber=Channel(10), tx_output=Channel(1000))
        core.execution_indices = await state.load_execution_indices()
        with pytest.raises(Crash):
            for output in outputs:
                await core.execute_certificate(output, batches)
        assert state.handled == flat[:crash_at]
        start, end = next(span for span in spans if span[0] <= crash_at < span[1])

        again, core2, emitted = await _replay(path, batches)
        storage.close()
        assert again.handled == flat[start:], f"crash at {crash_at}: re-executed {again.handled}"
        assert emitted == flat[start:]
        assert all(emitted.count(tx) == 1 for tx in flat[start:end])
        assert core2.execution_indices == ExecutionIndices(next_certificate_index=len(CRASH_LAYOUT))

    _run(scenario())
