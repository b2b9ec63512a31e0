"""Property-based tests (hypothesis): randomized invariants the reference
guards with proptest.

- Codec roundtrip fuzz over the ENTIRE message registry (the reference's
  serde equivalence tests, types/src/tests/batch_serde.rs:88 and
  node/tests/formats.rs): decode(encode(m)) == m and the wire form is a
  fixed point (canonical encoding stability).
- Compressed-DAG invariants on random DAGs
  (/root/reference/dag/src/lib.rs:289-377): parents() only ever returns
  incompressible nodes, compression preserves reachability into the
  incompressible set, bft visits every live ancestor exactly once.
- Host ordering invariants on random lossy DAGs: order_dag output is
  duplicate-free, causally closed under the committed set, and sorted by
  (round, origin).
- WAL torn-tail fuzz: a log truncated at EVERY byte offset recovers to a
  prefix of the committed operations (tests/test_storage.py covers a single
  truncation point; this sweeps them all).
"""

import random as pyrandom

import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from narwhal_tpu import messages as M
from narwhal_tpu.messages import REGISTRY, decode_message, encode_message
from narwhal_tpu.types import Batch, Certificate, Header, Vote

# -- strategies ------------------------------------------------------------

digest = st.binary(min_size=32, max_size=32)
pubkey = digest
signature = st.binary(min_size=64, max_size=64)
rnd = st.integers(min_value=0, max_value=2**62)
small_bytes = st.binary(max_size=96)
short_text = st.text(max_size=48)

batches = st.builds(Batch, st.lists(small_bytes, max_size=4).map(tuple))

headers = st.builds(
    Header,
    author=pubkey,
    round=rnd,
    epoch=st.integers(min_value=0, max_value=2**31),
    payload=st.dictionaries(digest, st.integers(min_value=0, max_value=2**31), max_size=3),
    parents=st.frozensets(digest, max_size=3),
    signature=signature,
)

votes = st.builds(
    Vote,
    header_digest=digest,
    round=rnd,
    epoch=st.integers(min_value=0, max_value=2**31),
    origin=pubkey,
    author=pubkey,
    signature=signature,
)

certificates = st.builds(
    Certificate,
    header=headers,
    signers=st.lists(
        st.integers(min_value=0, max_value=200), max_size=4, unique=True
    ).map(lambda xs: tuple(sorted(xs))),
    signatures=st.lists(signature, max_size=4).map(tuple),
)

_digest_tuple = st.lists(digest, max_size=4).map(tuple)

_r32 = st.binary(min_size=32, max_size=32)
compact_certificates = st.builds(
    Certificate,
    header=headers,
    signers=st.lists(
        st.integers(min_value=0, max_value=200), max_size=4, unique=True
    ).map(lambda xs: tuple(sorted(xs))),
    signatures=st.lists(_r32, max_size=4).map(tuple),
    agg_s=_r32,
)

u16 = st.integers(min_value=0, max_value=2**16 - 1)
u32 = st.integers(min_value=0, max_value=2**32 - 1)
_index_tuple = st.lists(u16, max_size=4).map(tuple)

MESSAGE_STRATEGIES = {
    M.Ack: st.builds(M.Ack),
    M.HeaderMsg: st.builds(M.HeaderMsg, headers),
    M.VoteMsg: st.builds(M.VoteMsg, votes),
    M.CertificateMsg: st.builds(
        M.CertificateMsg, st.one_of(certificates, compact_certificates)
    ),
    M.CertificateRefMsg: st.builds(
        M.CertificateRefMsg,
        header_digest=digest,
        round=rnd,
        epoch=st.integers(min_value=0, max_value=2**31),
        origin=pubkey,
        signers=st.lists(
            st.integers(min_value=0, max_value=200), max_size=4, unique=True
        ).map(lambda xs: tuple(sorted(xs))),
        rs=st.lists(_r32, max_size=4).map(tuple),
        agg_s=_r32,
    ),
    M.CertificatesRequest: st.builds(M.CertificatesRequest, _digest_tuple, pubkey),
    M.CertificatesBatchRequest: st.builds(
        M.CertificatesBatchRequest, _digest_tuple, pubkey
    ),
    M.CertificatesBatchResponse: st.builds(
        M.CertificatesBatchResponse,
        st.lists(st.tuples(digest, st.none() | certificates), max_size=3).map(tuple),
    ),
    M.CertificatesRangeRequest: st.builds(
        M.CertificatesRangeRequest, rnd, rnd, pubkey
    ),
    M.CertificatesRangeResponse: st.builds(M.CertificatesRangeResponse, _digest_tuple),
    M.PayloadAvailabilityRequest: st.builds(
        M.PayloadAvailabilityRequest, _digest_tuple, pubkey
    ),
    M.PayloadAvailabilityResponse: st.builds(
        M.PayloadAvailabilityResponse,
        st.lists(st.tuples(digest, st.booleans()), max_size=4).map(tuple),
    ),
    M.RelayMsg: st.builds(M.RelayMsg, pubkey, rnd, rnd, u16, small_bytes),
    M.RelayAckMsg: st.builds(M.RelayAckMsg, digest, pubkey),
    M.DeltaHeaderMsg: st.builds(
        M.DeltaHeaderMsg,
        author=pubkey,
        round=rnd,
        epoch=rnd,
        header_digest=digest,
        payload=st.lists(st.tuples(digest, u32), max_size=3).map(tuple),
        parent_indices=_index_tuple,
        signature=signature,
    ),
    M.HeaderResyncRequest: st.builds(M.HeaderResyncRequest, digest, pubkey, rnd, pubkey),
    M.HeaderResyncResponse: st.builds(
        M.HeaderResyncResponse, st.lists(headers, max_size=3).map(tuple)
    ),
    M.CertificateDeltaMsg: st.builds(
        M.CertificateDeltaMsg,
        header_digest=digest,
        round=rnd,
        epoch=rnd,
        origin=pubkey,
        signers=_index_tuple,
        signatures=st.lists(signature, max_size=4).map(tuple),
    ),
    M.Relay2Msg: st.builds(
        M.Relay2Msg, u16, u32, u16, st.integers(min_value=0, max_value=255), small_bytes
    ),
    M.Vote2Msg: st.builds(M.Vote2Msg, digest, pubkey, signature),
    M.RelayAck2Msg: st.builds(M.RelayAck2Msg, digest, u16),
    M.TelemetryScrapeMsg: st.builds(M.TelemetryScrapeMsg),
    M.TelemetryScrapeResponse: st.builds(M.TelemetryScrapeResponse, short_text),
    M.FlightDumpMsg: st.builds(M.FlightDumpMsg, u32),
    M.FlightDumpResponse: st.builds(M.FlightDumpResponse, small_bytes),
    M.SynchronizeMsg: st.builds(M.SynchronizeMsg, _digest_tuple, pubkey),
    M.CleanupMsg: st.builds(M.CleanupMsg, rnd),
    M.RequestBatchMsg: st.builds(M.RequestBatchMsg, digest),
    M.RequestBatchesMsg: st.builds(M.RequestBatchesMsg, _digest_tuple),
    M.DeleteBatchesMsg: st.builds(M.DeleteBatchesMsg, _digest_tuple),
    M.BackpressureMsg: st.builds(M.BackpressureMsg, u16),
    M.ReconfigureMsg: st.builds(M.ReconfigureMsg, short_text, short_text),
    M.OurBatchMsg: st.builds(M.OurBatchMsg, digest, st.integers(0, 2**31)),
    M.OthersBatchMsg: st.builds(M.OthersBatchMsg, digest, st.integers(0, 2**31)),
    M.RequestedBatchMsg: st.builds(
        M.RequestedBatchMsg, digest, small_bytes, st.booleans()
    ),
    M.RequestedBatchesMsg: st.builds(
        M.RequestedBatchesMsg,
        st.lists(st.tuples(digest, st.booleans(), small_bytes), max_size=3).map(
            tuple
        ),
    ),
    M.DeletedBatchesMsg: st.builds(M.DeletedBatchesMsg, _digest_tuple),
    M.WorkerErrorMsg: st.builds(M.WorkerErrorMsg, short_text),
    M.WorkerBatchMsg: st.builds(M.WorkerBatchMsg, small_bytes),
    M.WorkerBatchRequest: st.builds(M.WorkerBatchRequest, _digest_tuple),
    M.WorkerBatchResponse: st.builds(
        M.WorkerBatchResponse, st.lists(small_bytes, max_size=3).map(tuple)
    ),
    M.SubmitTransactionMsg: st.builds(M.SubmitTransactionMsg, small_bytes),
    M.SubmitTransactionStreamMsg: st.builds(
        M.SubmitTransactionStreamMsg,
        st.lists(small_bytes, max_size=3).map(tuple),
        st.none(),
    ),
    M.GetCollectionsRequest: st.builds(M.GetCollectionsRequest, _digest_tuple),
    M.GetCollectionsResponse: st.builds(
        M.GetCollectionsResponse,
        st.lists(
            st.tuples(
                digest,
                st.lists(
                    st.tuples(digest, st.lists(small_bytes, max_size=2).map(tuple)),
                    max_size=2,
                ).map(tuple),
                short_text,
            ),
            max_size=2,
        ).map(tuple),
    ),
    M.RemoveCollectionsRequest: st.builds(M.RemoveCollectionsRequest, _digest_tuple),
    M.ReadCausalRequest: st.builds(M.ReadCausalRequest, digest),
    M.ReadCausalResponse: st.builds(M.ReadCausalResponse, _digest_tuple),
    M.RoundsRequest: st.builds(M.RoundsRequest, pubkey),
    M.RoundsResponse: st.builds(M.RoundsResponse, rnd, rnd),
    M.NodeReadCausalRequest: st.builds(M.NodeReadCausalRequest, pubkey, rnd),
    M.NewNetworkInfoRequest: st.builds(
        M.NewNetworkInfoRequest,
        st.integers(0, 2**31),
        st.lists(st.tuples(pubkey, st.integers(0, 2**31), short_text), max_size=3).map(
            tuple
        ),
    ),
    M.GetPrimaryAddressRequest: st.builds(M.GetPrimaryAddressRequest),
    M.GetPrimaryAddressResponse: st.builds(M.GetPrimaryAddressResponse, short_text),
    M.NewEpochRequest: st.builds(M.NewEpochRequest, st.integers(0, 2**31)),
}

# Messages whose decode intentionally normalizes the representation (lazy
# wire-form carriers): field equality does not hold, canonical stability must.
_NORMALIZING = {M.SubmitTransactionStreamMsg}


def test_registry_fully_covered():
    """Every registered message tag has a fuzz strategy — adding a message
    without one fails CI here."""
    missing = [cls.__name__ for cls in REGISTRY.values() if cls not in MESSAGE_STRATEGIES]
    assert not missing, f"no strategy for: {missing}"


# One case per registered message, so a missing strategy or a broken codec
# fails under the message's name; the example budget is split to match.
@pytest.mark.parametrize(
    "cls", sorted(REGISTRY.values(), key=lambda c: c.__name__), ids=lambda c: c.__name__
)
@given(st.data())
@settings(max_examples=30, deadline=None, suppress_health_check=[HealthCheck.too_slow])
def test_message_roundtrip_whole_registry(cls, data):
    msg = data.draw(MESSAGE_STRATEGIES[cls])
    tag, body = encode_message(msg)
    assert tag == cls.TAG
    decoded = decode_message(tag, body)
    if cls not in _NORMALIZING:
        assert decoded == msg
    # Canonical stability: the wire form is a fixed point of decode∘encode.
    tag2, body2 = encode_message(decoded)
    assert (tag2, body2) == (tag, body)


# -- compressed DAG invariants ---------------------------------------------


class _Vertex:
    def __init__(self, digest, parents, compressible):
        self._digest = digest
        self._parents = parents
        self._compressible = compressible

    @property
    def digest(self):
        return self._digest

    def parents(self):
        return list(self._parents)

    def compressible(self):
        return self._compressible


dag_shapes = st.lists(
    st.tuples(
        st.integers(min_value=0, max_value=3),  # parent picks per node
        st.booleans(),  # compressible?
    ),
    min_size=1,
    max_size=40,
)


@given(dag_shapes, st.integers(0, 2**32))
@settings(max_examples=100, deadline=None)
def test_node_dag_compression_invariants(shape, seed):
    """dag/src/lib.rs:289-377: after arbitrary insert + make_compressible
    sequences, parents() never returns a compressible digest, and every
    parents() entry is an ancestor in the original edge relation."""
    from narwhal_tpu.dag import NodeDag

    rng = pyrandom.Random(seed)
    dag = NodeDag()
    inserted = []  # digests in insertion order
    edges = {}  # digest -> original parent digests
    compressible = set()
    for i, (nparents, comp) in enumerate(shape):
        d = i.to_bytes(32, "big")
        parents = (
            [rng.choice(inserted) for _ in range(min(nparents, len(inserted)))]
            if inserted
            else []
        )
        parents = list(dict.fromkeys(parents))
        dag.try_insert(_Vertex(d, parents, comp))
        inserted.append(d)
        edges[d] = parents
        if comp:
            compressible.add(d)
            dag.make_compressible(d)

    # Transitive ancestor sets in the ORIGINAL relation.
    ancestors = {}
    for d in inserted:
        anc = set()
        stack = list(edges[d])
        while stack:
            p = stack.pop()
            if p in anc:
                continue
            anc.add(p)
            stack.extend(edges[p])
        ancestors[d] = anc

    for d in inserted:
        if not dag.contains_live(d):
            continue
        got = dag.parents(d)
        for p in got:
            assert p not in compressible, "compressed parent leaked"
            assert p in ancestors[d], "parents() must stay within ancestors"
        # Compression preserves reachability: every incompressible ancestor
        # reachable only through compressible nodes must still be reachable
        # through parents() links.
        reach = set()
        stack = list(got)
        while stack:
            p = stack.pop()
            if p in reach or not dag.contains_live(p):
                continue
            reach.add(p)
            stack.extend(dag.parents(p))
        wanted = {
            a
            for a in ancestors[d]
            if a not in compressible and dag.contains_live(a)
        }
        assert wanted <= reach | set(got), "compression lost an ancestor"


# -- ordering invariants ----------------------------------------------------


@given(
    st.integers(min_value=4, max_value=7),  # committee size
    st.integers(min_value=3, max_value=12),  # rounds
    st.floats(min_value=0.0, max_value=0.4),  # failure probability
    st.integers(0, 2**32),
)
@settings(max_examples=25, deadline=None)
def test_order_dag_invariants(size, rounds, failure, seed):
    """order_dag (consensus/src/utils.rs:55-101): duplicate-free, sorted by
    (round, origin), and closed under uncommitted causal history."""
    from narwhal_tpu.consensus import Bullshark, ConsensusState
    from narwhal_tpu.fixtures import CommitteeFixture, make_certificates
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.types import Certificate

    f = CommitteeFixture(size=size)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_certificates(
        f.committee, 1, rounds, genesis,
        failure_probability=failure, rng=pyrandom.Random(seed),
    )
    state = ConsensusState(Certificate.genesis(f.committee))
    engine = Bullshark(f.committee, NodeStorage(None).consensus_store, 50)
    index = 0
    committed = []
    for c in certs:
        out = engine.process_certificate(state, index, c)
        index += len(out)
        committed.extend(o.certificate for o in out)

    digests = [c.digest for c in committed]
    assert len(digests) == len(set(digests)), "duplicate commit"
    committed_set = set(digests)
    by_digest = {c.digest: c for c in certs}
    # The per-authority implicit-commit rule (utils.rs:86-89 / state.update):
    # once a round R of authority A is committed, A's certificates at rounds
    # <= R are skipped forever — they count as covered, not as holes.
    max_committed_round = {}
    for cert in committed:
        max_committed_round[cert.origin] = max(
            max_committed_round.get(cert.origin, 0), cert.round
        )
    for cert in committed:
        for parent in cert.header.parents:
            parent_cert = by_digest.get(parent)
            if parent_cert is None:
                continue  # genesis
            assert (
                parent in committed_set
                or parent_cert.round
                <= max_committed_round.get(parent_cert.origin, 0)
            ), "causal hole in committed sequence"


# -- WAL torn-tail sweep -----------------------------------------------------


def test_wal_recovers_any_truncation(tmp_path):
    """Truncate the log at every byte offset: recovery must never raise and
    must yield a prefix of the committed operation sequence."""
    from narwhal_tpu.storage import StorageEngine

    path = str(tmp_path / "wal")
    engine = StorageEngine(path, use_native=False)
    cf_a = engine.column_family("a")
    cf_b = engine.column_family("b")
    states = []  # state after each record

    def snapshot():
        return (
            sorted(cf_a.iter()),
            sorted(cf_b.iter()),
        )

    states.append(snapshot())
    ops = []
    rng = pyrandom.Random(7)
    for i in range(12):
        k = bytes([i]) * 4
        v = rng.randbytes(rng.randint(0, 40))
        if i % 3 == 2:
            cf_a.delete(bytes([i - 1]) * 4)
        elif i % 2:
            cf_b.put(k, v)
        else:
            cf_a.put(k, v)
        states.append(snapshot())
    engine.close()

    with open(path + "/wal.log", "rb") as fobj:
        full = fobj.read()

    for cut in range(len(full) + 1):
        with open(path + "/wal.log", "wb") as fobj:
            fobj.write(full[:cut])
        eng2 = StorageEngine(path, use_native=False)
        got = (
            sorted(eng2.column_family("a").iter()),
            sorted(eng2.column_family("b").iter()),
        )
        eng2.close()
        assert got in states, f"truncation at {cut} is not a committed prefix"
    # Restore the intact log (leave tmp_path consistent).
    with open(path + "/wal.log", "wb") as fobj:
        fobj.write(full)
