"""Full-system tests over the in-process Cluster, mirroring
/root/reference/node/tests/node_smoke_test.rs,
executor/tests/consensus_integration_tests.rs and the cluster-based
nodes_bootstrapping/restart tests."""

import asyncio

import pytest

from narwhal_tpu.cluster import Cluster
from narwhal_tpu.messages import SubmitTransactionMsg, SubmitTransactionStreamMsg
from narwhal_tpu.network import NetworkClient


def test_cluster_commits_without_load(run):
    """Four nodes, no transactions: empty headers still drive Bullshark
    commits (leader election over empty certificates)."""

    async def scenario():
        cluster = Cluster(size=4, workers=1)
        await cluster.start()
        try:
            rounds = await cluster.assert_progress(commit_threshold=2, timeout=30.0)
            assert all(r >= 2 for r in rounds.values())
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=60.0)


def test_cluster_commits_transactions_e2e(run):
    """Client txs -> worker batches -> DAG -> Bullshark -> executor: the
    executed transactions come out the execution output channel in the same
    order on every node."""

    async def scenario():
        cluster = Cluster(size=4, workers=1)
        await cluster.start()
        client = NetworkClient()
        try:
            target = cluster.authorities[0].worker_transactions_address(0)
            txs = tuple(bytes([1]) * 8 + bytes([i]) for i in range(64))
            await client.request(target, SubmitTransactionStreamMsg(txs))

            async def executed(details, count):
                out = []
                while len(out) < count:
                    _, tx = await asyncio.wait_for(
                        details.primary.tx_execution_output.recv(), 30.0
                    )
                    out.append(tx)
                return out

            # Every node must execute all 64 txs, in an identical order.
            results = await asyncio.gather(
                *(executed(a, 64) for a in cluster.authorities)
            )
            assert all(len(r) == 64 for r in results)
            assert results[0] == results[1] == results[2] == results[3]
            assert set(results[0]) == set(txs)

            # §5.6 observability: every inter-task channel carries a depth
            # gauge wired into the node registry (metered_channel.rs:15-259).
            # Check REGISTRATION (render includes the metric's HELP/TYPE
            # lines), not .value(), which returns 0.0 for unknown names.
            rendered = cluster.authorities[0].primary.registry.render()
            for gauge in (
                "primary_channel_primary_messages_depth",
                "primary_channel_our_digests_depth",
                "node_channel_new_certificates_depth",
                "node_channel_consensus_output_depth",
            ):
                assert gauge in rendered, f"{gauge} not registered"
            # Executor progress counters (executor/src/metrics.rs parity).
            executed = cluster.authorities[0].metric("executor_executed_transactions")
            assert executed >= 64, executed
        finally:
            client.close()
            await cluster.shutdown()

    run(scenario(), timeout=90.0)


def test_cluster_survives_one_fault(run):
    """Stop one of four nodes: the remaining 2f+1 keep committing
    (the benchmark harness's `faults` parameter behavior)."""

    async def scenario():
        cluster = Cluster(size=4, workers=1)
        await cluster.start()
        try:
            await cluster.assert_progress(commit_threshold=2, timeout=30.0)
            await cluster.stop_node(3)
            before = min(
                a.metric("consensus_last_committed_round")
                for a in cluster.authorities
                if a.primary is not None
            )
            await cluster.assert_progress(
                expected_nodes=3, commit_threshold=int(before) + 4, timeout=30.0
            )
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=90.0)


def test_node_restart_recovers_from_store(run, tmp_path):
    """Restart a node with a persistent store: consensus state recovers and
    the node resumes committing (causal_completion_tests.rs restart)."""

    async def scenario():
        cluster = Cluster(size=4, workers=1, store_base=str(tmp_path))
        await cluster.start()
        try:
            await cluster.assert_progress(commit_threshold=2, timeout=30.0)
            await cluster.restart_node(0)
            rounds = await cluster.assert_progress(commit_threshold=4, timeout=30.0)
            assert rounds[cluster.authorities[0].name] >= 4
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=120.0)


def test_cluster_with_tpu_dag_backend(run, tmp_path):
    """--dag-backend tpu: production consensus runs through TpuBullshark's
    adjacency-tensor kernels. All nodes execute client transactions in an
    identical order, and a restarted node rebuilds its device DAG window
    from the store (TpuBullshark.recover) and resumes committing."""

    async def scenario():
        cluster = Cluster(
            size=4, workers=1, store_base=str(tmp_path), dag_backend="tpu"
        )
        await cluster.start()
        client = NetworkClient()
        try:
            from narwhal_tpu.tpu.dag_kernels import TpuBullshark

            assert isinstance(
                cluster.authorities[0].primary.consensus.protocol, TpuBullshark
            )
            target = cluster.authorities[0].worker_transactions_address(0)
            txs = tuple(bytes([7]) * 8 + bytes([i]) for i in range(32))
            await client.request(target, SubmitTransactionStreamMsg(txs))

            async def executed(details, count):
                out = []
                while len(out) < count:
                    _, tx = await asyncio.wait_for(
                        details.primary.tx_execution_output.recv(), 30.0
                    )
                    out.append(tx)
                return out

            results = await asyncio.gather(
                *(executed(a, 32) for a in cluster.authorities)
            )
            assert all(len(r) == 32 for r in results)
            assert results[0] == results[1] == results[2] == results[3]
            assert set(results[0]) == set(txs)

            # Restart: the fresh TpuBullshark must recover its window from
            # the recovered ConsensusState and keep committing.
            await cluster.restart_node(0)
            before = max(
                a.metric("consensus_last_committed_round")
                for a in cluster.authorities
                if a.primary is not None
            )
            rounds = await cluster.assert_progress(
                commit_threshold=int(before) + 2, timeout=30.0
            )
            assert rounds[cluster.authorities[0].name] >= int(before) + 2
        finally:
            client.close()
            await cluster.shutdown()

    run(scenario(), timeout=150.0)


def test_cluster_with_verification_pool(run):
    """crypto_backend="pool": the async pre-verification stage (coalesced
    batch verification off the Core's loop) must preserve liveness and
    ordering; a forged certificate must still be rejected."""

    async def scenario():
        cluster = Cluster(size=4, workers=1, crypto_backend="pool")
        await cluster.start()
        client = NetworkClient()
        try:
            target = cluster.authorities[0].worker_transactions_address(0)
            txs = tuple(bytes([9]) * 16 + bytes([i]) for i in range(32))
            await client.request(target, SubmitTransactionStreamMsg(txs))

            # Forge a certificate with garbage signatures at node 1.
            from dataclasses import replace as dreplace

            from narwhal_tpu.fixtures import mock_certificate
            from narwhal_tpu.messages import CertificateMsg
            from narwhal_tpu.types import Certificate

            genesis = {
                c.digest for c in Certificate.genesis(cluster.committee)
            }
            # Unique payload so the forged digest cannot collide with any
            # legitimately produced certificate.
            forged = mock_certificate(
                cluster.committee,
                cluster.authorities[0].name,
                1,
                genesis,
                payload={b"\xab" * 32: 0},
            )
            forged = dreplace(
                forged,
                signers=(0, 1, 2),
                signatures=(b"\x00" * 64, b"\x01" * 64, b"\x02" * 64),
            )
            # Deliver it as an authenticated committee peer so it passes
            # transport auth and exercises signature verification.
            from narwhal_tpu.network import Credentials, committee_resolver

            peer_client = NetworkClient(
                credentials=Credentials(
                    cluster.fixture.authorities[0].network_keypair,
                    committee_resolver(
                        lambda: cluster.committee, lambda: cluster.worker_cache
                    ),
                )
            )
            await peer_client.unreliable_send(
                cluster.authorities[1].primary.address, CertificateMsg(forged)
            )
            peer_client.close()

            rounds = await cluster.assert_progress(commit_threshold=3, timeout=30.0)
            assert all(r >= 3 for r in rounds.values())
            assert not cluster.authorities[1].primary.storage.certificate_store.contains(
                forged.digest
            )
        finally:
            client.close()
            await cluster.shutdown()

    run(scenario(), timeout=90.0)


def test_cluster_with_sharded_tpu_dag_backend(run, tmp_path):
    """--dag-backend tpu --dag-shards 2: the node wires a mesh into
    TpuBullshark, whose production chain_commit dispatch shards the
    committee axis across two devices. The committee still commits and
    executes transactions identically on every node."""

    async def scenario():
        cluster = Cluster(
            size=4, workers=1, store_base=str(tmp_path),
            dag_backend="tpu", dag_shards=2,
        )
        await cluster.start()
        client = NetworkClient()
        try:
            proto = cluster.authorities[0].primary.consensus.protocol
            assert proto.mesh is not None and proto.mesh.shape["auth"] == 2
            target = cluster.authorities[0].worker_transactions_address(0)
            txs = tuple(bytes([9]) * 8 + bytes([i]) for i in range(16))
            await client.request(target, SubmitTransactionStreamMsg(txs))

            async def executed(details, count):
                out = []
                while len(out) < count:
                    _, tx = await asyncio.wait_for(
                        details.primary.tx_execution_output.recv(), 30.0
                    )
                    out.append(tx)
                return out

            results = await asyncio.gather(
                *(executed(a, 16) for a in cluster.authorities)
            )
            assert all(r == results[0] for r in results)
        finally:
            client.close()
            await cluster.shutdown()

    run(scenario(), timeout=90.0)


@pytest.mark.slow
def test_twenty_node_committee_with_faults(run):
    """Committee scaling (BASELINE configs #4-5 risk): a 20-node in-process
    committee commits, and keeps committing after f=6 nodes die (the
    remaining 14 hold a 2f+1 quorum). Exercises proposer fan-in, certificate
    aggregation and window sizing at a committee size kernels can't see."""

    async def scenario():
        cluster = Cluster(size=20, workers=1)
        await cluster.start()
        try:
            await cluster.assert_progress(commit_threshold=3, timeout=60.0)
            for i in range(14, 20):
                await cluster.stop_node(i)
            before = min(
                a.metric("consensus_last_committed_round")
                for a in cluster.authorities
                if a.primary is not None
            )
            await cluster.assert_progress(
                expected_nodes=14, commit_threshold=int(before) + 4, timeout=60.0
            )
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=150.0)


@pytest.mark.slow
def test_fifty_node_committee_liveness(run):
    """The north-star committee size: a 50-node in-process committee over
    the authenticated mesh reaches lockstep commits (each round is ~7.5k
    signed+sealed control messages on this host's single core, so the
    assertion is liveness, not throughput; `python -m benchmark.liveness
    --nodes 50` is the same drive with wire counts)."""
    from narwhal_tpu.config import Parameters

    async def scenario():
        cluster = Cluster(
            size=50, workers=1,
            parameters=Parameters(max_header_delay=1.0, max_batch_delay=0.5),
        )
        await cluster.start()
        try:
            rounds = await cluster.assert_progress(
                commit_threshold=2, timeout=240.0
            )
            assert len(rounds) == 50  # every primary reported progress
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=300.0)


def test_verify_rule_validated_at_startup(tmp_path):
    """parameters.verify_rule is a committee-wide accept-set contract: a
    cpu/pool node (host library = strict/cofactorless rule) must refuse to
    start under verify_rule=cofactored — mixing the two rules in one
    committee is a consensus-split vector on crafted torsion signatures
    (ADVICE r3; narwhal_tpu/tpu/verifier.py msm_epilogue_check)."""
    from dataclasses import replace

    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.node import NodeStorage, PrimaryNode

    fx = CommitteeFixture(size=4)
    auth = fx.authorities[0]
    params = replace(fx.parameters, verify_rule="cofactored")
    for backend in ("cpu", "pool"):
        with pytest.raises(ValueError, match="cofactored"):
            PrimaryNode(
                auth.keypair,
                fx.committee,
                fx.worker_cache,
                params,
                NodeStorage(None),
                crypto_backend=backend,
            )
    with pytest.raises(ValueError, match="verify_rule"):
        PrimaryNode(
            auth.keypair,
            fx.committee,
            fx.worker_cache,
            replace(fx.parameters, verify_rule="bogus"),
            NodeStorage(None),
        )


def test_verify_shards_validated_and_wired(tmp_path):
    """--verify-shards: a node boots with the VerifyService's flushes
    sharded over a 'data' CPU mesh (the §7.8a verifier service at §5.8
    scale), mis-sized shard counts fail AT STARTUP (bucket divisibility,
    like the verify_rule check), and the flag requires the tpu backend.
    Also: parameters.cert_format is validated at startup (advisor r4 — a
    typo must not silently run the 'full' wire form in a 'compact'
    committee)."""
    from dataclasses import replace

    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.node import NodeStorage, PrimaryNode
    from narwhal_tpu.tpu.verifier import VerifyService

    fx = CommitteeFixture(size=4)
    auth = fx.authorities[0]

    def make(**kw):
        return PrimaryNode(
            auth.keypair,
            fx.committee,
            fx.worker_cache,
            kw.pop("parameters", fx.parameters),
            NodeStorage(None),
            **kw,
        )

    with pytest.raises(ValueError, match="verify-shards"):
        make(crypto_backend="cpu", verify_shards=2)
    # 3 does not divide the service's fixed dispatch bucket: the boot must
    # fail, not the first verify — and with ConfigError specifically, the
    # class the node treats as never-fallback-able.
    from narwhal_tpu.config import ConfigError

    with pytest.raises(ConfigError, match="divide"):
        make(crypto_backend="tpu", verify_shards=3)
    with pytest.raises(ValueError, match="cert_format"):
        make(parameters=replace(fx.parameters, cert_format="compat"))

    node = make(crypto_backend="tpu", verify_shards=2)
    try:
        svc = node.crypto_pool
        assert isinstance(svc, VerifyService)
        assert svc.verifier.mesh is not None
        assert svc.verifier.mesh.shape["data"] == 2
        # Catch-up sync shares the same batched lane (advisor r4).
        assert node.block_synchronizer.crypto_pool is svc
    finally:
        if isinstance(node.crypto_pool, VerifyService):
            node.crypto_pool.shutdown()


def _bare_primary(**kw):
    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.node import NodeStorage, PrimaryNode

    fx = CommitteeFixture(size=4)
    auth = fx.authorities[0]
    return PrimaryNode(
        auth.keypair,
        fx.committee,
        fx.worker_cache,
        kw.pop("parameters", fx.parameters),
        NodeStorage(None),
        **kw,
    )


@pytest.mark.parametrize("rule", ["strict", "cofactored"])
def test_tpu_crypto_backend_never_degrades_to_host(monkeypatch, rule):
    """--crypto-backend tpu never serves from the host: whatever stops the
    device verifier from being built — an environmental failure inside
    jax as much as an operator's ConfigError — stops the boot, under BOTH
    verify rules (the strict rule used to log one line and serve from the
    host pool, looking healthy with no chip behind it)."""
    from dataclasses import replace

    from narwhal_tpu.config import Parameters
    from narwhal_tpu.tpu.verifier import VerifyService

    def boom(mode, shards=1, **kw):
        raise ValueError("XLA backend initialization failed")  # environmental

    monkeypatch.setattr(VerifyService, "shared", boom)
    with pytest.raises(ValueError, match="XLA backend initialization failed"):
        _bare_primary(
            crypto_backend="tpu",
            parameters=replace(Parameters(), verify_rule=rule),
        )


@pytest.mark.parametrize(
    "backend", [{"crypto_backend": "tpu"}, {"dag_backend": "tpu"}]
)
def test_device_backend_refuses_an_unasked_for_cpu_platform(monkeypatch, backend):
    """With libtpu installed and no chip JAX itself lands on CPU; a node
    asked for a device backend then refuses to boot unless JAX_PLATFORMS
    names cpu explicitly (conftest does, for every other test)."""
    from narwhal_tpu.config import ConfigError

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ConfigError, match="no accelerator"):
        _bare_primary(**backend)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")  # cpu named: a rehearsal
    node = _bare_primary(dag_backend="tpu")
    node.storage.close()


def test_more_shards_than_devices_is_a_config_error():
    """Neither shard flag moves to virtual CPU devices behind the
    operator's back: conftest forces 8 host devices, 16 is too many."""
    from narwhal_tpu.config import ConfigError
    from narwhal_tpu.tpu.verifier import data_mesh

    with pytest.raises(ConfigError, match="exceeds the 8 cpu devices"):
        data_mesh(16)
    with pytest.raises(ConfigError, match="exceeds the 8 cpu devices"):
        _bare_primary(crypto_backend="tpu", verify_shards=16)
    with pytest.raises(ConfigError, match="--dag-shards 16 exceeds the 8 cpu"):
        _bare_primary(dag_backend="tpu", dag_shards=16)


@pytest.mark.slow  # the device-crypto kernel compiles take minutes on a
# 1-core CPU-backend host (the persistent cache is CPU-disabled); the
# real-chip twin is the round artifact
def test_cluster_with_tpu_crypto_shared_service(run):
    """crypto_backend="tpu": the whole committee shares ONE process-wide
    VerifyService (merged flushes sealed on the loop, one collect thread) —
    certificates verify through the device kernel path and commits advance
    (on conftest's CPU devices; the real-chip twin is the round artifact).

    The service is pre-seeded with a small-bucket verifier and warmed: on
    this 1-core CPU host an in-protocol first compile would eat the whole
    progress window (production pays this once at boot, inside the bench's
    warmup_timeout)."""
    from narwhal_tpu.tpu.verifier import TpuVerifier, VerifyService

    svc = VerifyService(
        TpuVerifier(max_bucket=32, msm_min_bucket=16, mode="msm"),
        max_batch=32,
        max_delay=0.002,
    )
    from narwhal_tpu.crypto import KeyPair

    kp = KeyPair.generate()
    item = (kp.public, b"warmup", kp.sign(b"warmup"))
    for size in (16, 32):
        assert all(svc.verifier([item] * size))
    VerifyService._shared["msm:1"] = svc

    async def scenario():
        cluster = Cluster(size=4, workers=1, crypto_backend="tpu")
        assert cluster.parameters.verify_rule == "cofactored"
        await cluster.start()
        try:
            rounds = await cluster.assert_progress(commit_threshold=2, timeout=180.0)
            assert all(r >= 2 for r in rounds.values())
            # Every node's pool is the same process-wide service.
            pools = {id(a.primary.crypto_pool) for a in cluster.authorities}
            assert len(pools) == 1
            assert cluster.authorities[0].primary.crypto_pool is svc
        finally:
            await cluster.shutdown()

    try:
        run(scenario(), timeout=300.0)
    finally:
        svc.shutdown()


@pytest.mark.slow  # same compile bill as the shared-service cluster test
def test_verify_service_merges_and_survives_loops(run):
    """VerifyService is loop-agnostic: requests from sequential event loops
    resolve correctly, bad signatures are rejected, and an msm-mode service
    propagates dispatch failures instead of host-fallback (accept-set
    safety)."""
    import asyncio

    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.tpu.verifier import TpuVerifier, VerifyService

    kp = KeyPair.generate()
    good = (kp.public, b"m", kp.sign(b"m"))
    bad = (kp.public, b"x", kp.sign(b"m"))
    svc = VerifyService(
        TpuVerifier(max_bucket=64, msm_min_bucket=16, mode="msm"),
        max_batch=64,
        max_delay=0.002,
    )
    try:
        async def burst():
            return await asyncio.gather(
                *(svc.verify(*good) for _ in range(20)), svc.verify(*bad)
            )

        # Two separate loops back to back — the service must serve both.
        res1 = asyncio.run(burst())
        res2 = asyncio.run(burst())
        for res in (res1, res2):
            assert res[:-1] == [True] * 20 and res[-1] is False

        # Dispatch failure with no safe fallback (msm): error propagates.
        def boom(items):
            raise RuntimeError("device lost")

        svc.verifier.submit = boom  # type: ignore[assignment]
        async def failing():
            with pytest.raises(RuntimeError, match="device lost"):
                await svc.verify(*good)

        asyncio.run(failing())
    finally:
        svc.shutdown()


def test_byzantine_peer_equivocation_and_stale_epoch(run, caplog):
    """A committee member gone byzantine: it equivocates (two validly signed
    round-1 headers with different parent sets) and replays a wrong-epoch
    header, from an authenticated mesh identity. The equivocation guard
    (primary/core.py process_header; core.rs:281-308) must trigger
    observably — the first header's vote digest stays recorded, the second
    is refused with a logged warning — the stale-epoch header is dropped,
    and the honest quorum keeps committing throughout. This exercises
    adversarial-peer behavior the reference's cluster tests never do (they
    are crash-fault only, test_utils/src/cluster.rs:169)."""
    import logging

    from narwhal_tpu.network import Credentials, committee_resolver
    from narwhal_tpu.types import Certificate, Header

    caplog.set_level(logging.DEBUG, logger="narwhal.primary")

    async def scenario():
        cluster = Cluster(size=4, workers=1)
        byz = cluster.fixture.authorities[3]
        await cluster.start(3)  # the byzantine member never runs a node
        try:
            await cluster.assert_progress(commit_threshold=2, timeout=60.0)

            client = NetworkClient(
                credentials=Credentials(
                    byz.network_keypair,
                    committee_resolver(
                        lambda: cluster.committee, lambda: cluster.worker_cache
                    ),
                )
            )
            from narwhal_tpu.messages import HeaderMsg

            genesis = sorted(
                c.digest for c in Certificate.genesis(cluster.committee)
            )
            epoch = cluster.committee.epoch
            # Two quorum-sized but different parent subsets => two distinct,
            # validly signed headers for the same (author, round).
            h1 = Header.build(byz.public, 1, epoch, {}, genesis[:3], byz.keypair)
            h2 = Header.build(byz.public, 1, epoch, {}, genesis[1:], byz.keypair)
            assert h1.digest != h2.digest
            target = cluster.authorities[0].primary.address
            await client.unreliable_send(target, HeaderMsg(h1))
            await asyncio.sleep(1.0)
            await client.unreliable_send(target, HeaderMsg(h2))
            # Wrong-epoch replay: validly signed, stale epoch.
            h3 = Header.build(byz.public, 1, epoch + 7, {}, genesis[:3], byz.keypair)
            await client.unreliable_send(target, HeaderMsg(h3))
            await asyncio.sleep(1.0)
            client.close()

            # The guard recorded the FIRST header's vote and refused the
            # equivocating twin, loudly.
            store = cluster.authorities[0].primary.storage.vote_digest_store
            last = store.read(byz.public)
            assert last is not None and last == (1, h1.digest)
            primary_logs = [
                r.getMessage()
                for r in caplog.records
                if r.name.startswith("narwhal.primary")
            ]
            assert any("equivocated" in m for m in primary_logs), primary_logs[-20:]
            assert any("stale" in m.lower() for m in primary_logs), primary_logs[-20:]

            # Liveness: the honest quorum keeps committing after the attack.
            rounds = await cluster.assert_progress(commit_threshold=4, timeout=60.0)
            assert all(r >= 4 for r in rounds.values())
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=180.0)


def test_cluster_with_compact_certificates(run, tmp_path):
    """Parameters.cert_format="compact": certificates assemble as
    half-aggregated proofs, broadcast by reference (CertificateRefMsg,
    header by digest), peers rebuild them from their header stores, and
    the committee commits transactions with identical order. The pool
    backend exercises the host aggregate-verify path end-to-end."""
    from dataclasses import replace

    from narwhal_tpu.config import Parameters

    async def scenario():
        cluster = Cluster(
            size=4,
            workers=1,
            store_base=str(tmp_path),
            crypto_backend="pool",
            parameters=Parameters(
                max_header_delay=0.1,
                max_batch_delay=0.1,
                cert_format="compact",
            ),
        )
        await cluster.start()
        try:
            rounds = await cluster.assert_progress(commit_threshold=3, timeout=90.0)
            assert all(r >= 3 for r in rounds.values())
            # The stored certificates really are the compact form.
            store = cluster.authorities[0].primary.storage.certificate_store
            compact_seen = 0
            for other in cluster.authorities[1:]:
                for cert in store.after_round(1):
                    if cert.origin == other.name and cert.is_compact:
                        compact_seen += 1
                        break
            assert compact_seen >= 2, "peers' certificates not compact"
        finally:
            await cluster.shutdown()

    run(scenario(), timeout=150.0)
