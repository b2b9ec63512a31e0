"""Generic NodeDag (compression/tombstones) and the external Dag service.

Mirrors /root/reference/dag/src/node_dag.rs proptests (path-compression
invariants) and /root/reference/consensus/src/tests/dag_tests.rs (insert
ordering, causal reads, rounds, remove, notify_read)."""

import asyncio
import random
from dataclasses import dataclass, field

import pytest

from narwhal_tpu.consensus import Dag, ValidatorDagError
from narwhal_tpu.consensus.dag import NoCertificateForCoordinates, OutOfCertificates
from narwhal_tpu.dag import DroppedDigest, NodeDag, UnknownDigests
from narwhal_tpu.channels import Channel
from narwhal_tpu.fixtures import CommitteeFixture, make_optimal_certificates
from narwhal_tpu.types import Certificate


@dataclass
class V:
    digest: str
    _parents: list[str] = field(default_factory=list)
    _compressible: bool = False

    def parents(self):
        return list(self._parents)

    def compressible(self):
        return self._compressible


class TestNodeDag:
    def test_insert_rejects_unknown_parents(self):
        dag = NodeDag()
        with pytest.raises(UnknownDigests) as e:
            dag.try_insert(V("b", ["a"]))
        assert e.value.digests == ["a"]

    def test_insert_idempotent_and_heads(self):
        dag = NodeDag()
        dag.try_insert(V("a"))
        dag.try_insert(V("b", ["a"]))
        dag.try_insert(V("b", ["a"]))
        assert dag.size() == 2
        assert dag.has_head("b") and not dag.has_head("a")
        assert set(dag.head_digests()) == {"b"}

    def test_compression_bypasses_and_sweep_tombstones(self):
        dag = NodeDag()
        dag.try_insert(V("a"))
        dag.try_insert(V("m", ["a"], _compressible=True))
        dag.try_insert(V("b", ["m"]))
        assert dag.parents("b") == ["a"]  # m bypassed
        dropped = dag.sweep()
        assert dropped == 1
        assert dag.contains("m") and not dag.contains_live("m")  # tombstone
        with pytest.raises(DroppedDigest):
            dag.get("m")
        # inserting a child of a dropped parent skips it silently
        dag.try_insert(V("c", ["m", "b"]))
        assert dag.parents("c") == ["b"]

    def test_compressible_head_survives_sweep(self):
        dag = NodeDag()
        dag.try_insert(V("a", _compressible=True))
        assert dag.sweep() == 0
        assert dag.contains_live("a")

    def test_bft_skips_compressed(self):
        dag = NodeDag()
        dag.try_insert(V("a"))
        dag.try_insert(V("m", ["a"], _compressible=True))
        dag.try_insert(V("b", ["m"]))
        assert [v.digest for v in dag.bft("b")] == ["b", "a"]

    def test_random_dags_compression_invariants(self):
        # proptest analog (dag/src/lib.rs:289-377): after compressing, no
        # compressible vertex appears in any live parents list; traversals
        # reach exactly the incompressible causal history.
        rng = random.Random(3)
        for trial in range(5):
            dag = NodeDag()
            layers = [[f"0-{i}" for i in range(4)]]
            for v in layers[0]:
                dag.try_insert(V(v))
            for layer in range(1, 8):
                prev = layers[-1]
                cur = []
                for i in range(4):
                    name = f"{layer}-{i}"
                    parents = [p for p in prev if rng.random() > 0.3] or [prev[0]]
                    dag.try_insert(V(name, parents, _compressible=rng.random() < 0.4))
                    cur.append(name)
                layers.append(cur)
            for head in dag.head_digests():
                for p in dag.parents(head):
                    assert not dag._nodes[p].compressible
            dag.sweep()
            for d, node in dag._nodes.items():
                if node.live:
                    for p in node.parents:
                        assert dag.contains_live(p), (trial, d, p)


def _dag_with_rounds(rounds=4, size=4):
    f = CommitteeFixture(size=size)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_optimal_certificates(f.committee, 1, rounds, genesis)
    return f, certs


class TestDagService:
    def test_insert_and_causal_read(self, run):
        async def scenario():
            f, certs = _dag_with_rounds(4)
            dag = Dag(f.committee)
            for c in certs:
                await dag.insert(c)
            tip = certs[-1]
            causal = await dag.read_causal(tip.digest)
            # genesis is compressible (empty payload) but the round 1..4
            # mock certificates have no payload either -> all compressible
            # except... mock certs have empty payload, so only the tip
            # (start vertex) is reported.
            assert causal[0] == tip.digest
            rounds = await dag.node_read_causal(tip.origin, tip.round)
            assert rounds == causal

        run(scenario())

    def test_insert_with_payload_reports_history(self, run):
        async def scenario():
            f = CommitteeFixture(size=4)
            genesis = {c.digest for c in Certificate.genesis(f.committee)}
            from narwhal_tpu.fixtures import mock_certificate

            keys = f.committee.authority_keys()
            payload = {b"\x01" * 32: 0}
            r1 = [
                mock_certificate(f.committee, pk, 1, genesis, payload=payload)
                for pk in keys
            ]
            r2 = [
                mock_certificate(
                    f.committee, pk, 2, {c.digest for c in r1}, payload=payload
                )
                for pk in keys
            ]
            dag = Dag(f.committee)
            for c in r1 + r2:
                await dag.insert(c)
            causal = await dag.read_causal(r2[0].digest)
            assert set(causal) == {r2[0].digest} | {c.digest for c in r1}

        run(scenario())

    def test_rounds_and_remove(self, run):
        async def scenario():
            f = CommitteeFixture(size=4)
            genesis = {c.digest for c in Certificate.genesis(f.committee)}
            from narwhal_tpu.fixtures import mock_certificate

            keys = f.committee.authority_keys()
            payload = {b"\x02" * 32: 0}
            rows = []
            parents = genesis
            for r in range(1, 4):
                row = [
                    mock_certificate(f.committee, pk, r, parents, payload=payload)
                    for pk in keys
                ]
                rows.append(row)
                parents = {c.digest for c in row}
            dag = Dag(f.committee)
            for row in rows:
                for c in row:
                    await dag.insert(c)
            lo, hi = await dag.rounds(keys[0])
            assert (lo, hi) == (1, 3)
            # remove round-1 certificates: earliest live round advances
            await dag.remove([c.digest for c in rows[0]])
            lo, hi = await dag.rounds(keys[0])
            assert (lo, hi) == (2, 3)
            with pytest.raises(ValidatorDagError):
                await dag.remove([b"\x00" * 32])
            with pytest.raises(NoCertificateForCoordinates):
                await dag.node_read_causal(keys[0], 9)

        run(scenario())

    def test_rounds_empty_origin_errors(self, run):
        async def scenario():
            f = CommitteeFixture(size=4)
            dag = Dag(f.committee)
            # only genesis (round 0) is present and it's live until swept;
            # genesis certs exist for every key, so rounds() = (0, 0)
            keys = f.committee.authority_keys()
            lo, hi = await dag.rounds(keys[0])
            assert (lo, hi) == (0, 0)

        run(scenario())

    def test_notify_read_resolves_on_insert(self, run):
        async def scenario():
            f, certs = _dag_with_rounds(2)
            dag = Dag(f.committee)
            target = certs[-1]
            waiter = asyncio.ensure_future(dag.notify_read(target.digest))
            await asyncio.sleep(0.01)
            assert not waiter.done()
            for c in certs:
                await dag.insert(c)
            got = await asyncio.wait_for(waiter, 1.0)
            assert got.digest == target.digest

        run(scenario())

    def test_notify_read_fails_on_remove_and_prunes_cancelled(self, run):
        """Removed digests fail their waiters instead of leaving futures
        pending forever, and cancelled waiters are pruned from the
        obligations map (ADVICE r1)."""

        async def scenario():
            f = CommitteeFixture(size=4)
            genesis = {c.digest for c in Certificate.genesis(f.committee)}
            from narwhal_tpu.fixtures import mock_certificate

            keys = f.committee.authority_keys()
            payload = {b"\x03" * 32: 0}
            cert = mock_certificate(f.committee, keys[0], 1, genesis, payload=payload)
            dag = Dag(f.committee)
            await dag.insert(cert)
            waiter = asyncio.ensure_future(dag.notify_read(cert.digest))
            got = await asyncio.wait_for(waiter, 1.0)
            assert got.digest == cert.digest
            # Waiter for a digest that then gets removed -> fails fast.
            other = mock_certificate(f.committee, keys[1], 1, genesis, payload=payload)
            await dag.insert(other)
            pending = asyncio.ensure_future(dag.notify_read(b"\x0f" * 32))
            await asyncio.sleep(0.01)
            # remove() raises on the unknown digest; its waiter stays pending
            # (the feed may still insert it later), while the actually-removed
            # digest's slot is cleared.
            with pytest.raises(ValidatorDagError):
                await dag.remove([b"\x0f" * 32, other.digest])
            await asyncio.sleep(0.01)
            assert not pending.done()
            pending.cancel()
            await asyncio.sleep(0.01)
            assert b"\x0f" * 32 not in dag._obligations
            # White-box: a waiter parked on a digest that IS removed gets
            # failed (in the public flow inserts resolve waiters first, so
            # this guards the defensive path directly).
            victim = mock_certificate(f.committee, keys[2], 1, genesis, payload=payload)
            await dag.insert(victim)
            parked = asyncio.get_running_loop().create_future()
            dag._obligations[victim.digest].append(parked)
            await dag.remove([victim.digest])
            assert isinstance(parked.exception(), ValidatorDagError)
            assert victim.digest not in dag._obligations
            # Cancelled waiters are pruned.
            never = asyncio.ensure_future(dag.notify_read(b"\x0e" * 32))
            await asyncio.sleep(0.01)
            never.cancel()
            await asyncio.sleep(0.01)
            assert b"\x0e" * 32 not in dag._obligations

        run(scenario())

    def test_feed_from_channel(self, run):
        async def scenario():
            f, certs = _dag_with_rounds(3)
            ch = Channel(100)
            dag = Dag(f.committee, ch)
            dag.spawn()
            for c in certs:
                await ch.send(c)
            await asyncio.sleep(0.05)
            assert await dag.contains(certs[-1].digest)
            await dag.shutdown()

        run(scenario())


class TestDeviceDagService:
    def test_device_read_causal_matches_host(self, run):
        """backend="tpu", policy="device": ReadCausal/NodeReadCausal served
        by one reach_mask dispatch must return exactly the host BFS's
        result — same vertices, same canonical order (advisor r4: the
        external API's order must be backend-invariant) — across random
        DAGs with mixed payloads (compressible interiors), removals, and
        window coverage fallbacks."""
        import random

        from narwhal_tpu.fixtures import CommitteeFixture, mock_certificate

        rng = random.Random(7)

        async def scenario():
            for trial in range(4):
                f = CommitteeFixture(size=4)
                genesis = [c.digest for c in Certificate.genesis(f.committee)]
                keys = f.committee.authority_keys()
                host = Dag(f.committee)
                dev = Dag(f.committee, backend="tpu", window=16, policy="device")
                prev = list(genesis)
                all_certs = []
                for r in range(1, 7):
                    cur = []
                    for i, pk in enumerate(keys):
                        payload = (
                            {bytes([r, i]) * 16: 0} if rng.random() < 0.5 else {}
                        )
                        c = mock_certificate(
                            f.committee, pk, r,
                            set(rng.sample(prev, k=max(3, len(prev) - 1))),
                            payload=payload,
                        )
                        cur.append(c)
                        all_certs.append(c)
                    prev = [c.digest for c in cur]
                for c in all_certs:
                    await host.insert(c)
                    await dev.insert(c)
                # Remove a random earlier certificate on both.
                victim = all_certs[rng.randrange(len(all_certs) // 2)]
                await host.remove([victim.digest])
                await dev.remove([victim.digest])
                for c in all_certs[-8:]:
                    h = await host.read_causal(c.digest)
                    d = await dev.read_causal(c.digest)
                    assert h == d, (trial, c.round)  # exact canonical order
                    assert d[0] == c.digest  # start-first shape
                    n_h = await host.node_read_causal(c.origin, c.round)
                    n_d = await dev.node_read_causal(c.origin, c.round)
                    assert n_h == n_d
                assert dev.routing_stats()["dev_calls"] > 0

        run(scenario(), timeout=120.0)

    def test_concurrent_reads_coalesce_into_one_dispatch(self, run):
        """K concurrent ReadCausal requests on the device path must fuse
        into ONE vmapped reach_mask dispatch (the RTT-amortization the
        routing policy's device side is priced on)."""
        from narwhal_tpu.fixtures import CommitteeFixture, mock_certificate

        async def scenario():
            f = CommitteeFixture(size=4)
            genesis = [c.digest for c in Certificate.genesis(f.committee)]
            keys = f.committee.authority_keys()
            dev = Dag(f.committee, backend="tpu", window=16, policy="device")
            host = Dag(f.committee)
            prev = list(genesis)
            tips = []
            for r in range(1, 5):
                cur = [
                    mock_certificate(
                        f.committee, pk, r, set(prev),
                        payload={bytes([r, i]) * 16: 0},
                    )
                    for i, pk in enumerate(keys)
                ]
                for c in cur:
                    await dev.insert(c)
                    await host.insert(c)
                prev = [c.digest for c in cur]
                tips = cur
            dispatches = 0
            real_many = dev._device_causal_many

            def counting(starts):
                nonlocal dispatches
                dispatches += 1
                return real_many(starts)

            dev._device_causal_many = counting
            results = await asyncio.gather(
                *(dev.read_causal(c.digest) for c in tips)
            )
            assert dispatches == 1, "concurrent reads must share one dispatch"
            for c, got in zip(tips, results):
                assert got == await host.read_causal(c.digest)

        run(scenario(), timeout=120.0)

    def test_coalesced_batch_equivalent_to_sequential_host_walks(self, run):
        """The coalescing contract end to end: K concurrent read_causal
        calls with DISTINCT starts spread across rounds, fused into one
        batched reach_mask dispatch over the resident window, must return
        byte-identical causal histories to K sequential host BFS walks."""
        from narwhal_tpu.fixtures import CommitteeFixture, mock_certificate

        async def scenario():
            f = CommitteeFixture(size=4)
            genesis = [c.digest for c in Certificate.genesis(f.committee)]
            keys = f.committee.authority_keys()
            dev = Dag(f.committee, backend="tpu", window=16, policy="device")
            host = Dag(f.committee)
            prev = list(genesis)
            all_certs = []
            for r in range(1, 6):
                cur = [
                    mock_certificate(
                        f.committee, pk, r, set(prev),
                        payload={bytes([r, i]) * 16: 0} if (r + i) % 3 else {},
                    )
                    for i, pk in enumerate(keys)
                ]
                for c in cur:
                    await dev.insert(c)
                    await host.insert(c)
                prev = [c.digest for c in cur]
                all_certs.extend(cur)
            # K starts at different depths: rounds 2..5 across authorities.
            starts = [c for c in all_certs if c.round >= 2][:8]
            dispatches = 0
            real_many = dev._device_causal_many

            def counting(batch):
                nonlocal dispatches
                dispatches += 1
                return real_many(batch)

            dev._device_causal_many = counting
            fused = await asyncio.gather(
                *(dev.read_causal(c.digest) for c in starts)
            )
            assert dispatches == 1, "K concurrent reads must share one dispatch"
            assert dev.routing_stats()["last_coalesced_batch"] == len(starts)
            for c, got in zip(starts, fused):
                want = await host.read_causal(c.digest)
                assert got == want  # byte-identical digests, same order
                assert all(isinstance(d, bytes) for d in got)

        run(scenario(), timeout=120.0)

    def test_read_metrics_and_cost_model(self, run):
        """The per-route latency/EWMA metrics and the coalesced-batch-size
        gauge are recorded (ISSUE acceptance), and the cost model routes by
        amortized prediction: a device dispatch far cheaper than the host's
        per-vertex walk cost pulls adaptive traffic onto the device path."""
        from narwhal_tpu.consensus.metrics import ConsensusMetrics
        from narwhal_tpu.fixtures import CommitteeFixture, mock_certificate
        from narwhal_tpu.metrics import Registry

        async def scenario():
            f = CommitteeFixture(size=4)
            genesis = [c.digest for c in Certificate.genesis(f.committee)]
            keys = f.committee.authority_keys()
            registry = Registry()
            dag = Dag(
                f.committee, backend="tpu", window=16,
                metrics=ConsensusMetrics(registry),
            )
            prev = list(genesis)
            tip = None
            for r in range(1, 5):
                cur = [
                    mock_certificate(
                        f.committee, pk, r, set(prev),
                        payload={bytes([r, i]) * 16: 0},
                    )
                    for i, pk in enumerate(keys)
                ]
                for c in cur:
                    await dag.insert(c)
                prev = [c.digest for c in cur]
                tip = cur[0]
            # First adaptive request goes host, second probes the device.
            await dag.read_causal(tip.digest)
            await dag.read_causal(tip.digest)
            # Warm flag set by the probe's compile dispatch; now force the
            # model coefficients to a regime where the device must win:
            # host pays 10ms/vertex, a fused dispatch costs 1us.
            dag._host_pv = 0.010
            dag._dev_dispatch = 1e-6
            for _ in range(10):
                await dag.read_causal(tip.digest)
            stats = dag.routing_stats()
            assert stats["dev_calls"] >= 10  # cost model prefers the device
            assert stats["host_us_per_vertex"] is not None
            # Histogram counts per route and the EWMA gauges were recorded.
            assert registry.value(
                "consensus_dag_read_causal_latency_seconds", "host"
            ) >= 1
            assert registry.value(
                "consensus_dag_read_causal_latency_seconds", "device"
            ) >= 10
            assert registry.value("consensus_dag_read_route_ewma_ms", "host") > 0
            # Every fused dispatch here served one request; the gauge holds
            # the most recent batch size.
            assert (
                registry.get("consensus_dag_read_coalesced_batch_size").get() == 1
            )
            # And a genuinely concurrent burst moves the gauge to K.
            burst = await asyncio.gather(
                *(dag.read_causal(tip.digest) for _ in range(4))
            )
            assert len(burst) == 4
            assert (
                registry.get("consensus_dag_read_coalesced_batch_size").get() == 4
            )

        run(scenario(), timeout=120.0)

    def test_shutdown_fails_stranded_device_readers(self, run):
        """Shutdown with queued (unflushed) device requests must fail
        their futures — a reader awaiting a coalesced dispatch cannot be
        left hanging forever when the flush task is cancelled."""
        from narwhal_tpu.fixtures import CommitteeFixture

        async def scenario():
            f = CommitteeFixture(size=4)
            dag = Dag(f.committee, backend="tpu", window=16, policy="device")
            fut = asyncio.get_running_loop().create_future()
            dag._dev_queue.append((b"\x00" * 32, fut))
            await dag.shutdown()
            with pytest.raises(ValidatorDagError, match="shut down"):
                await fut

        run(scenario(), timeout=30.0)

    def test_adaptive_policy_routes_to_measured_faster_path(self, run):
        """policy="adaptive" (the default): after both paths have been
        measured, requests go to the faster one — on the virtual-CPU test
        host the BFS wins, so a long request stream must be served
        overwhelmingly by the host path (the measured-crossover fence for
        the r4 'device path 3-30x slower yet preferred' regression)."""
        from narwhal_tpu.fixtures import CommitteeFixture, mock_certificate

        async def scenario():
            f = CommitteeFixture(size=4)
            genesis = [c.digest for c in Certificate.genesis(f.committee)]
            keys = f.committee.authority_keys()
            dag = Dag(f.committee, backend="tpu", window=16)
            prev = list(genesis)
            tip = None
            for r in range(1, 5):
                cur = [
                    mock_certificate(
                        f.committee, pk, r, set(prev),
                        payload={bytes([r, i]) * 16: 0},
                    )
                    for i, pk in enumerate(keys)
                ]
                for c in cur:
                    await dag.insert(c)
                prev = [c.digest for c in cur]
                tip = cur[0]
            # Fake the device measurement as catastrophically slow so the
            # adaptive router must fence it.
            dag._ewma["dev"] = 1.0
            dag._dev_warmed.add(1)
            for _ in range(20):
                await dag.read_causal(tip.digest)
            stats = dag.routing_stats()
            assert stats["host_calls"] >= 19  # probes aside, host serves
            assert stats["ewma_host_ms"] is not None

        run(scenario(), timeout=120.0)
