"""TPU DAG kernel equivalence: the vectorized adjacency-tensor commit walk
must reproduce the host engine's sequence bit-for-bit on arbitrary DAGs.
Runs on the virtual CPU backend (conftest); chip_smoke.py exercises the same
kernels on the real chip."""

import random

import numpy as np
import pytest

from narwhal_tpu.consensus import Bullshark, ConsensusState
from narwhal_tpu.fixtures import CommitteeFixture, make_certificates, make_optimal_certificates
from narwhal_tpu.stores import NodeStorage
from narwhal_tpu.tpu.dag_kernels import DagWindow, TpuBullshark, leader_support, reach_mask
from narwhal_tpu.types import Certificate

from tests.test_consensus import fixed_leader

GC = 50


def _run_both(
    size, rounds, failure, seed, gc=GC, leader_fn=fixed_leader, window=None,
    host_cls=Bullshark, dev_cls=TpuBullshark, dev_kwargs=None,
):
    f = CommitteeFixture(size=size)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_certificates(
        f.committee, 1, rounds, genesis,
        failure_probability=failure, rng=random.Random(seed),
    )
    host_state = ConsensusState(Certificate.genesis(f.committee))
    tpu_state = ConsensusState(Certificate.genesis(f.committee))
    host = host_cls(f.committee, NodeStorage(None).consensus_store, gc, leader_fn=leader_fn)
    dev = dev_cls(f.committee, NodeStorage(None).consensus_store, gc,
                  leader_fn=leader_fn, window=window, **(dev_kwargs or {}))
    host_seq, dev_seq = [], []
    hi = di = 0
    for c in certs:
        hs = host.process_certificate(host_state, hi, c)
        ds = dev.process_certificate(tpu_state, di, c)
        hi += len(hs)
        di += len(ds)
        host_seq.extend(hs)
        dev_seq.extend(ds)
        assert [o.certificate.digest for o in hs] == [o.certificate.digest for o in ds], (
            f"diverged at round {c.round}"
        )
    assert host_state.last_committed == tpu_state.last_committed
    assert [o.consensus_index for o in host_seq] == [o.consensus_index for o in dev_seq]
    return host_seq


def test_equivalence_optimal_dag():
    seq = _run_both(size=4, rounds=12, failure=0.0, seed=0)
    assert len(seq) > 30


def test_equivalence_lossy_dags():
    for seed in range(5):
        _run_both(size=4, rounds=25, failure=0.3, seed=seed)


def test_equivalence_larger_committee():
    _run_both(size=10, rounds=15, failure=0.15, seed=3)


def test_equivalence_weighted_leader():
    # default (stake-weighted) leader election on both sides
    _run_both(size=7, rounds=20, failure=0.2, seed=1, leader_fn=None)


def test_equivalence_small_window_slides():
    # Window smaller than the run length forces sliding + GC drops.
    seq = _run_both(size=4, rounds=60, failure=0.0, seed=0, gc=10, window=24)
    assert len(seq) > 200


def test_equivalence_tusk_optimal_and_lossy():
    """TpuTusk reproduces the host Tusk engine bit-for-bit (the asynchronous
    commit rule: leader two rounds below the wait round)."""
    from narwhal_tpu.consensus import Tusk
    from narwhal_tpu.tpu.dag_kernels import TpuTusk

    seq = _run_both(
        size=4, rounds=14, failure=0.0, seed=0, host_cls=Tusk, dev_cls=TpuTusk
    )
    assert len(seq) > 20
    for seed in range(3):
        _run_both(
            size=4, rounds=25, failure=0.3, seed=seed, host_cls=Tusk, dev_cls=TpuTusk
        )
    _run_both(
        size=7, rounds=20, failure=0.15, seed=2,
        leader_fn=None, host_cls=Tusk, dev_cls=TpuTusk,
    )


def _auth_mesh(auth, data=1):
    """A CPU device mesh with an 'auth' axis (and optionally a leading
    'data' axis) for the production engine's sharded dispatch."""
    import jax
    from jax.sharding import Mesh

    cpus = jax.devices("cpu")
    need = auth * data
    if len(cpus) < need:
        pytest.skip(f"need {need} cpu devices")
    if data > 1:
        return Mesh(np.array(cpus[:need]).reshape(data, auth), ("data", "auth"))
    return Mesh(np.array(cpus[:auth]), ("auth",))


def test_equivalence_mesh_sharded():
    """The PRODUCTION TpuBullshark with a 4-device 'auth' mesh: the real
    chain_commit dispatch shards the committee axis and must stay
    bit-for-bit equivalent to the host engine (VERDICT r2 #2)."""
    _run_both(size=4, rounds=20, failure=0.2, seed=0,
              dev_kwargs={"mesh": _auth_mesh(4)})


def test_equivalence_mesh_padded_committee():
    """Committee size (7) not divisible by the auth axis (2): the window
    pads the committee axis with absent slots; commits are unchanged."""
    _run_both(size=7, rounds=15, failure=0.15, seed=1, leader_fn=None,
              dev_kwargs={"mesh": _auth_mesh(2)})


def test_equivalence_mesh_two_axis():
    """A 2-axis (data x auth) mesh — the dryrun_multichip layout — behind
    the production engine: specs name only 'auth', 'data' is replicated."""
    _run_both(size=4, rounds=20, failure=0.3, seed=3,
              dev_kwargs={"mesh": _auth_mesh(2, data=4)})


def test_equivalence_mesh_tusk():
    from narwhal_tpu.consensus import Tusk
    from narwhal_tpu.tpu.dag_kernels import TpuTusk

    _run_both(size=4, rounds=20, failure=0.3, seed=2, host_cls=Tusk,
              dev_cls=TpuTusk, dev_kwargs={"mesh": _auth_mesh(2)})


def test_mesh_window_slides_and_grows():
    """Sliding + growth still work when the dispatch is mesh-sharded (the
    doubled W recompiles the sharded jit)."""
    _run_both(size=4, rounds=60, failure=0.0, seed=0, gc=10, window=24,
              dev_kwargs={"mesh": _auth_mesh(4)})


def test_window_grows_when_no_commits():
    # No leader ever present => no commits => window must grow, not slide.
    f = CommitteeFixture(size=4)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    keys = f.committee.authority_keys()[1:]
    certs, _ = make_certificates(f.committee, 1, 40, genesis, keys=keys)
    state = ConsensusState(Certificate.genesis(f.committee))
    dev = TpuBullshark(f.committee, None, gc_depth=10, leader_fn=fixed_leader, window=16)
    for c in certs:
        assert dev.process_certificate(state, 0, c) == []
    assert dev.win.W >= 40


def test_reach_mask_simple_chain():
    # Hand-built 3-round window over 2 authorities:
    # (2,0) -> (1,1) -> (0,0); (1,0) unlinked.
    import jax.numpy as jnp

    parent = np.zeros((3, 2, 2), np.uint8)
    present = np.ones((3, 2), np.uint8)
    parent[2, 0, 1] = 1  # (2,0) links (1,1)
    parent[1, 1, 0] = 1  # (1,1) links (0,0)
    onehot = np.array([1, 0], np.uint8)
    mask = np.asarray(
        reach_mask(jnp.asarray(parent), jnp.asarray(present), jnp.int32(2), jnp.asarray(onehot))
    )
    expected = np.array([[1, 0], [0, 1], [1, 0]], bool)
    assert (mask == expected).all()

    # Committed relay blocks propagation: mark (1,1) committed.
    unc = present.copy()
    unc[1, 1] = 0
    mask2 = np.asarray(
        reach_mask(jnp.asarray(parent), jnp.asarray(unc), jnp.int32(2), jnp.asarray(onehot))
    )
    expected2 = np.array([[0, 0], [0, 0], [1, 0]], bool)
    assert (mask2 == expected2).all()


def test_leader_support_kernel():
    import jax.numpy as jnp

    parent = np.zeros((2, 3, 3), np.uint8)
    present = np.ones((2, 3), np.uint8)
    stakes = np.array([5, 7, 11], np.int32)
    parent[1, 0, 2] = 1  # authority 0 at round 1 links leader (0, 2)
    parent[1, 2, 2] = 1  # authority 2 links it too
    got = int(
        leader_support(
            jnp.asarray(parent), jnp.asarray(present), jnp.asarray(stakes),
            jnp.int32(1), jnp.int32(2),
        )
    )
    assert got == 16  # 5 + 11


def test_window_growth_is_precompiled():
    """_grow() doubles W mid-stream exactly when the node is behind; the
    engine must keep the doubled shape compiled AHEAD of need (VERDICT r2
    weak #7). We assert the prewarm covers the next size before growth and
    that the first post-growth dispatch completes without a cold-compile
    stall."""
    import time

    f = CommitteeFixture(size=4)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    # No leader present => no commits => the window must grow past 16.
    keys = f.committee.authority_keys()[1:]
    certs, _ = make_certificates(f.committee, 1, 40, genesis, keys=keys)
    state = ConsensusState(Certificate.genesis(f.committee))
    dev = TpuBullshark(f.committee, None, gc_depth=10, leader_fn=fixed_leader,
                       window=16, prewarm=True)
    assert (32, dev.win.N, 0) in dev._warmed  # next size queued at init
    for c in certs:
        dev.process_certificate(state, 0, c)
    assert dev.win.W >= 40
    # Every size the window reached had been queued ahead of need.
    assert (dev.win.W * 2, dev.win.N, 0) in dev._warmed
    for t in dev._prewarm_threads:
        t.join(timeout=180.0)
        assert not t.is_alive()
    # A commit at the grown window size now dispatches from the warm cache:
    # well under any cold-compile time even on this host.
    from narwhal_tpu.fixtures import mock_certificate

    lead = mock_certificate(f.committee, f.committee.authority_keys()[0], 40, set())
    sup_parent = {lead.digest}
    sup = mock_certificate(
        f.committee, f.committee.authority_keys()[1], 41, sup_parent
    )
    dev.win.insert(lead, 0)
    t0 = time.monotonic()
    dev.process_certificate(state, 0, sup)
    # Generous bound: proves "no cold multi-minute compile", robust to
    # parallel load on a 1-core CI host.
    assert time.monotonic() - t0 < 30.0, "post-growth dispatch stalled"


def test_process_batch_matches_sequential():
    """The fused pipeline's engine half: feeding a causally ordered stream
    through process_batch (arbitrary chunking) yields the IDENTICAL output
    sequence to per-certificate calls — content, order and consensus
    indexes — including windows with losses and multi-leader chains."""
    f = CommitteeFixture(size=4)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_certificates(
        f.committee, 1, 25, genesis,
        failure_probability=0.2, rng=random.Random(4),
    )
    seq_state = ConsensusState(Certificate.genesis(f.committee))
    bat_state = ConsensusState(Certificate.genesis(f.committee))
    seq_eng = TpuBullshark(f.committee, NodeStorage(None).consensus_store, GC,
                           leader_fn=fixed_leader)
    bat_eng = TpuBullshark(f.committee, NodeStorage(None).consensus_store, GC,
                           leader_fn=fixed_leader)
    seq_out = []
    i = 0
    for c in certs:
        outs = seq_eng.process_certificate(seq_state, i, c)
        i += len(outs)
        seq_out.extend(outs)
    bat_out = []
    j = 0
    for lo in range(0, len(certs), 7):  # chunking unaligned with rounds
        outs = bat_eng.process_batch(bat_state, j, certs[lo:lo + 7])
        j += len(outs)
        bat_out.extend(outs)
    assert [o.certificate.digest for o in seq_out] == [
        o.certificate.digest for o in bat_out
    ]
    assert [o.consensus_index for o in seq_out] == [
        o.consensus_index for o in bat_out
    ]
    assert seq_state.last_committed == bat_state.last_committed
    assert len(seq_out) > 10


def test_process_batch_async_matches_sequential(run):
    """The runner's burst path (process_batch_async) is output-identical."""
    f = CommitteeFixture(size=4)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_certificates(
        f.committee, 1, 12, genesis, failure_probability=0.0,
        rng=random.Random(0),
    )
    seq_state = ConsensusState(Certificate.genesis(f.committee))
    bat_state = ConsensusState(Certificate.genesis(f.committee))
    seq_eng = TpuBullshark(f.committee, None, GC, leader_fn=fixed_leader)
    bat_eng = TpuBullshark(f.committee, None, GC, leader_fn=fixed_leader)
    seq_out = []
    i = 0
    for c in certs:
        outs = seq_eng.process_certificate(seq_state, i, c)
        i += len(outs)
        seq_out.extend(outs)

    async def batched():
        return await bat_eng.process_batch_async(bat_state, 0, list(certs))

    bat_out = run(batched(), timeout=120.0)
    assert [o.certificate.digest for o in seq_out] == [
        o.certificate.digest for o in bat_out
    ]


def test_mesh_growth_rederives_sharded_dispatch():
    """ISSUE 10 satellite: after _grow() doubles W, a MESHED engine must
    re-derive its dispatch from the kernel registry — the same process-
    wide 'auth'-sharded program — rather than a fresh unsharded jit that
    would silently run replicated layouts."""
    from narwhal_tpu.tpu import kernel_registry
    from narwhal_tpu.tpu.dag_kernels import chain_commit

    mesh = _auth_mesh(2)
    f = CommitteeFixture(size=4)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    keys = f.committee.authority_keys()[1:]  # no leader => growth, not slide
    certs, _ = make_certificates(f.committee, 1, 40, genesis, keys=keys)
    state = ConsensusState(Certificate.genesis(f.committee))
    dev = TpuBullshark(f.committee, None, gc_depth=10, leader_fn=fixed_leader,
                       window=16, mesh=mesh)
    before = dev._chain_commit
    for c in certs:
        assert dev.process_certificate(state, 0, c) == []
    assert dev.win.W >= 40  # grew (twice)
    assert dev._dispatch_W == dev.win.W
    # Still the registry's sharded wrapper for THIS mesh — not a fresh
    # unsharded trace, and not a stale per-shape object.
    from jax.sharding import PartitionSpec as P

    expected = kernel_registry.sharded(
        chain_commit, mesh,
        in_specs=(
            P(None, None, "auth"), P(None, "auth"), None, P("auth"),
            None, None, P(None, None),
        ),
        out_specs=P(None, None, "auth"),
    )
    assert dev._chain_commit is expected
    assert expected is before  # same mesh -> same program across growth
    assert dev._chain_commit is not chain_commit
    # And the grown window still commits correctly through the mesh.
    from narwhal_tpu.fixtures import mock_certificate

    lead = mock_certificate(f.committee, f.committee.authority_keys()[0], 40, set())
    assert dev.process_certificate(state, 0, lead) == []
    outs = []
    for sup_key in f.committee.authority_keys()[1:3]:  # f+1 = 2 supporters
        sup = mock_certificate(f.committee, sup_key, 41, {lead.digest})
        outs = dev.process_certificate(state, 0, sup)
        if outs:
            break
    assert outs and outs[-1].certificate.digest == lead.digest
