"""The driver-contract multi-chip dry run, under pytest.

Nothing in CI used to execute `__graft_entry__.dryrun_multichip`, so the
multi-device evidence a CPU environment can produce lived outside the test
suite and broke unnoticed. These tests run
the exact driver entry points on conftest's 8 virtual CPU devices so any
regression in the sharded consensus step (mesh construction, in_shardings,
the unsharded comparison leg's device pinning) fails the suite instead of
the round artifact.
"""

import os

import jax
import numpy as np
import pytest

import __graft_entry__


def test_entry_compiles_and_runs():
    fn, args = __graft_entry__.entry()
    out = jax.jit(fn)(*args)
    out.block_until_ready()
    # reach_mask returns a [W, N] mask covering the whole window.
    W, N, _ = args[0].shape
    assert out.shape == (W, N)


@pytest.mark.parametrize("n_devices", [8, 4, 2, 1])
def test_dryrun_multichip(n_devices):
    cpus = jax.devices("cpu")
    if len(cpus) < n_devices:
        pytest.skip(f"need {n_devices} cpu devices")
    __graft_entry__.dryrun_multichip(n_devices, devices=cpus)


def test_dryrun_multichip_odd_mesh():
    """n_devices not divisible by 2 exercises the auth=1 mesh fallback."""
    cpus = jax.devices("cpu")
    if len(cpus) < 3:
        pytest.skip("need 3 cpu devices")
    __graft_entry__.dryrun_multichip(3, devices=cpus)


def test_dryrun_pins_unsharded_dispatch():
    """Regression class: module-level jitted kernels called through
    library code dispatch to the *process default device*, not the dry
    run's pinned devices, so the mesh correctness artifact goes red for a
    reason unrelated to sharding.

    Reproduce the failure mode on the virtual mesh: pin the dry run to the
    UPPER half of the 8 CPU devices, spy on the module-level chain_commit
    dispatch (the route an unmeshed TpuBullshark takes, including its
    device-resident DagWindow tensors), and assert no kernel output ever
    lands on a device outside the pinned list. Without
    `jax.default_device(devs[0])` pinning, those outputs land on the
    process default device (cpus[0]) and this test fails — exactly the
    class of bug that `devices=cpus`
    tests structurally cannot see.

    Runs in a SUBPROCESS (tests/_dryrun_guard.py): pinning to cpus[4:]
    compiles a second full kernel set for a non-default device, and
    XLA:CPU's compiler segfaulted when that compile landed on top of a
    long-lived suite process's accumulated state (r5; 125 GB free, so not
    memory) — process isolation keeps the guard deterministic."""
    import subprocess
    import sys

    script = os.path.join(os.path.dirname(__file__), "_dryrun_guard.py")
    proc = subprocess.run(
        [sys.executable, script],
        capture_output=True,
        text=True,
        timeout=1800,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(script))),
    )
    tail = (proc.stdout + proc.stderr)[-2000:]
    assert proc.returncode == 0, f"dryrun guard failed (rc={proc.returncode}): {tail}"
    assert "GUARD-OK" in proc.stdout or "SKIP" in proc.stdout, tail


def _mesh_verifier(mode="item"):
    """A mesh-sharded verifier sharing mesh (4-device 'data') and bucket
    (fixed 32) with the dryrun leg and tests/test_kernel_registry.py, so
    the whole suite pays each staged-kernel compile once per process."""
    from narwhal_tpu.tpu.verifier import TpuVerifier, data_mesh

    cpus = jax.devices("cpu")
    if len(cpus) < 4:
        pytest.skip("need 4 cpu devices")
    return TpuVerifier(
        max_bucket=32, msm_min_bucket=16, mode=mode, fixed_bucket=True,
        mesh=data_mesh(4, devices=cpus[:4]),
    )


def test_fused_pipeline_matches_sequential_host():
    """The tentpole's fusion leg: FusedCertificatePipeline (mesh-sharded
    verify -> one place_batch scatter per batch -> chain_commit with
    deferred readbacks) commits the IDENTICAL sequence to a host engine
    fed the same fully-signed stream one certificate at a time, with the
    host touching each certificate once."""
    from narwhal_tpu.consensus import Bullshark, ConsensusState
    from narwhal_tpu.fixtures import CommitteeFixture, make_signed_certificates
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.tpu.dag_kernels import TpuBullshark
    from narwhal_tpu.tpu.pipeline import FusedCertificatePipeline
    from narwhal_tpu.types import Certificate

    f = CommitteeFixture(size=4)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_signed_certificates(f, 1, 10, genesis)

    host_state = ConsensusState(Certificate.genesis(f.committee))
    host = Bullshark(f.committee, NodeStorage(None).consensus_store, 50)
    host_out = []
    hi = 0
    for c in certs:
        outs = host.process_certificate(host_state, hi, c)
        hi += len(outs)
        host_out.extend(outs)
    assert host_out  # the optimal DAG commits

    pipe_state = ConsensusState(Certificate.genesis(f.committee))
    engine = TpuBullshark(f.committee, NodeStorage(None).consensus_store, 50)
    pipe = FusedCertificatePipeline(_mesh_verifier(), engine, pipe_state)
    for lo in range(0, len(certs), 8):  # 8 certs x 3 sigs = 24 <= bucket 32
        pipe.feed(certs[lo:lo + 8])
        assert len(pipe._inflight) <= pipe.depth  # double-buffered bound
    out = pipe.drain()
    assert not pipe.rejected
    assert [o.certificate.digest for o in out] == [
        o.certificate.digest for o in host_out
    ]
    assert [o.consensus_index for o in out] == [
        o.consensus_index for o in host_out
    ]
    assert pipe_state.last_committed == host_state.last_committed


def test_fused_pipeline_rejects_bad_signatures():
    """A certificate with a corrupted vote signature is rejected by the
    verify stage and never reaches the DAG window; the rest of its batch
    is unaffected."""
    from narwhal_tpu.consensus import ConsensusState
    from narwhal_tpu.fixtures import CommitteeFixture, make_signed_certificates
    from narwhal_tpu.tpu.dag_kernels import TpuBullshark
    from narwhal_tpu.tpu.pipeline import FusedCertificatePipeline
    from narwhal_tpu.types import Certificate

    f = CommitteeFixture(size=4)
    genesis = {c.digest for c in Certificate.genesis(f.committee)}
    certs, _ = make_signed_certificates(f, 1, 1, genesis)
    good = certs[:-1]
    victim = certs[-1]
    bad = Certificate(
        victim.header,
        victim.signers,
        victim.signatures[:-1] + (b"\x00" * 64,),
    )
    state = ConsensusState(Certificate.genesis(f.committee))
    engine = TpuBullshark(f.committee, None, 50)
    pipe = FusedCertificatePipeline(_mesh_verifier(), engine, state)
    pipe.feed(good + [bad])
    pipe.drain()
    assert pipe.rejected == [bad]
    idx = f.committee.index_of(bad.origin)
    assert engine.win.present[engine.win._off(1), idx] == 0  # never placed
    for cert in good:
        gidx = f.committee.index_of(cert.origin)
        assert engine.win.present[engine.win._off(1), gidx] == 1


def test_primary_node_shutdown_joins_prewarm_threads(run):
    """ISSUE 10 satellite: PrimaryNode.shutdown must bounded-join the
    background window prewarm compiles (dag_backend=tpu) so they cannot
    outlive the node and contend with a successor's foreground traces —
    previously only the atexit hook covered this, i.e. process exit, not
    node teardown."""
    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.node import NodeStorage, PrimaryNode
    from narwhal_tpu.tpu import dag_kernels

    fx = CommitteeFixture(size=4)
    auth = fx.authorities[0]
    node = PrimaryNode(
        auth.keypair,
        fx.committee,
        fx.worker_cache,
        fx.parameters,
        NodeStorage(None),
        dag_backend="tpu",
    )
    calls = []
    orig = dag_kernels.join_prewarm_threads
    dag_kernels.join_prewarm_threads = lambda grace=60.0: calls.append(grace)
    try:
        run(node.shutdown(), timeout=60.0)
    finally:
        dag_kernels.join_prewarm_threads = orig
    assert calls, "shutdown did not join the prewarm threads"

    # A cpu-dag node must NOT import jax machinery at shutdown.
    node2 = PrimaryNode(
        auth.keypair,
        fx.committee,
        fx.worker_cache,
        fx.parameters,
        NodeStorage(None),
        dag_backend="cpu",
    )
    calls2 = []
    dag_kernels.join_prewarm_threads = lambda grace=60.0: calls2.append(grace)
    try:
        run(node2.shutdown(), timeout=60.0)
    finally:
        dag_kernels.join_prewarm_threads = orig
    assert not calls2
