"""Subprocess body of test_dryrun_pins_unsharded_dispatch.

Runs the driver dryrun pinned to the UPPER half of the CPU devices with a
spy on the module-level `chain_commit` kernel — the unsharded jitted
dispatch that library code (an unmeshed TpuBullshark, exactly what
`--dag-backend tpu` wires without `--dag-shards`) reaches through the
process-default device — and exits non-zero if any kernel output or
device-resident window tensor lands outside the pinned device list (the
failure class: module-level jits following the process default device
instead of the dry run's pinned devices).

Executed in its own process: the spy run compiles a kernel set for a
non-default device, and XLA:CPU's compiler has crashed when that compile
landed on top of a long-lived suite process's accumulated state —
isolation keeps the guard deterministic either way. The dryrun's sharded
verifier leg is skipped here (its compile bill is minutes and its evidence
— sharded verdicts — is not what this guard checks; the in-suite
dryrun_multichip[8] run still pays it once).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

import __graft_entry__  # noqa: E402
import narwhal_tpu.tpu.dag_kernels as dk  # noqa: E402


def main() -> int:
    cpus = jax.devices("cpu")
    if len(cpus) < 8:
        print("SKIP: need 8 cpu devices")
        return 0
    allowed = set(cpus[4:8])
    placements = []

    orig_chain_commit = dk.chain_commit

    def spy(*args, **kwargs):
        out = orig_chain_commit(*args, **kwargs)
        for leaf in jax.tree_util.tree_leaves(out):
            try:
                placements.extend(leaf.devices())
            except (AttributeError, jax.errors.ConcretizationTypeError):
                pass  # tracer (the meshed leg re-jits through us): not a
                # concrete dispatch, placement is governed by in_shardings
        return out

    dk.chain_commit = spy
    # The sharded-verifier leg's multi-minute compile adds nothing to this
    # placement check; skip it (see module docstring).
    __graft_entry__._VERIFIER_LEG_RAN = True
    __graft_entry__.dryrun_multichip(4, devices=cpus[4:])

    # The unmeshed production engine: module-level chain_commit dispatch
    # over the DEVICE-RESIDENT window, under the same pin the dryrun uses.
    # This is the exact route `--dag-backend tpu` takes in a node whose
    # process default device is NOT the dryrun's — the r04 failure class.
    import random as _random

    from narwhal_tpu.consensus import ConsensusState
    from narwhal_tpu.fixtures import CommitteeFixture, make_certificates
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.tpu.dag_kernels import TpuBullshark
    from narwhal_tpu.types import Certificate

    with jax.default_device(cpus[4]):
        f = CommitteeFixture(size=4)
        genesis = {c.digest for c in Certificate.genesis(f.committee)}
        certs, _ = make_certificates(
            f.committee, 1, 8, genesis,
            failure_probability=0.0, rng=_random.Random(0),
        )
        engine = TpuBullshark(
            f.committee, NodeStorage(None).consensus_store, 50, prewarm=False
        )
        state = ConsensusState(Certificate.genesis(f.committee))
        index = 0
        committed = 0
        for c in certs:
            out = engine.process_certificate(state, index, c)
            index += len(out)
            committed += len(out)
        if committed == 0:
            print("FAIL: unmeshed engine never committed")
            return 1
        for arr in engine.win.device_view():
            placements.extend(arr.devices())

    if not placements:
        print("FAIL: the dry run never dispatched the module-level kernel")
        return 1
    outside = {str(d) for d in placements if d not in allowed}
    if outside:
        print(f"FAIL: dispatch landed outside the pinned device list: {outside}")
        return 1
    print("GUARD-OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
