"""The shared kernel registry: one compile per (kernel, mesh shape).

These are the tier-1-fast mesh tests of the multi-chip device plane
(ISSUE 10): they run on conftest's virtual CPU devices and deliberately
share their mesh + bucket shapes with tests/test_multichip.py's dryrun
legs, so the suite pays each sharded kernel compile once no matter which
file runs first.

The recompile guard uses `jax_log_compiles`: with it on, every XLA
compile emits a 'Compiling <name> ...' log record, so 'one compile per
(kernel, mesh shape) per process' is asserted against jax's own
accounting rather than wall-clock heuristics.
"""

from __future__ import annotations

import logging

import numpy as np
import pytest

import jax

from narwhal_tpu.tpu import kernel_registry


def _data_mesh(n):
    from narwhal_tpu.tpu.verifier import data_mesh

    cpus = jax.devices("cpu")
    if len(cpus) < n:
        pytest.skip(f"need {n} cpu devices")
    return data_mesh(n, devices=cpus[:n])


def _auth_mesh(n):
    from jax.sharding import Mesh

    cpus = jax.devices("cpu")
    if len(cpus) < n:
        pytest.skip(f"need {n} cpu devices")
    return Mesh(np.array(cpus[:n]), ("auth",))


class _CompileLog(logging.Handler):
    """Captures jax's 'Compiling <fn> ...' records while installed."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.compiles: list[str] = []
        self.finished: list[str] = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.compiles.append(msg)
        elif msg.startswith("Finished XLA compilation "):
            self.finished.append(msg)

    def count(self, name: str) -> int:
        # jax 0.9 names the computation `jit(<fn>)`; older releases `<fn>`.
        heads = (f"Compiling {name} ", f"Compiling jit({name}) ")
        return sum(1 for m in self.compiles if m.startswith(heads))

    def executables(self, name: str) -> int:
        """`Finished XLA compilation of jit(<fn>)`: one per executable
        compiled or taken from the persistent cache, none per lowering."""
        return sum(1 for m in self.finished if m.startswith(f"Finished XLA compilation of jit({name}) "))


@pytest.fixture
def compile_log():
    jax.config.update("jax_log_compiles", True)
    handler = _CompileLog()
    jax_logger = logging.getLogger("jax")
    old_level = jax_logger.level
    jax_logger.addHandler(handler)
    jax_logger.setLevel(logging.DEBUG)
    try:
        yield handler
    finally:
        jax_logger.removeHandler(handler)
        jax_logger.setLevel(old_level)
        jax.config.update("jax_log_compiles", False)


def test_module_kernels_are_registered():
    """Every jit entry point in tpu/ lands in the registry catalog (the
    runtime half of the no-untracked-jit lint rule)."""
    import narwhal_tpu.tpu.dag_kernels  # noqa: F401
    import narwhal_tpu.tpu.ed25519  # noqa: F401

    names = set(kernel_registry.kernel_names())
    assert {
        "reach_mask",
        "roll_window",
        "place_batch",
        "leader_support",
        "chain_commit",
        "verify_batch_kernel",
        "msm_accumulate_kernel",
        "verify_decompress_kernel",
        "verify_straus_kernel",
        "verify_verdict_kernel",
        "msm_window_kernel",
    } <= names


def test_sharded_wrappers_are_process_wide():
    """Two fetches of the same (kernel, mesh, specs) return the SAME
    wrapper object — the structural guarantee that a second verifier or
    engine over the mesh can never pay a second compile."""
    from jax.sharding import PartitionSpec as P

    from narwhal_tpu.tpu.dag_kernels import chain_commit

    mesh = _auth_mesh(2)
    specs = dict(
        in_specs=(
            P(None, None, "auth"),
            P(None, "auth"),
            None,
            P("auth"),
            None,
            None,
            P(None, None),
        ),
        out_specs=P(None, None, "auth"),
    )
    k1 = kernel_registry.sharded(chain_commit, mesh, **specs)
    k2 = kernel_registry.sharded(chain_commit, mesh, **specs)
    assert k1 is k2
    # A different mesh shape is a different program.
    k3 = kernel_registry.sharded(chain_commit, _auth_mesh(4), **specs)
    assert k3 is not k1


def test_verifier_modes_share_staged_kernels():
    """The dryrun's historical double-compile: an item-mode and an
    msm-mode verifier over the SAME mesh must dispatch through identical
    stage wrappers (the msm fallback path reuses the item stages)."""
    from narwhal_tpu.tpu import ed25519 as kernel
    from narwhal_tpu.tpu.verifier import _sharded_kernels

    mesh = _data_mesh(4)
    before = kernel_registry.sharded_entries()
    _sharded_kernels(kernel, mesh, "data")
    after_first = kernel_registry.sharded_entries()
    _sharded_kernels(kernel, mesh, "data")
    assert kernel_registry.sharded_entries() == after_first
    assert after_first > before  # the first build did register stages


def test_one_compile_per_kernel_mesh_shape(compile_log):
    """The recompile guard: dispatching the registry's chain_commit
    wrapper for one (mesh, operand-shape) tuple from TWO consumers
    compiles exactly once per mesh shape — pinned via jax_log_compiles."""
    from jax.sharding import PartitionSpec as P

    from narwhal_tpu.tpu.dag_kernels import chain_commit

    W, N = 8, 4
    args = (
        np.zeros((W, N, N), np.uint8),
        np.zeros((W, N), np.uint8),
        np.int32(2),
        np.zeros((N,), np.int32),
        np.int32(-1),
        np.zeros((1,), np.int32),
        np.zeros((1, N), np.uint8),
    )
    specs = dict(
        in_specs=(
            P(None, None, "auth"),
            P(None, "auth"),
            None,
            P("auth"),
            None,
            None,
            P(None, None),
        ),
        out_specs=P(None, None, "auth"),
    )
    mesh = _auth_mesh(2)
    k1 = kernel_registry.sharded(chain_commit, mesh, **specs)
    jax.block_until_ready(k1(*args))
    first = compile_log.count("chain_commit")
    assert first >= 1  # this (mesh, shape) had not been dispatched before

    # Second consumer, same mesh + shapes: zero new compiles.
    k2 = kernel_registry.sharded(chain_commit, mesh, **specs)
    jax.block_until_ready(k2(*args))
    jax.block_until_ready(k1(*args))
    assert compile_log.count("chain_commit") == first

    # A new mesh shape compiles once more; repeating it does not.
    k4 = kernel_registry.sharded(chain_commit, _auth_mesh(4), **specs)
    jax.block_until_ready(k4(*args))
    second = compile_log.count("chain_commit")
    assert second == first + 1
    jax.block_until_ready(k4(*args))
    assert compile_log.count("chain_commit") == second


def test_compile_walls_recorded():
    """First dispatches self-report their walls per (kernel, mesh shape) —
    the accounting the dryrun/bench artifacts embed."""
    from jax.sharding import PartitionSpec as P

    from narwhal_tpu.tpu.dag_kernels import chain_commit

    mesh = _auth_mesh(2)
    k = kernel_registry.sharded(
        chain_commit,
        mesh,
        in_specs=(
            P(None, None, "auth"),
            P(None, "auth"),
            None,
            P("auth"),
            None,
            None,
            P(None, None),
        ),
        out_specs=P(None, None, "auth"),
    )
    W, N = 8, 4
    jax.block_until_ready(
        k(
            np.zeros((W, N, N), np.uint8),
            np.zeros((W, N), np.uint8),
            np.int32(2),
            np.zeros((N,), np.int32),
            np.int32(-1),
            np.zeros((1,), np.int32),
            np.zeros((1, N), np.uint8),
        )
    )
    walls = kernel_registry.compile_walls()
    rows = [r for r in walls if r["kernel"] == "chain_commit" and r["mesh"] == "2:auth"]
    assert rows and all(r["wall_s"] >= 0 for r in rows)
    agg = kernel_registry.compile_walls_by_shape()
    assert "chain_commit@2:auth" in agg


def test_one_compile_per_persisted_kernel_shape(compile_log):
    """The recompile guard on the load path (ISSUE 32): a persisted kernel
    dispatches its exported program, loaded from beside the compile cache
    or traced and written there, whichever this checkout's cache gives —
    either way one executable per (kernel, shape), under the kernel's own
    name in jax's log lines, and a first-dispatch wall per shape."""
    from tests.artifact_kernels import tiny_persisted_kernel as kernel

    name = "tiny_persisted_kernel"
    x = np.ones((6, 3), np.uint8)  # shapes no other test of this process dispatches
    assert jax.block_until_ready(kernel(x, scale=2)).tolist() == [6] * 6
    assert compile_log.executables(name) == 1
    lowered = compile_log.count(name)  # 1 loaded; 2 traced: the body for the export, then the export
    assert lowered in (1, 2)
    for _ in range(3):
        jax.block_until_ready(kernel(x, scale=2))
    assert (compile_log.executables(name), compile_log.count(name)) == (1, lowered)

    # Another shape, and another static, are programs of their own: once each.
    jax.block_until_ready(kernel(np.ones((12, 3), np.uint8), scale=2))
    jax.block_until_ready(kernel(x, scale=4))
    assert jax.block_until_ready(kernel(x, scale=4)).tolist() == [12] * 6
    assert compile_log.executables(name) == 3

    rows = [r for r in kernel_registry.compile_walls() if r["kernel"] == name]
    assert {(r["mesh"], r["shapes"]) for r in rows} >= {
        ("1", "uint8[6,3];scale=2"), ("1", "uint8[12,3];scale=2"), ("1", "uint8[6,3];scale=4")}
    assert all(r["wall_s"] >= 0 for r in rows)
    assert f"{name}@1" in kernel_registry.compile_walls_by_shape()


@pytest.mark.parametrize("which", ["tiny_persisted_kernel", "msm_accumulate_kernel"])
def test_a_loaded_program_keeps_the_kernels_name(which):
    """What a device trace shows, and `chipbench/trace_reduce.py` looks for:
    the module a persisted kernel dispatches is `jit_<kernel>`, whether its
    export was loaded or just written. (`_load` does not compile: the msm
    kernel's minute on XLA:CPU is not paid here.)"""
    if which == "tiny_persisted_kernel":
        from tests.artifact_kernels import tiny_persisted_kernel as kernel

        operand, statics = np.zeros((5, 2), np.uint8), {"scale": 7}
    else:
        from narwhal_tpu.tpu import ed25519

        kernel = ed25519.msm_accumulate_kernel
        operand, statics = np.zeros((16, ed25519.ROW_BYTES), np.uint8), {}
    program = kernel._load(kernel_registry._shapes_sig((operand,), statics), (operand,), statics)
    assert program.lower(operand).as_text().startswith(f"module @jit_{which} ")
    assert kernel.__name__ == kernel.name == which and callable(kernel.__wrapped__)
    if statics:  # the prewarm's entry traces the body: seconds for the small kernel only
        assert kernel.lower(operand, **statics).as_text().startswith(f"module @jit_{which} ")


def test_verify_shard_divisibility_still_fails_fast():
    """Mesh sizing errors stay construction-time errors through the
    registry path (the advisor-r4 rule: stop the node at startup)."""
    from narwhal_tpu.config import ConfigError
    from narwhal_tpu.tpu.verifier import TpuVerifier

    mesh = _data_mesh(3)
    with pytest.raises(ConfigError):
        TpuVerifier(max_bucket=32, mode="item", mesh=mesh)  # 16 % 3 != 0


def test_sharded_verifier_verdicts_match_host():
    """Tier-1 mesh verdict equivalence: the STAGED sharded pipeline (both
    accept-set modes) against the host library on a batch mixing valid
    signatures, a forgery, a malformed signature and a wrong-length key.
    Shares mesh (4-device 'data') and bucket (32) with the dryrun leg in
    test_multichip.py, so the compile is paid once per suite process.
    Exact bit-equivalence of staged-vs-monolithic kernels is pinned in the
    slow lane (test_tpu_ed25519.py)."""
    from narwhal_tpu import crypto
    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.tpu.verifier import TpuVerifier

    mesh = _data_mesh(4)
    kp = KeyPair.generate()
    items = [(kp.public, b"m%d" % i, kp.sign(b"m%d" % i)) for i in range(28)]
    items.append((kp.public, b"forged", kp.sign(b"not-forged")))  # wrong msg
    items.append((kp.public, b"mangled", b"\x00" * 64))  # junk signature
    items.append((kp.public[:16], b"short", kp.sign(b"short")))  # bad key len
    items.append((kp.public, b"ok-tail", kp.sign(b"ok-tail")))
    expected = crypto._host_batch_verify(items)
    assert expected[:28] == [True] * 28 and expected[28:31] == [False] * 3

    for mode in ("item", "msm"):
        v = TpuVerifier(max_bucket=32, msm_min_bucket=16, mode=mode, mesh=mesh)
        got = v(items)
        assert got == expected, f"sharded {mode} verdicts diverged from host"
        assert v(items) == expected  # compiled-path dispatch is stable


def test_auth_axis_committee_padding():
    """Committee sizes that don't divide the 'auth' axis are padded with
    always-absent authority slots: zero stake, never present, invisible
    to reachability — and an exactly-divisible committee pads nothing.
    (Commit-sequence equivalence of the padded engine is pinned in
    tests/test_dag_kernels.py::test_equivalence_mesh_padded_committee.)"""
    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.tpu.dag_kernels import TpuBullshark

    mesh = _auth_mesh(2)
    f7 = CommitteeFixture(size=7)
    eng = TpuBullshark(f7.committee, None, 50, mesh=mesh, prewarm=False)
    assert eng.win.N == 8  # 7 -> next multiple of auth=2
    assert eng.win.stakes[7] == 0  # padded slot carries no stake
    assert not eng.win.present[:, 7].any()  # ... and never a certificate

    f4 = CommitteeFixture(size=4)
    eng4 = TpuBullshark(f4.committee, None, 50, mesh=mesh, prewarm=False)
    assert eng4.win.N == 4  # divisible: no padding
