"""One fresh process of tests/test_kernel_artifacts.py: dispatch a persisted
kernel against the compile cache directory the environment names and print,
as the last line, one JSON object of what happened (`kernel_load` and
`compile` records, `kernel_artifact_total`, whether the Python body ran, the
results). Run as `python tests/kernel_artifact_child.py '<json options>'`.

Options: kernel "tiny" | "tiny_mesh" | "msm"; module: a path to import the
tiny kernel's module from (a copy the test may have edited); shapes: the
operand shapes to dispatch, in order; jax_version: pose as that jax; wait_for:
a path to wait for before the first dispatch (two children racing)."""

from __future__ import annotations

import hashlib
import importlib.util
import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def report(kernel_name: str, **more) -> dict:
    from narwhal_tpu import tracing
    from narwhal_tpu.tpu import kernel_registry

    events = [r for r in tracing.flight_dump()["events"] if getattr(r, "kernel", None) == kernel_name]
    return {
        "loads": [r._asdict() for r in events if r.kind == "kernel_load"],
        "compiles": [r._asdict() for r in events if r.kind == "compile"],
        "counter": {
            o: kernel_registry.KERNEL_ARTIFACTS.labels(kernel_name, o).value
            for o in ("hit", "miss", "stale", "unreadable")
        },
        "walls": [w for w in kernel_registry.compile_walls() if w["kernel"] == kernel_name],
        "files": sorted(os.listdir(kernel_registry.artifact_dir()))
        if os.path.isdir(kernel_registry.artifact_dir()) else None,
        **more,
    }


def tiny(opts: dict) -> dict:
    import jax

    path = opts.get("module") or os.path.join(os.path.dirname(__file__), "artifact_kernels.py")
    spec = importlib.util.spec_from_file_location("artifact_kernels", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    if opts.get("jax_version"):
        jax.__version__ = opts["jax_version"]
    if opts.get("wait_for"):
        while not os.path.exists(opts["wait_for"]):
            time.sleep(0.001)
    results = []
    for shape in opts.get("shapes", [[4, 4]]):
        x = np.arange(int(np.prod(shape)), dtype=np.uint8).reshape(shape)
        results.append(np.asarray(mod.tiny_persisted_kernel(x, scale=5)).tolist())
        results.append(np.asarray(mod.tiny_persisted_kernel(x, scale=5)).tolist())  # steady state
    return report("tiny_persisted_kernel", traces=mod.TRACES, results=results)


def tiny_mesh(opts: dict) -> dict:
    """The same kernel through `kernel_registry.sharded` on two devices."""
    import jax
    from jax.sharding import PartitionSpec as P

    from narwhal_tpu import tracing
    from narwhal_tpu.tpu import device_mesh, kernel_registry
    from tests import artifact_kernels as mod

    mesh = device_mesh(2, "data", "--verify-shards", jax.devices("cpu"))
    wrapper = kernel_registry.sharded(
        mod.tiny_persisted_kernel, mesh, (P("data", None),), P("data"), static_argnames=("scale",)
    )
    x = np.arange(16, dtype=np.uint8).reshape(4, 4)
    out = np.asarray(wrapper(x, 5)).tolist()  # a jit with shardings takes no keywords
    kinds = sorted({r.kind for r in tracing.flight_dump()["events"]})
    return report("tiny_persisted_kernel", traces=mod.TRACES, results=[out], kinds=kinds,
                  persisted=wrapper._persist is not None)


def msm_buckets(k, ref) -> list[tuple[np.ndarray, int]]:
    """Three buckets of 16 raw rows, the same bytes in every process, each
    with the Σ z·s its epilogue needs: all valid; one signature over another
    message; one R that is no point."""
    from narwhal_tpu.crypto import KeyPair

    def bucket(spoil: str | None):
        rows, sum_s = np.zeros((16, k.ROW_BYTES), np.uint8), 0
        for i in range(12):
            kp = KeyPair.from_seed(hashlib.sha256(b"artifact-key-%d" % (i % 3)).digest())
            msg = b"artifact-msg-%d" % i
            sig = kp._private.sign(b"another message" if spoil == "forged" and i == 5 else msg)
            r, s = sig[:32], int.from_bytes(sig[32:], "little")
            z = int.from_bytes(hashlib.sha256(b"artifact-z-%d" % i).digest()[:16], "little")
            h = int.from_bytes(hashlib.sha512(r + kp.public + msg).digest(), "little") % ref.L
            if spoil == "no_point" and i == 7:
                r = next(c for c in (bytes([j]) + r[1:] for j in range(256)) if ref.decompress(c) is None)
            rows[i] = np.frombuffer(
                kp.public + r + (z * h % ref.L).to_bytes(32, "little") + z.to_bytes(16, "little"), np.uint8)
            sum_s += z * s
        return rows, sum_s % ref.L

    return [bucket(None), bucket("forged"), bucket("no_point")]


def msm(opts: dict) -> dict:
    """The real `msm_accumulate_kernel` at bucket 16, at the kernel's own
    interface and under a `TpuVerifier` (whose per-item detour runs on the
    plain-integer stand-in: tier-1 never traces `verify_batch_kernel`)."""
    import jax

    from narwhal_tpu import crypto
    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.tpu import ed25519 as k
    from narwhal_tpu.tpu.verifier import TpuVerifier, msm_epilogue_check
    from tests import plain_kernels

    body_entered = []
    expand = k.expand_rows
    k.expand_rows = lambda rows: body_entered.append(1) or expand(rows)  # the body's first line

    compiling = []
    handler = logging.Handler(level=logging.DEBUG)
    handler.emit = lambda record: compiling.append(record.getMessage())
    jax.config.update("jax_log_compiles", True)
    logging.getLogger("jax").addHandler(handler)

    raw, verdicts, oracle, same_points = [], [], [], []
    for rows, sum_s in msm_buckets(k, k.ref):
        flat = np.asarray(k.msm_accumulate_kernel(rows))
        raw.append(hashlib.sha256(flat.tobytes()).hexdigest())
        va, vr, valid = k.split_msm_result(flat)
        verdicts.append(bool(valid and msm_epilogue_check(va, vr, sum_s, k)))
        pva, pvr, pvalid = k.split_msm_result(np.asarray(plain_kernels.msm_kernel(rows)))
        oracle.append(bool(pvalid and msm_epilogue_check(pva, pvr, sum_s, k)))
        # window sum by window sum, the device's loose limbs name the points
        # the plain integers do: X1 Z2 = X2 Z1 and Y1 Z2 = Y2 Z1 (mod p);
        # where a row is no point only the flag is defined
        same_points.append(valid == pvalid and (not valid or all(
            (k.limbs_to_int(d[c, :, w]) * k.limbs_to_int(p[2, :, w])
             - k.limbs_to_int(p[c, :, w]) * k.limbs_to_int(d[2, :, w])) % k.ref.P == 0
            for d, p in ((va, pva), (vr, pvr)) for w in range(d.shape[2]) for c in (0, 1))))

    v = TpuVerifier(max_bucket=16, msm_min_bucket=16, fixed_bucket=True, mode="msm")
    v._item_kernel = plain_kernels.item_kernel
    kp = KeyPair.from_seed(hashlib.sha256(b"artifact-verifier").digest())
    items = [(kp.public, b"m%d" % i, kp._private.sign(b"m%d" % i)) for i in range(10)]
    all_valid = v(items)
    items[3] = (kp.public, b"forged", kp._private.sign(b"not forged"))
    items[6] = (kp.public, b"mangled", b"\x00" * 64)
    mixed = v(items)
    return report(
        "msm_accumulate_kernel", body_entered=len(body_entered), raw=raw, verdicts=verdicts,
        oracle=oracle, same_points=same_points, all_valid=all_valid, mixed=mixed,
        host=crypto._host_batch_verify(items), counts=dict(v.counts),
        # jax's own account: a "Compiling" line per lowering, a "Finished XLA
        # compilation" line per executable compiled or found in the cache
        lowered=[m.split(" with ")[0] for m in compiling if m.startswith("Compiling ") and "msm_acc" in m],
        compiled=[m.split(" in ")[0] for m in compiling if m.startswith("Finished XLA") and "msm_acc" in m],
        program=k.msm_accumulate_kernel._programs["uint8[16,112]"]
        .lower(np.zeros((16, k.ROW_BYTES), np.uint8)).as_text().split(" ", 2)[1],
    )


if __name__ == "__main__":
    options = json.loads(sys.argv[1])
    out = {"tiny": tiny, "tiny_mesh": tiny_mesh, "msm": msm}[options["kernel"]](options)
    print(json.dumps(out), flush=True)
