"""TpuVerifier: host wrapper turning (pk, msg, sig) batches into fixed-shape
device dispatches of the ed25519 kernel.

Plugs into the batch-verification seam (crypto.set_batch_verifier) that the
primary's certificate path and the worker's batch path call — the TPU-era
`TpuVerifier` service of SURVEY §7.8a. Responsibilities:

- host prechecks the kernel doesn't do: length, canonical S (< L), canonical
  R/A encodings (y < p);
- the SHA-512 challenge k = H(R || A || M) mod L (hashlib is C-speed; the
  device only sees 256-bit scalars as 4-bit window digits);
- shape bucketing: pad each call to the next power-of-two batch so XLA
  compiles a handful of programs, not one per batch size;
- the device link: an msm dispatch is ONE buffer of raw rows up (uint8
  [bucket, 112]: A | R | z*k mod L | z as the host holds them, zero rows
  the padding) and ONE int32 array down (both window sums and the
  all-rows-valid flag, ~30 KB). Limbs, sign bits, digits and padding —
  whatever scales with the bucket and not with the useful rows — are the
  kernel's (ed25519.expand_rows).

A device dispatch that fails raises to the caller: nothing here answers
from the host in the device's place. The detours that remain are the
protocol's own handling of INVALID input (a failed msm bucket is
re-dispatched per item, a failed certificate chunk per group, a group
whose solo device check fails is walked on the host) and each is counted
in `TpuVerifier.counts`, so a run on all-valid input can assert that none
fired — a kernel that miscompiled would otherwise be "corrected" quietly.

What runs where. `submit` / `submit_groups` (the native precheck and fold
with the GIL released, the useful rows' raw bytes written into a staging
buffer of the verifier's small pool, the jit dispatch of that one buffer,
one `copy_to_host_async`) run on whatever thread calls them; under
`VerifyService` that is the event loop that sealed the flush, which holds
the interpreter anyway, so a flush wins it from nobody — and what it costs
that loop no longer grows with the bucket. `collect` / `collect_groups`
block on the device, take the one result with one `np.asarray`, hand the
staging buffer back to the pool and run the host epilogue,
`msm_epilogue_check`, on views of that array — with the native library the
walk is `msm_epilogue_native` in native/scalar_ops.cpp and needs no
interpreter; without a toolchain, and in the tests as the oracle, it runs
on Python integers — on the service's one `verify-collect` thread.
`counts["epilogue_native"]` / `["epilogue_python"]` say which ran;
`counts["upload"]` / `["readback"]` (and `_bytes`) count the arrays an msm
dispatch moved: one each. The per-item detour packs limbs and digits with
numpy (`bytes_to_limbs`, `bytes_to_digits`) from the raw rows the handle
keeps: invalid input only.

Two async fronts batch concurrent requests with a size-or-deadline window,
the BatchMaker pattern applied to crypto (SURVEY §7 "hard parts": offload
must be batched or it adds latency): `VerifyService` (one per process, the
device's) and `AsyncVerifierPool` (per node, host backends in an executor).
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import itertools
import logging
import queue
import threading
import time
from typing import Sequence

import numpy as np

from .. import tracing
from ..config import ConfigError
from ..crypto import BatchItem
from ..metrics import Counter, Histogram

logger = logging.getLogger("narwhal.tpu.verifier")

_MIN_BUCKET = 16
_MAX_BUCKET = 8192


def _scalar_lib():
    """The native host scalar pipeline, or None (pure-Python fallback)."""
    from ..native import load_scalar

    return load_scalar()


def _next_pow2(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b *= 2
    return b


def _sharded_kernels(kernel, mesh, data_axis: str):
    """The mesh-sharded verify pipeline: STAGED kernels from the process-
    wide registry (kernel_registry.sharded — one compile per (kernel, mesh
    shape) no matter how many verifiers/modes share the mesh).

    The monolithic verify_batch_kernel/msm_accumulate_kernel traces compile
    as single large XLA modules (minutes each on XLA:CPU); the sharded
    variant dispatches the split stages instead —
    ed25519.verify_decompress_kernel (ONE ladder compile serving the A set,
    the R set, AND both msm point sets), verify_straus_kernel,
    verify_verdict_kernel, msm_window_kernel — with intermediates resident
    on device between stages and donated forward. Per-lane arithmetic is
    identical to the monoliths, so verdicts are bit-equal.

    Returns (item_fn, msm_fn) with the monoliths' host-facing signatures:
    msm_fn takes the bucket's raw rows and returns the one flat result.
    """
    from jax.sharding import PartitionSpec as P

    from . import kernel_registry

    b = P(data_axis)  # [B]
    bn = P(data_axis, None)  # [B, NLIMB] / [B, W] / [B, ROW_BYTES] batch-leading rows
    cnb = P(None, None, data_axis)  # [4, NLIMB, B] coord stacks

    decompress = kernel_registry.sharded(
        kernel.verify_decompress_kernel, mesh,
        in_specs=(bn, b), out_specs=(cnb, b),
    )
    straus = kernel_registry.sharded(
        kernel.verify_straus_kernel, mesh,
        in_specs=(cnb, bn, bn), out_specs=cnb,
        donate_argnums=(0,),
    )
    # No donation on verdict/msm_window: their outputs are far smaller
    # than the coordinate-stack inputs, so nothing could alias and jax
    # would warn 'donated buffers were not usable' on every compile.
    verdict = kernel_registry.sharded(
        kernel.verify_verdict_kernel, mesh,
        in_specs=(cnb, cnb, bn, b, b, b), out_specs=(b, b),
    )
    msm_window = kernel_registry.sharded(
        kernel.msm_window_kernel, mesh,
        # V [4, NLIMB, W] has no batch axis left: per-device partial
        # accumulates, one XLA-inserted cross-device reduce (replicated).
        in_specs=(cnb, bn), out_specs=None,
        static_argnames=("chunk",),
    )

    def item_fn(a_y, a_sign, r_y, r_sign, k_digits, s_digits):
        a_pt, a_valid = decompress(a_y, a_sign)
        r_pt, r_valid = decompress(r_y, r_sign)
        acc = straus(a_pt, k_digits, s_digits)
        return verdict(acc, r_pt, r_y, r_sign, a_valid, r_valid)

    # The monolith's own expansion of raw rows and its one result array,
    # each jitted on the data axis: written once, in ed25519.py.
    expand = kernel_registry.sharded(
        kernel.expand_rows, mesh,
        in_specs=(bn,), out_specs=(bn, b, bn, b, bn, bn),
    )
    result = kernel_registry.sharded(
        kernel.msm_result, mesh,
        in_specs=(None, None, b), out_specs=None,
    )

    def msm_fn(rows):
        a_y, a_sign, r_y, r_sign, ak_digits, z_digits = expand(rows)
        a_pt, a_valid = decompress(a_y, a_sign)
        r_pt, r_valid = decompress(r_y, r_sign)
        v_a = msm_window(a_pt, ak_digits)
        v_r = msm_window(r_pt, z_digits)
        return result(v_a, v_r, a_valid & r_valid)

    return item_fn, msm_fn


def msm_epilogue_check(
    va_limbs: np.ndarray, vr_limbs: np.ndarray, sum_s: int, kernel, lib=None
) -> bool:
    """Host half of the batch check: Horner-collapse the device's
    per-window point sums and test
    [8]([Σ z_iS_i]B + Σ_w 16^(63-w) (V_a[w] + V_r[w-32])) == identity.

    va_limbs: int32[4, NLIMB, 64] and vr_limbs: int32[4, NLIMB, 32] loose
    X/Y/Z/T limbs from msm_accumulate_kernel (MSB-first window lanes; the
    R accumulator covers only the low 32 windows because z_i < 2^128).
    The device equivalent would be sub-tile sequential work costing
    hundreds of ms.

    `lib`: the native scalar library (`native.load_scalar()`), which walks
    it in `msm_epilogue_native` with the GIL released — what the served
    path passes. Without it the walk runs here on Python integers, ~750
    bigint point operations under the GIL: the no-toolchain twin, and the
    tests' oracle for the native one. Same mathematics, same accept set.

    COFACTORED (the [8]·): torsion components of adversarial A/R cancel
    deterministically, so acceptance never depends on the random z_i — a
    cofactorless batch would accept a torsion-defect signature with
    probability 1/8 over z, making two honest verifiers of the SAME bytes
    disagree at random (a consensus-splitting vector). This matches
    ed25519-dalek's batch_verify semantics (RFC 8032 cofactored); the
    strict per-item rule differs on such crafted inputs, so in msm mode
    every per-item verdict (small buckets, fallback) also uses the
    kernel's device-computed cofactored output, keeping the whole tpu
    backend deterministic.
    Committees must not mix cofactored (tpu) and cofactorless (cpu host
    library) backends if adversarially-crafted torsion keys are a concern.
    """
    ref = kernel.ref
    if lib is not None:
        va = np.ascontiguousarray(va_limbs, np.int32)
        vr = np.ascontiguousarray(vr_limbs, np.int32)
        rc = -1
        if va.shape == (4, kernel.NLIMB, 64) and vr.shape[:2] == va.shape[:2]:
            rc = lib.msm_epilogue_native(
                va.ctypes.data, vr.ctypes.data, vr.shape[2],
                (sum_s % ref.L).to_bytes(32, "little"),
            )
        if rc < 0:
            raise ValueError(
                f"window sums {va.shape} / {vr.shape} are not the kernels' "
                "[4, NLIMB, 64] / [4, NLIMB, 1..64]"
            )
        return rc == 1
    Wa = va_limbs.shape[2]
    off = Wa - vr_limbs.shape[2]

    def window_point(v, w):
        return tuple(kernel.limbs_to_int(v[c, :, w]) % ref.P for c in range(4))

    acc = (0, 1, 1, 0)  # identity, extended coordinates
    for w in range(Wa):
        for _ in range(4):
            acc = ref.point_double(acc)
        acc = ref.point_add(acc, window_point(va_limbs, w))
        if w >= off:
            acc = ref.point_add(acc, window_point(vr_limbs, w - off))
    acc = ref.point_add(acc, ref.point_mul(sum_s % ref.L, ref.G))
    for _ in range(3):  # cofactor 8
        acc = ref.point_double(acc)
    # Identity ⇔ X ≡ 0 and Y ≡ Z (mod p).
    return acc[0] % ref.P == 0 and (acc[1] - acc[2]) % ref.P == 0


# What `submit` and `submit_groups` return: opaque to callers but for
# `padded`, the rows handed to the device with their padding (0 where no
# row passed the host prechecks).
SubmitHandle = collections.namedtuple("SubmitHandle", "ok idx outs packed items padded")
GroupsHandle = collections.namedtuple("GroupsHandle", "ok candidates outs groups padded")
# One msm dispatch of either lane, until its collect: the device's one
# result array, the host's sum of scalars for the epilogue, the staging
# buffer the rows went up in and how many of its rows were written.
MsmDispatch = collections.namedtuple("MsmDispatch", "out sum_s staged dirty")
# Staging buffers kept per bucket size: the service's in-flight bound + 1.
_STAGING_KEPT = 4


class TpuVerifier:
    """Synchronous batch verifier backed by the JAX kernels.

    mode="msm" (default): one random-linear-combination check per bucket —
    [Σ z_iS_i]B − Σ[z_ik_i]A_i − Σ[z_i]R_i == 0 with fresh 128-bit z_i —
    sharing a single doubling chain across the whole bucket (~2x the
    per-item kernel's throughput). A failed bucket (any bad or malformed
    signature) falls back to the per-item kernel to locate offenders, so
    adversarial input degrades one bucket to ~old cost, never correctness.
    All msm-mode verdicts — the batch check, small buckets and the
    per-item fallback — use the device-computed COFACTORED rule, so the
    accept set is deterministic and independent of flush composition.
    mode="item": always the per-item Straus kernel, strict verdict.
    """

    def __init__(
        self,
        max_bucket: int = _MAX_BUCKET,
        mode: str = "msm",
        msm_min_bucket: int = 512,
        fixed_bucket: bool = False,
        mesh=None,
        data_axis: str = "data",
    ):
        from . import ed25519 as kernel  # deferred: imports jax

        self.kernel = kernel
        self.max_bucket = max_bucket
        self.mode = mode
        # Small buckets stay on the per-item kernel: they're the latency
        # path, the msm advantage is amortization, and each extra bucket
        # shape costs a multi-minute first compile.
        self.msm_min_bucket = msm_min_bucket
        # fixed_bucket pads EVERY dispatch to max_bucket: one shape means
        # one program per process to load (or, on a tree's first start, to
        # trace and compile). What padding a near-empty
        # flush to the full bucket costs on a locally attached chip is
        # unmeasured; ROADMAP D1/S2 decide it from the benchmark's numbers.
        # The protocol-serving VerifyService runs this way.
        self.fixed_bucket = fixed_bucket
        # Dispatch, epilogue and detour counts (see the module docstring);
        # bumped from the service's sealing loops AND its collect thread,
        # hence the lock.
        self.counts: collections.Counter = collections.Counter()
        self._counts_lock = threading.Lock()
        # Staging buffers of raw rows, uint8[bucket, ROW_BYTES], free for
        # the next msm dispatch: bucket -> [(buffer, rows its last use
        # wrote)]. Everything beyond those rows is zero. A buffer leaves
        # at `submit` and comes back at `collect`, not before: the runtime
        # may read the host memory until the program that takes it has run.
        self._staging: dict[int, list] = {}
        self._staging_lock = threading.Lock()
        # mesh: shard verify batches over the mesh's data axis (SURVEY
        # §7.8a's TpuVerifier service at §5.8 scale — the certificate
        # analog of `--dag-shards` for the commit walk). Items are
        # embarrassingly parallel; the per-item kernel shards its whole
        # batch, the msm kernel's shared accumulator V comes back via the
        # XLA-inserted cross-device reduction. Constraint: every bucket
        # size (powers of two up to max_bucket) must be divisible by the
        # data-axis size.
        self.mesh = mesh
        if mesh is not None:
            # Fail at CONSTRUCTION, not first dispatch: every bucket this
            # verifier can ever pad to is a power of two in
            # [_MIN_BUCKET, max_bucket] (or exactly max_bucket when
            # fixed_bucket), and the data axis must divide each — a
            # mis-sized mesh must stop a node at startup the way
            # verify_rule validation does, not stall it at the first
            # verify (advisor r4).
            if data_axis not in mesh.shape:
                raise ConfigError(
                    f"verifier mesh has no {data_axis!r} axis "
                    f"(axes: {tuple(mesh.shape)})"
                )
            data_size = mesh.shape[data_axis]
            smallest = self.max_bucket if self.fixed_bucket else _MIN_BUCKET
            if smallest % data_size != 0 or self.max_bucket % data_size != 0:
                raise ConfigError(
                    f"verify shard count {data_size} must divide every "
                    f"dispatch bucket (smallest {smallest}, largest "
                    f"{self.max_bucket}); use a power of two <= {smallest}"
                )

            # Shared per-mesh jit wrappers: every verifier over this mesh
            # (either mode — msm keeps the item kernel as its fallback)
            # reuses ONE compiled kernel pair instead of re-jitting.
            self._item_kernel, self._msm_kernel = _sharded_kernels(
                kernel, mesh, data_axis
            )
        else:
            # One device: the module-level kernels. `msm_accumulate_kernel`
            # is persisted (its first dispatch at a bucket loads its export
            # from beside the compile cache, kernel_registry.py); the mesh
            # wrappers above and the per-item detour trace as they always did.
            self._item_kernel = kernel.verify_batch_kernel
            self._msm_kernel = kernel.msm_accumulate_kernel

    def _count(self, key: str) -> None:
        with self._counts_lock:
            self.counts[key] += 1

    def _count_transfer(self, direction: str, nbytes: int) -> None:
        """One array crossed the device link on the msm path: `up` is
        counted as `upload`, `down` as `readback`."""
        key = "upload" if direction == "up" else "readback"
        with self._counts_lock:
            self.counts[key] += 1
            self.counts[key + "_bytes"] += nbytes
        SERVICE_TRANSFERS.labels(direction).inc()
        SERVICE_BYTES.labels(direction).inc(nbytes)

    def _stage(self, bucket: int, rows: int) -> np.ndarray:
        """A staging buffer for `rows` useful rows of a `bucket`-row
        dispatch: zero from `rows` on (only what its last use dirtied is
        zeroed again); the caller writes every byte of the rows before."""
        with self._staging_lock:
            free = self._staging.get(bucket)
            buf, dirty = free.pop() if free else (None, 0)
        if buf is None:
            return np.zeros((bucket, self.kernel.ROW_BYTES), np.uint8)
        if dirty > rows:
            buf[rows:dirty] = 0
        return buf

    def _unstage(self, dispatched: "MsmDispatch") -> None:
        """The dispatch was collected: its buffer may be written again."""
        buf = dispatched.staged
        with self._staging_lock:
            free = self._staging.setdefault(buf.shape[0], [])
            if len(free) < _STAGING_KEPT:
                free.append((buf, dispatched.dirty))

    def _run_msm(self, staged: np.ndarray, dirty: int, sum_s: int) -> "MsmDispatch":
        """Hand one staged bucket to the device: one array up, and the
        copy of the one result back started as soon as the program ends,
        so `collect` finds the bytes already local."""
        out = self._msm_kernel(staged)
        out.copy_to_host_async()
        self._count_transfer("up", staged.nbytes)
        return MsmDispatch(out, sum_s, staged, dirty)

    def _precheck_native(self, items: Sequence[BatchItem], lib):
        """Batched canonicality checks + challenge scalars in C (GIL
        released for the call): returns (precheck[n] bool, a_raw, r_raw,
        s_raw, k_raw as uint8[n, 32])."""
        n = len(items)
        lenok = np.ones(n, bool)
        pk_parts: list[bytes] = []
        sig_parts: list[bytes] = []
        msg_parts: list[bytes] = []
        lens = np.empty(n, np.int64)
        zero32, zero64 = b"\0" * 32, b"\0" * 64
        for i, (pk, msg, sig) in enumerate(items):
            if len(pk) != 32 or len(sig) != 64:
                lenok[i] = False
                pk_parts.append(zero32)
                sig_parts.append(zero64)
                lens[i] = 0
                continue
            pk_parts.append(pk)
            sig_parts.append(sig)
            msg_parts.append(msg)
            lens[i] = len(msg)
        pk_buf = b"".join(pk_parts)
        sig_buf = b"".join(sig_parts)
        offs = np.zeros(n + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        k_raw = np.empty((n, 32), np.uint8)
        ok_raw = np.empty(n, np.uint8)
        rc = lib.ed25519_precheck_k(
            n,
            pk_buf,
            sig_buf,
            b"".join(msg_parts),
            offs.ctypes.data,
            k_raw.ctypes.data,
            ok_raw.ctypes.data,
        )
        if rc != 0:  # pragma: no cover - internal failure only
            raise RuntimeError(f"ed25519_precheck_k failed: rc={rc}")
        precheck = ok_raw.astype(bool) & lenok
        sig_rows = np.frombuffer(sig_buf, np.uint8).reshape(n, 64)
        a_raw = np.frombuffer(pk_buf, np.uint8).reshape(n, 32)
        return precheck, a_raw, sig_rows[:, :32], sig_rows[:, 32:], k_raw

    def _precheck_py(self, items: Sequence[BatchItem]):
        """Pure-Python twin of `_precheck_native` (no-toolchain fallback);
        bit-identical outputs — asserted by tests/test_tpu_ed25519.py."""
        n = len(items)
        precheck = np.zeros(n, bool)
        a_raw = np.zeros((n, 32), np.uint8)
        r_raw = np.zeros((n, 32), np.uint8)
        s_raw = np.zeros((n, 32), np.uint8)
        k_raw = np.zeros((n, 32), np.uint8)
        L = self.kernel.ref.L
        P = self.kernel.ref.P
        sha512 = hashlib.sha512
        top_mask = (1 << 255) - 1
        frombuf = np.frombuffer
        for i, (pk, msg, sig) in enumerate(items):
            if len(pk) != 32 or len(sig) != 64:
                continue
            rs, sb = sig[:32], sig[32:]
            if int.from_bytes(sb, "little") >= L:
                continue
            if (int.from_bytes(pk, "little") & top_mask) >= P:
                continue
            if (int.from_bytes(rs, "little") & top_mask) >= P:
                continue
            k_int = int.from_bytes(sha512(rs + pk + msg).digest(), "little") % L
            a_raw[i] = frombuf(pk, np.uint8)
            r_raw[i] = frombuf(rs, np.uint8)
            s_raw[i] = frombuf(sb, np.uint8)
            k_raw[i] = frombuf(k_int.to_bytes(32, "little"), np.uint8)
            precheck[i] = True
        return precheck, a_raw, r_raw, s_raw, k_raw

    def submit(self, items: Sequence[BatchItem]):
        """Precheck on host, stage the raw rows and enqueue the device
        dispatch(es). Returns an opaque handle for `collect` — dispatch is
        asynchronous, so several submitted batches stay in flight and the
        device readback latency overlaps the next batch's host work and
        compute. The handle's `padded` is the rows handed to the device,
        padding included. An msm dispatch writes only its useful rows (112
        raw bytes each) into a staging buffer that stays its own until
        `collect`; nothing here runs over the padded bucket.

        The per-item host work (SHA-512 challenge, canonicality checks,
        msm scalars) runs in native/scalar_ops.cpp when available — the
        Python loop it replaces was the pipelined path's ceiling (~250 ms
        per 32k batch vs ~3 ms native)."""
        n = len(items)
        if n == 0:
            return SubmitHandle(np.zeros(0, bool), np.zeros(0, np.int64), [], None, items, 0)
        ok = np.zeros(n, bool)
        lib = _scalar_lib()
        if lib is not None:
            precheck, a_all, r_all, s_all, k_all = self._precheck_native(items, lib)
        else:
            precheck, a_all, r_all, s_all, k_all = self._precheck_py(items)

        idx = np.flatnonzero(precheck)
        if idx.size == 0:
            return SubmitHandle(ok, idx, [], None, items, 0)

        # Compact to precheck-passing rows (contiguous for the C fold).
        # The raw bytes are all the handle keeps: an msm dispatch writes
        # them into its staging buffer as they are, and the limbs and
        # digits the per-item kernel takes are derived in _dispatch_items,
        # which in msm mode is the rare detour.
        packed = tuple(np.ascontiguousarray(x[idx]) for x in (a_all, r_all, k_all, s_all))

        outs = []  # (kind, lo, hi, pad, device out)
        for lo in range(0, idx.size, self.max_bucket):
            hi = min(lo + self.max_bucket, idx.size)
            if self.fixed_bucket:
                bucket = self.max_bucket
            else:
                bucket = _MIN_BUCKET
                while bucket < hi - lo:
                    bucket *= 2
            pad = bucket - (hi - lo)

            if self.mode == "msm" and bucket >= self.msm_min_bucket:
                out = self._dispatch_msm(packed, lo, hi, pad)
                kind = "msm"
            else:
                out = self._dispatch_items(packed, lo, hi, pad)
                kind = "item"
                # Kick off the device->host copy as soon as the kernel
                # finishes so collect() finds the bytes already local.
                for arr in out:  # (strict, cofactored) device arrays
                    arr.copy_to_host_async()
            outs.append((kind, lo, hi, pad, out))
        padded = sum(hi - lo + pad for _, lo, hi, pad, _ in outs)
        return SubmitHandle(ok, idx, outs, packed, items, padded)

    def _dispatch_items(self, packed, lo, hi, pad):
        """Per-item Straus kernel over one padded bucket. Its operands —
        13-bit limbs, sign bits, 4-bit digit planes, narrow dtypes the
        kernel widens on the device — are derived here, on demand, from
        the raw rows the handle holds: numpy over the whole bucket, which
        in msm mode only invalid input pays."""
        a_raw, r_raw, k_raw, s_raw = packed

        def pad_to(arr):
            if pad == 0:
                return arr[lo:hi]
            return np.concatenate(
                [arr[lo:hi], np.repeat(arr[lo : lo + 1], pad, axis=0)]
            )

        def point(raw):
            return (
                self.kernel.bytes_to_limbs(raw).astype(np.int16),
                (raw[:, 31] >> 7).astype(np.int8),
            )

        k_digits = self.kernel.bytes_to_digits(pad_to(k_raw)).astype(np.int8)
        s_digits = self.kernel.bytes_to_digits(pad_to(s_raw)).astype(np.int8)
        self._count("item_dispatch")
        return self._item_kernel(
            *point(pad_to(a_raw)), *point(pad_to(r_raw)), k_digits, s_digits
        )

    def _fold_native(self, lib, k_rows: np.ndarray, s_rows: np.ndarray, rnd: bytes):
        """ak_i = z_i*k_i mod L and sum(z_i*s_i) mod L in C."""
        m = k_rows.shape[0]
        ak_raw = np.empty((m, 32), np.uint8)
        sum_raw = np.empty(32, np.uint8)
        lib.scalar_fold(
            m,
            k_rows.ctypes.data,
            s_rows.ctypes.data,
            rnd,
            ak_raw.ctypes.data,
            sum_raw.ctypes.data,
        )
        return ak_raw, int.from_bytes(sum_raw.tobytes(), "little")

    def _fold_py(self, k_rows: np.ndarray, s_rows: np.ndarray, rnd: bytes):
        """Python twin of `_fold_native` (identical outputs)."""
        L = self.kernel.ref.L
        m = k_rows.shape[0]
        from_bytes = int.from_bytes
        kb, sb = k_rows.tobytes(), s_rows.tobytes()
        ak_parts: list[bytes] = []
        sum_s = 0
        for t in range(m):
            z = from_bytes(rnd[16 * t : 16 * (t + 1)], "little")
            k = from_bytes(kb[32 * t : 32 * (t + 1)], "little")
            s = from_bytes(sb[32 * t : 32 * (t + 1)], "little")
            ak_parts.append(((z * k) % L).to_bytes(32, "little"))
            sum_s += z * s
        ak_raw = np.frombuffer(b"".join(ak_parts), np.uint8).reshape(m, 32)
        return ak_raw, sum_s % L

    def _dispatch_msm(self, packed, lo, hi, pad):
        """Random-linear-combination check over one bucket. Fresh 128-bit
        z_i per item per call (os.urandom — the adversary must not predict
        them); zero rows are inert padding. The rows go up as raw bytes
        in one staging buffer (ed25519.expand_rows has the layout); the
        Horner/identity epilogue runs on host at collect time."""
        import os as _os

        a_raw, r_raw, k_raw, s_raw = packed
        m = hi - lo
        # RLC folding weights must be unpredictable to an adversary who
        # crafts signatures (a seeded stream would let forged batches pass
        # the combined check); verdicts don't depend on the draw — a failed
        # fold bisects deterministically — so replays stay bit-identical
        # where it matters.
        rnd = _os.urandom(16 * m)  # lint: allow(raw-entropy)
        k_rows, s_rows = k_raw[lo:hi], s_raw[lo:hi]  # row slices: contiguous
        lib = _scalar_lib()
        if lib is not None:
            ak_raw, sum_s = self._fold_native(lib, k_rows, s_rows, rnd)
        else:
            ak_raw, sum_s = self._fold_py(k_rows, s_rows, rnd)
        staged = self._stage(m + pad, m)
        staged[:m, 0:32] = a_raw[lo:hi]
        staged[:m, 32:64] = r_raw[lo:hi]
        staged[:m, 64:96] = ak_raw
        staged[:m, 96:112] = np.frombuffer(rnd, np.uint8).reshape(m, 16)
        self._count("msm_dispatch")
        return self._run_msm(staged, m, sum_s)

    def submit_groups(self, groups):
        """Dispatch half-aggregated certificate proofs (types.Certificate
        compact form). Each group is (items [(pk, msg, R)], zs, s_agg):
        the claim sum(z_i s_i) = s_agg over the verification equations
        [s_i]B = R_i + [k_i]A_i. One msm dispatch checks the OUTER random
        combination over all groups — fresh 128-bit w_g per group, so
        adversarially related groups cannot cancel each other:
          [sum_g w_g s_agg_g]B == sum_g w_g (sum_i z_i R_i + [z_i k_i]A_i)
        Each signer contributes two kernel rows (A_i with scalar w z k, and
        R_i — fed through the A slot — with scalar w z; the R slot's
        128-bit scalar lane is too narrow for the 256-bit products). Zero
        R-slot rows are inert. Returns a handle for `collect_groups`; its
        `padded` is the rows handed to the device, padding included."""
        import os as _os

        n_groups = len(groups)
        ok = np.zeros(n_groups, bool)
        candidates = []  # (group index, items, zs, s_agg, w)
        for g, (items, zs, s_agg) in enumerate(groups):
            if items and 2 * len(items) <= self.max_bucket:
                # Adversarial RLC weight: same argument as _fold above.
                w = int.from_bytes(_os.urandom(16), "little")  # lint: allow(raw-entropy)
                candidates.append((g, items, zs, s_agg, w))
            # oversized/empty groups fall back at collect (host verify)
        outs = []
        lo = 0
        while lo < len(candidates):
            # Greedy-pack whole groups into one bucket (a group must not
            # straddle dispatches: the epilogue identity is per dispatch).
            hi, rows = lo, 0
            while hi < len(candidates) and rows + 2 * len(candidates[hi][1]) <= self.max_bucket:
                rows += 2 * len(candidates[hi][1])
                hi += 1
            chunk = candidates[lo:hi]
            lo = hi
            outs.append((chunk, self._dispatch_group_chunk(chunk, rows)))
        padded = sum(d.staged.shape[0] for _, d in outs if d is not None)
        return GroupsHandle(ok, candidates, outs, groups, padded)

    def _dispatch_group_chunk(self, chunk, rows):
        """One msm dispatch over the doubled rows of `chunk`'s groups, as
        `_dispatch_msm` makes it (None where an item fails the prechecks);
        the staging buffer's row count is what it was padded to."""
        L = self.kernel.ref.L
        lib = _scalar_lib()
        sum_s = 0
        # Per item: k_i = H(R||A||m) + canonicality (native precheck path;
        # the fake 64-byte signature is R || 0 so the s-range check passes).
        flat_items = []
        for _, items, zs, s_agg, w in chunk:
            flat_items.extend(items)
        m = len(flat_items)
        sig_rows = b"".join(r + b"\0" * 32 for _, _, r in flat_items)
        fake = [(pk, msg, sig_rows[64 * i : 64 * (i + 1)]) for i, (pk, msg, _) in enumerate(flat_items)]
        if lib is not None:
            precheck, a_all, r_all, _s, k_all = self._precheck_native(fake, lib)
        else:
            precheck, a_all, r_all, _s, k_all = self._precheck_py(fake)
        if not bool(precheck.all()):
            # Some item failed canonicality prechecks: the combined check
            # cannot pass attribution; collect falls back per group.
            return None

        # Effective scalars y_i = w_g * z_i and ak_i = y_i * k_i (mod L).
        w_rows = np.empty((m, 32), np.uint8)
        z_rows = np.empty((m, 32), np.uint8)
        t = 0
        for _, items, zs, s_agg, w in chunk:
            sum_s = (sum_s + w * s_agg) % L
            wb = np.frombuffer(w.to_bytes(32, "little"), np.uint8)
            for z in zs:
                w_rows[t] = wb
                z_rows[t] = np.frombuffer(z.to_bytes(32, "little"), np.uint8)
                t += 1
        if lib is not None:
            y_rows = np.empty((m, 32), np.uint8)
            ak_items = np.empty((m, 32), np.uint8)
            lib.scalar_mulmod(
                m, w_rows.ctypes.data, z_rows.ctypes.data, y_rows.ctypes.data
            )
            lib.scalar_mulmod(
                m,
                y_rows.ctypes.data,
                np.ascontiguousarray(k_all[:m]).ctypes.data,
                ak_items.ctypes.data,
            )
        else:
            y_rows = np.empty((m, 32), np.uint8)
            ak_items = np.empty((m, 32), np.uint8)
            for i in range(m):
                w_i = int.from_bytes(w_rows[i].tobytes(), "little")
                z_i = int.from_bytes(z_rows[i].tobytes(), "little")
                k_i = int.from_bytes(k_all[i].tobytes(), "little")
                y = (w_i * z_i) % L
                y_rows[i] = np.frombuffer(y.to_bytes(32, "little"), np.uint8)
                ak_items[i] = np.frombuffer(
                    ((y * k_i) % L).to_bytes(32, "little"), np.uint8
                )

        # Doubled rows: even = A_i with scalar ak_i, odd = R_i (through the
        # A slot) with scalar y_i; the R and z columns stay zero.
        bucket = self.max_bucket if self.fixed_bucket else _next_pow2(rows)
        staged = self._stage(bucket, rows)
        staged[:rows] = 0
        staged[0:rows:2, 0:32] = a_all[:m]
        staged[1:rows:2, 0:32] = r_all[:m]
        staged[0:rows:2, 64:96] = ak_items
        staged[1:rows:2, 64:96] = y_rows
        self._count("group_dispatch")
        return self._run_msm(staged, rows, sum_s)

    def _batch_passes(self, out, sum_s: int) -> bool:
        """Force one msm dispatch's one result array (blocks on the
        device): the all-rows-valid flag, then the host epilogue identity
        on views of the same array, native where the scalar library is
        loaded (counted `epilogue_native`) and on Python integers where it
        is not (`epilogue_python`)."""
        flat = np.asarray(out)
        self._count_transfer("down", flat.nbytes)
        va, vr, valid = self.kernel.split_msm_result(flat)
        if not valid:
            return False
        lib = _scalar_lib()
        event = "epilogue_native" if lib is not None else "epilogue_python"
        self._count(event)
        SERVICE_EVENTS.labels(event).inc()
        return msm_epilogue_check(va, vr, sum_s, self.kernel, lib)

    def _dispatch_passes(self, dispatched) -> bool:
        """Force one msm dispatch of either lane (None: a group chunk that
        never left) and hand its staging buffer back to the pool."""
        if dispatched is None:
            return False
        passed = self._batch_passes(dispatched.out, dispatched.sum_s)
        self._unstage(dispatched)
        return passed

    def collect_groups(self, handle) -> list[bool]:
        """Resolve a `submit_groups` handle. A failed combined check
        RE-DISPATCHES each group as its own device msm chunk (all singles
        in flight before the first readback, so the bisect stays
        pipelined); only groups whose solo device check still fails reach
        the pure-Python host verifier. One adversarial compact certificate
        therefore costs the attacker's own group a host walk — it cannot
        drag every honest group in the chunk onto the 1-core host (the
        r4-advisor liveness-DoS amplification). Oversized groups (2 rows
        per signer > max_bucket — a committee larger than half the service
        bucket) still host-verify; splitting one group's epilogue identity
        across dispatches isn't supported."""
        from ..types import host_verify_aggregate

        ok, candidates, outs, groups, _ = handle
        for chunk, dispatched in outs:
            if self._dispatch_passes(dispatched):
                for g, *_ in chunk:
                    ok[g] = True
                continue
            if len(chunk) > 1:
                logger.warning(
                    "aggregate chunk of %d certificate groups failed the "
                    "combined check; re-dispatching each group solo",
                    len(chunk),
                )
                self._count("group_solo_redispatch")
                solos = [
                    (entry, self._dispatch_group_chunk([entry], 2 * len(entry[1])))
                    for entry in chunk
                ]
            else:
                solos = [(chunk[0], dispatched)]
            for (g, items, zs, s_agg, _), disp in solos:
                if len(chunk) > 1 and self._dispatch_passes(disp):
                    ok[g] = True
                else:
                    # The group's own device check failed: almost surely
                    # invalid, but the host verdict is authoritative for
                    # the rare device-fault case.
                    self._count("group_host_verify")
                    ok[g] = host_verify_aggregate(items, zs, s_agg)
        # Oversized/empty groups never dispatched: host-verify them too.
        dispatched_gs = {g for g, *_ in candidates}
        for g, (items, zs, s_agg) in enumerate(groups):
            if g not in dispatched_gs and items:  # empty groups stay False
                self._count("group_host_verify")
                ok[g] = host_verify_aggregate(items, zs, s_agg)
        return ok.tolist()

    def collect(self, handle) -> list[bool]:
        """Materialize a `submit` handle's results (blocks on the device).
        A failed msm bucket re-dispatches the per-item kernel to locate the
        offending signatures (rare path: only adversarial/corrupt input);
        strict-kernel rejects are then re-checked against the cofactored
        rule so the msm mode's accept set stays deterministic."""
        ok, idx, outs, packed, items, _ = handle
        if idx.size:
            results = np.zeros(idx.size, bool)
            # In msm mode EVERY verdict is the device-computed cofactored
            # one — small buckets, fallback buckets and the batch check all
            # share one accept set, so no signature's fate can depend on
            # flush size or bucket composition (consensus-split safety),
            # and there is no per-item host recheck an attacker could
            # amplify. mode="item" keeps the strict (host-library) rule.
            pick = 1 if self.mode == "msm" else 0

            for kind, lo, hi, pad, out in outs:
                if kind == "item":
                    results[lo:hi] = np.asarray(out[pick])[: hi - lo]
                    continue
                if self._dispatch_passes(out):
                    results[lo:hi] = True
                else:
                    logger.warning(
                        "msm bucket of %d signatures failed the batch "
                        "check; re-dispatching it per item",
                        hi - lo,
                    )
                    self._count("msm_redispatch")
                    fallback = self._dispatch_items(packed, lo, hi, pad)
                    results[lo:hi] = np.asarray(fallback[1])[: hi - lo]
            ok[idx] = results
        return ok.tolist()

    def __call__(self, items: Sequence[BatchItem]) -> list[bool]:
        return self.collect(self.submit(items))


def data_mesh(shards: int, devices=None):
    """The verify-sharding mesh: `shards` devices on a 1-axis 'data' mesh
    (SURVEY §7.8a at §5.8 scale — the certificate analog of --dag-shards).
    This is THE construction path for sharded verifiers: the node surface
    (--verify-shards) and the driver dryrun both come through here, so the
    dryrun's CPU-mesh evidence covers exactly what the CLI wires.
    `devices` pins an explicit list (tests; the dryrun's hermetic device
    set)."""
    from . import device_mesh

    return device_mesh(shards, "data", "--verify-shards", devices)


# The service's scrape series. Process-wide like the service: every node
# mounts them in its registry (`Registry.mount`), so a co-hosted
# committee's scrapes all show the one service they share.
SERVICE_ROWS = Counter(
    "verify_service_rows_total",
    "Rows the shared verify service handed to the device per lane "
    "(kind=useful: signatures, and 2 per signer of a certificate proof; "
    "kind=padded: the buckets dispatched)",
    ("lane", "kind"),
)
SERVICE_TRANSFERS = Counter(
    "verify_service_transfers_total",
    "Arrays the device verifier's msm dispatches moved over the device "
    "link (dir=up: operands handed to the kernel; dir=down: results read "
    "back): one each per flush. A detour's per-item dispatch is counted "
    "as the detour it is, not here",
    ("dir",),
)
SERVICE_BYTES = Counter(
    "verify_service_bytes_total",
    "Bytes the device verifier's msm dispatches moved over the device link "
    "(dir=up: bucket rows x 112 raw bytes; dir=down: the window sums and "
    "the all-rows-valid flag)",
    ("dir",),
)
SERVICE_WAIT = Histogram(
    "verify_service_wait_seconds",
    "Where a verification waits in the shared service (phase=queue: "
    "enqueued -> its flush sealed, per entry; phase=turnaround: sealed -> "
    "verdicts posted, per flush; phase=wake: posted -> the waiting "
    "coroutine resumed, per entry)",
    ("phase",),
)
SERVICE_EVENTS = Counter(
    "verify_service_events_total",
    "What a flush of the device verifier met on its way (event="
    "epilogue_native / epilogue_python: which twin ran an msm dispatch's "
    "host epilogue; event=deferred: a seal found every in-flight slot "
    "taken and left its entries queued for the next completion)",
    ("event",),
)
_LANES = {"s": "singles", "g": "groups"}


class VerifyService:
    """Process-wide pipelined verification front for the TPU backend.

    The per-node AsyncVerifierPool coalesces one node's concurrent
    requests, but a host running many nodes (the in-process committee
    bench; any multi-node-per-host deployment) then issues many small
    device dispatches, each paying the full dispatch + readback latency.
    ONE instance per process merges every node's items into large buckets
    and keeps several batches in flight, so all protocol hops of all nodes
    share flushes. The constants: a 2,048-row bucket, a 3 ms seal deadline
    counted from the oldest queued entry, at most three flushes sealed and
    not yet answered. ROADMAP S2 sizes the bucket and merges the hops from
    the benchmark's numbers; the deadline and the bound have not moved.

    Thread model (asyncio-loop agnostic — nodes on different loops can
    share it). A flush never competes for the interpreter with the loop
    that waits for it:
      callers     append (item, loop, future) under a lock; the first
                  entry of an idle queue arms ONE seal on its own loop
                  (`call_later` at the oldest entry's deadline; a full
                  bucket seals at once, inside the enqueue);
      the seal    runs on that loop's thread, which holds the interpreter
                  already: takes both lanes (entries of every loop), runs
                  TpuVerifier.submit / submit_groups inline — two
                  GIL-free native calls, the useful rows' raw bytes
                  into a staging buffer, the jit dispatch of that one
                  buffer; 0.7 ms at the median on the v5e's host,
                  PERF.md §5 — and hands the handle to the collect
                  thread.
                  It never blocks: with every in-flight slot taken it
                  leaves its entries queued (`flushes["deferred"]`) and
                  the next completion arms it again, on the loop of the
                  oldest queued entry;
      collect thread blocks on the device result (one array), hands
                  the staging buffer back, runs the native epilogue (no
                  interpreter needed) and resolves a flush's futures
                  with one `call_soon_threadsafe` per loop.
    While one callback holds a loop nothing seals there; no waiter could
    resume before that loop turns either, so only the overlap of device
    work with the stall is lost. Presents the AsyncVerifierPool interface
    (`await verify(...)`, `close()`).

    Flight record (tracing.flight, always on, one per flush; layout in
    tracing.FLIGHT_FIELDS): `flush`, and `wake` once its waiters have all
    resumed. `t_seal`: the batch left the queue; `t_dispatched`: `submit`
    returned; one stamp, `t_posted`, closes a flush: `collect` returned and
    the verdicts went to the waiters' loops, with nothing in between. The
    same sums feed SERVICE_ROWS and SERVICE_WAIT; the dispatch and the
    collect each run under a `tracing.annotation` carrying the flush's seq."""

    _shared: dict[str, "VerifyService"] = {}

    def __init__(
        self,
        verifier: TpuVerifier,
        max_batch: int = 4096,
        max_delay: float = 0.003,
        inflight: int = 3,
    ):
        self.verifier = verifier
        self.max_batch = max_batch
        self.max_delay = max_delay
        # Flushes handed to the device per lane, flushes whose dispatch or
        # readback raised (their waiters got the error: a failed device
        # dispatch is never answered from the host), and seals `deferred`.
        self.flushes: collections.Counter = collections.Counter()
        self._pending: collections.deque = collections.deque()
        # Aggregate-certificate groups (compact certs) ride a second lane:
        # they dispatch through submit_groups (doubled rows, per-group
        # random outer weights) but share the seal, the collect thread and
        # the in-flight bound.
        self._pending_groups: collections.deque = collections.deque()
        self.max_group_rows = max_batch  # 2 rows per signer, same bucket
        # Guards the lanes and everything below.
        self._lock = threading.Lock()
        self._inflight_max = inflight
        self._sealed = 0  # flushes sealed and not yet answered
        self._armed: tuple | None = None  # (loop, due) of the one live seal
        self._closed = False
        self._seq = 0  # flushes sealed, ever
        # Dispatched flushes on their way to the collect thread; bounded by
        # `_sealed`, so a put never blocks the loop.
        self._handoff: queue.SimpleQueue = queue.SimpleQueue()
        # Guards the per-flush wake tallies: waiters of one flush may
        # resume on different loops (threads).
        self._wake_lock = threading.Lock()
        self._collect_thread = threading.Thread(
            target=self._collect_loop, daemon=True, name="verify-collect"
        )
        self._collect_thread.start()
        # A daemon thread frozen inside XLA C++ during interpreter
        # finalization aborts the process ("FATAL: exception not
        # rethrown") — same hazard the DAG prewarm threads guard against.
        # Stop the loop and bounded-join before Python tears down.
        import atexit

        atexit.register(self.shutdown)

    @classmethod
    def shared(
        cls, mode: str, shards: int = 1, devices=None, bucket: int = 2048, **kw
    ) -> "VerifyService":
        """The process-wide instance for an accept-set mode ('item'/'msm')
        and shard count. Raises if the device verifier cannot be built; a
        node asked for the tpu backend then refuses to start. `shards > 1`
        (--verify-shards) shards every flush over a `data_mesh`;
        divisibility against the fixed bucket is validated at
        construction, so a mis-sized mesh stops the node at startup rather
        than at its first verify.

        The verifier runs fixed-bucket (pad every flush to one shape): one
        shape means one jit trace + compile per process instead of one per
        power-of-two flush size. `bucket` sizes that shape for whoever
        creates the instance first — nodes take the 2,048-row default; CPU
        rehearsals and tests create a small one before booting nodes."""
        key = f"{mode}:{shards}"
        svc = cls._shared.get(key)
        if svc is None:
            svc = cls(
                TpuVerifier(
                    max_bucket=bucket,
                    msm_min_bucket=16,
                    mode=mode,
                    fixed_bucket=True,
                    mesh=data_mesh(shards, devices) if shards > 1 else None,
                ),
                max_batch=bucket,
                **kw,
            )
            cls._shared[key] = svc
        return svc

    async def verify(self, public_key: bytes, message: bytes, signature: bytes) -> bool:
        return await self._enqueue(self._pending, (public_key, message, signature))

    async def verify_aggregate(self, items, zs, s_agg: int) -> bool:
        """Half-aggregated certificate proof (compact certs): queued on the
        group lane and checked on device — many groups fuse into one msm
        dispatch under an outer random combination."""
        return await self._enqueue(self._pending_groups, (items, zs, s_agg))

    async def _enqueue(self, lane: collections.deque, item):
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        tally: list = []  # the collect thread puts the flush's wake tally here
        now = time.monotonic()
        with self._lock:
            if self._closed:
                # Nothing seals anymore: an enqueued future would never
                # resolve.
                raise RuntimeError("verify service shut down")
            lane.append((item, loop, fut, now, tally))
            seal_now = self._arm(loop, now)
        if seal_now:
            self._on_seal()
        try:
            return await fut
        finally:
            if tally:
                self._woke(tally[0], time.monotonic())

    def _woke(self, tally: list, t: float) -> None:
        """One waiter of a posted flush resumed. tally = [seq, entries,
        t_posted, resumed, summed lag, longest lag]; the last to resume
        writes the flush's `wake` record."""
        lag = max(0.0, t - tally[2])
        with self._wake_lock:
            tally[3] += 1
            tally[4] += lag
            if lag > tally[5]:
                tally[5] = lag
            done = tally[3] == tally[1]
        SERVICE_WAIT.labels("wake").observe(lag)
        if done:
            tracing.flight("wake", tally[0], tally[3], tally[4], tally[5], tally[2])

    # -- the seal rule (all under the lock) ---------------------------------

    def _singles_due(self, now: float) -> bool:
        return bool(self._pending) and (
            len(self._pending) >= self.max_batch
            or now - self._pending[0][3] >= self.max_delay
        )

    def _groups_due(self, now: float) -> bool:
        """By total doubled-row budget, or the oldest group's deadline."""
        return bool(self._pending_groups) and (
            now - self._pending_groups[0][3] >= self.max_delay
            or sum(2 * len(g[0][0]) for g in self._pending_groups) >= self.max_group_rows
        )

    def _take_singles(self) -> list:
        take = min(len(self._pending), self.max_batch)
        return [self._pending.popleft() for _ in range(take)]

    def _take_groups(self) -> list:
        out, budget = [], self.max_group_rows
        while self._pending_groups:
            need = 2 * len(self._pending_groups[0][0][0])
            if out and need > budget:
                break
            out.append(self._pending_groups.popleft())
            budget -= need
        return out

    def _oldest(self):
        """The oldest queued entry of either lane, or None."""
        heads = [lane[0] for lane in (self._pending, self._pending_groups) if lane]
        return min(heads, key=lambda e: e[3]) if heads else None

    def _seal_is_armed(self) -> bool:
        """A seal is on its way, on a loop that can still run it."""
        return self._armed is not None and not self._armed[0].is_closed()

    def _arm(self, loop, now: float) -> bool:
        """On `loop`'s thread, after an append: see that a seal is on its
        way. True: a lane is due already (a full bucket, or a deadline its
        timer is late for), the caller seals at once."""
        if self._sealed >= self._inflight_max:
            return False  # the next completion arms it
        if self._singles_due(now) or self._groups_due(now):
            return True
        if not self._seal_is_armed():
            self._arm_deadline(loop, now)
        return False

    def _arm_deadline(self, loop, now: float) -> None:
        """On `loop`'s thread: one seal at the oldest entry's deadline."""
        due = self._oldest()[3] + self.max_delay + 1e-4
        self._armed = armed = (loop, due)
        loop.call_later(max(0.0, due - now), self._on_seal, armed)

    def _on_seal(self, armed: tuple | None = None) -> None:
        """On a loop's thread: seal what is due and dispatch it inline.
        `armed` names the timer that called; one that another has replaced
        does nothing."""
        now = time.monotonic()
        sealed = []
        with self._lock:
            if armed is not None and self._armed is not armed:
                return
            self._armed = None
            if self._closed:
                return
            for kind, due, take in (
                ("s", self._singles_due, self._take_singles),
                ("g", self._groups_due, self._take_groups),
            ):
                if not due(now):
                    continue
                if self._sealed >= self._inflight_max:
                    # Never wait on the loop: the entries stay queued and
                    # the collect thread's next completion arms the seal.
                    self.flushes["deferred"] += 1
                    SERVICE_EVENTS.labels("deferred").inc()
                    break
                self._sealed += 1
                self._seq += 1
                sealed.append((kind, self._seq, take()))
            else:
                # What stays queued is not due yet, or is more than a bucket:
                # its deadline, on this loop.
                if self._pending or self._pending_groups:
                    self._arm_deadline(asyncio.get_running_loop(), now)
        t_pack = sealed and tracing.ACCOUNTING and time.perf_counter()
        for kind, seq, entries in sealed:
            self._dispatch(kind, seq, entries, now)
        if t_pack:
            tracing.nested("verify:seal", t_pack)

    def _release(self) -> None:
        """A sealed flush was answered, or never left: free its slot, and
        if entries wait with no seal on its way, start one on the loop of
        the oldest (the first open loop, if that one closed)."""
        now = time.monotonic()
        with self._lock:
            self._sealed -= 1
            if self._closed or self._seal_is_armed():
                return
            oldest = self._oldest()
            if oldest is None:
                return
            queued = itertools.chain((oldest,), self._pending, self._pending_groups)
            for loop in (e[1] for e in queued):
                armed = (loop, now)
                try:
                    loop.call_soon_threadsafe(self._on_seal, armed)
                except RuntimeError:  # closed: nobody waits there anymore
                    continue
                self._armed = armed
                return

    def _dispatch(self, kind: str, seq: int, entries: list, t_seal: float) -> None:
        """On the sealing loop's thread: hand one sealed lane batch to the
        device and pass it, with what the flush record needs, to the
        collect thread."""
        lane = _LANES[kind]
        useful = len(entries) if kind == "s" else sum(2 * len(e[0][0]) for e in entries)
        waits = [t_seal - e[3] for e in entries]
        queue_wait = SERVICE_WAIT.labels("queue")
        for w in waits:
            queue_wait.observe(w)
        # The flush record, around the rows the dispatch was padded to.
        head, tail = (seq, lane, len(entries), useful), (entries[0][3], sum(waits), t_seal)
        submit = self.verifier.submit_groups if kind == "g" else self.verifier.submit
        try:
            with tracing.annotation("narwhal/verify_submit", seq=seq, lane=lane):
                handle = submit([e[0] for e in entries])
        except Exception as e:
            logger.exception("verify submit failed for %d %s entries", len(entries), lane)
            self.flushes["submit_failed"] += 1
            self._never_left(entries, (*head, 0, *tail), e, f"submit: {e!r}")
            return
        t_dispatched = time.monotonic()
        SERVICE_ROWS.labels(lane, "useful").inc(useful)
        SERVICE_ROWS.labels(lane, "padded").inc(handle.padded)
        self.flushes[lane] += 1
        record = (*head, handle.padded, *tail)
        with self._lock:
            if not self._closed:
                self._handoff.put((kind, handle, entries, (*record, t_dispatched)))
                return
        # shutdown() ran while this flush was packed: nobody collects it.
        self._never_left(entries, record, RuntimeError("verify service shut down"), "shut down")

    def _never_left(self, entries: list, record: tuple, exc, failure: str) -> None:
        """A sealed flush that reaches no collect: its waiters get `exc`,
        its record the failure, its slot goes back."""
        t_failed = time.monotonic()
        self._resolve_error(entries, exc)
        tracing.flight("flush", *record, t_failed, t_failed, failure[:160])
        self._release()

    def _collect_loop(self) -> None:
        while True:
            got = self._handoff.get()
            if got is None:
                return
            kind, handle, entries, meta = got
            collect = self.verifier.collect_groups if kind == "g" else self.verifier.collect
            failure = None
            try:
                with tracing.annotation("narwhal/verify_collect", seq=meta[0], lane=meta[1]):
                    results = collect(handle)
            except Exception as e:
                logger.exception("verify collect failed for %d entries", len(entries))
                self.flushes["collect_failed"] += 1
                failure = f"collect: {e!r}"[:160]
                t_posted = time.monotonic()
                self._resolve_error(entries, e)
            else:
                # t_posted: the verdicts start on their way to the waiters'
                # loops. Each waiter finds the flush's wake tally in its entry.
                t_posted = time.monotonic()
                tally = [meta[0], len(entries), t_posted, 0, 0.0, 0.0]
                for entry in entries:
                    entry[4].append(tally)
                self._post(entries, results, None)
            self._release()
            SERVICE_WAIT.labels("turnaround").observe(t_posted - meta[7])
            tracing.flight("flush", *meta, t_posted, failure)

    def _resolve_error(self, entries, exc) -> None:
        self._post(entries, [None] * len(entries), exc)

    @staticmethod
    def _post(entries, results, exc) -> None:
        """Resolve a flush's futures: one wake-up per loop, not per entry
        (each `call_soon_threadsafe` is a write to the loop's self-pipe and
        a chance to lose the interpreter between two verdicts)."""
        by_loop: dict = {}
        for (_, loop, fut, _, _), res in zip(entries, results):
            by_loop.setdefault(loop, []).append((fut, res))

        def deliver(pairs) -> None:
            tracing.charge("verify:deliver")
            for fut, res in pairs:
                if fut.done():
                    continue
                if exc is not None:
                    fut.set_exception(exc)
                else:
                    fut.set_result(res)

        for loop, pairs in by_loop.items():
            try:
                loop.call_soon_threadsafe(deliver, pairs)
            except RuntimeError:
                # The caller's loop closed (its cluster/test tore down before
                # the device answered); nobody is waiting anymore.
                pass

    async def close(self) -> None:
        """Per-node shutdown is a no-op for the process-wide instance: other
        nodes (and the next in-process cluster) keep using it; the collect
        thread is a daemon and idle when no traffic flows."""
        return None

    def shutdown(self) -> bool:
        """Really stop (tests; process teardown): fail what is queued, let
        the collect thread answer what is in flight, and join it. Returns
        whether it stopped inside the join window."""
        with self._lock:
            first = not self._closed
            self._closed = True
            leftovers = [*self._pending, *self._pending_groups]
            self._pending.clear()
            self._pending_groups.clear()
            if first:
                self._handoff.put(None)  # after every flush already handed over
        if leftovers:
            self._resolve_error(leftovers, RuntimeError("verify service shut down"))
        self._collect_thread.join(timeout=10.0)
        for key, svc in list(self._shared.items()):
            if svc is self:
                del self._shared[key]
        return not self._collect_thread.is_alive()


class AsyncVerifierPool:
    """Size-or-deadline coalescing of concurrent verification requests.

    await pool.verify(pk, msg, sig) from any task; items are flushed to the
    backend in one batch when `max_batch` are waiting or `max_delay` elapsed
    since the first queued item (BatchMaker's seal rule, applied to crypto).
    The backend call runs in a thread so the event loop never blocks on the
    device.
    """

    def __init__(
        self,
        backend=None,
        max_batch: int = 512,
        max_delay: float = 0.002,
        group_backend=None,
        max_groups: int = 64,
    ):
        from .. import crypto
        from ..types import host_batch_verify_aggregates

        self.backend = backend or crypto.batch_verify
        self.max_batch = max_batch
        self.max_delay = max_delay
        self._pending: list[tuple[BatchItem, asyncio.Future]] = []
        self._flusher: asyncio.Task | None = None
        self._batches: set[asyncio.Task] = set()  # strong refs: loop holds weak
        # Aggregate-certificate group lane (compact certs): concurrent
        # verify_aggregate calls coalesce under the same seal rule and
        # dispatch as ONE host_batch_verify_aggregates call — one
        # bucket-method MSM amortized across every certificate in the
        # flush, the host analog of VerifyService's device group lane.
        self.group_backend = group_backend or host_batch_verify_aggregates
        self.max_groups = max_groups
        self._pending_groups: list[tuple[tuple, asyncio.Future]] = []
        self._group_flusher: asyncio.Task | None = None

    async def verify(self, public_key: bytes, message: bytes, signature: bytes) -> bool:
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending.append(((public_key, message, signature), fut))
        if len(self._pending) >= self.max_batch:
            self._flush_now()
        elif self._flusher is None or self._flusher.done():
            self._flusher = asyncio.ensure_future(self._deadline_flush())
        return await fut

    def _flush_now(self) -> None:
        pending, self._pending = self._pending, []
        if pending:
            task = asyncio.ensure_future(self._run_batch(pending))
            self._batches.add(task)
            task.add_done_callback(self._batches.discard)

    async def _deadline_flush(self) -> None:
        await asyncio.sleep(self.max_delay)
        self._flush_now()

    async def _run_batch(self, pending) -> None:
        items = [item for item, _ in pending]
        loop = asyncio.get_running_loop()
        try:
            results = await loop.run_in_executor(None, self.backend, items)
        except Exception as e:
            for _, fut in pending:
                if not fut.done():
                    fut.set_exception(e)
            return
        for (_, fut), res in zip(pending, results):
            if not fut.done():
                fut.set_result(res)

    async def verify_aggregate(self, items, zs, s_agg: int) -> bool:
        """Half-aggregated certificate proof check (compact certs), batched:
        groups queued by concurrent callers — the verifier stage's
        per-message tasks, the block synchronizer's catch-up fetches —
        seal into one `host_batch_verify_aggregates` dispatch (size- or
        deadline-triggered, like the item lane), so many certificates
        share one randomized-linear-combination MSM instead of paying a
        per-certificate scalar-mul walk."""
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self._pending_groups.append(((items, zs, s_agg), fut))
        if len(self._pending_groups) >= self.max_groups:
            self._flush_groups_now()
        elif self._group_flusher is None or self._group_flusher.done():
            self._group_flusher = asyncio.ensure_future(self._deadline_flush_groups())
        return await fut

    def _flush_groups_now(self) -> None:
        pending, self._pending_groups = self._pending_groups, []
        if pending:
            task = asyncio.ensure_future(self._run_group_batch(pending))
            self._batches.add(task)
            task.add_done_callback(self._batches.discard)

    async def _deadline_flush_groups(self) -> None:
        await asyncio.sleep(self.max_delay)
        self._flush_groups_now()

    async def _run_group_batch(self, pending) -> None:
        groups = [group for group, _ in pending]
        loop = asyncio.get_running_loop()
        try:
            results = await loop.run_in_executor(None, self.group_backend, groups)
        except Exception as e:
            for _, fut in pending:
                if not fut.done():
                    fut.set_exception(e)
            return
        for (_, fut), res in zip(pending, results):
            if not fut.done():
                fut.set_result(res)

    async def close(self) -> None:
        if self._flusher is not None:
            self._flusher.cancel()
            self._flusher = None
        if self._group_flusher is not None:
            self._group_flusher.cancel()
            self._group_flusher = None
        self._flush_now()
        self._flush_groups_now()
        # In-flight batch dispatches resolve their callers' futures; give
        # them a bounded window to finish, then cancel stragglers so no
        # batch task survives its owner (a wedged executor thread must not
        # hang node shutdown or leak tasks into the next test).
        if self._batches:
            _, stuck = await asyncio.wait(set(self._batches), timeout=5.0)
            for t in stuck:
                t.cancel()
