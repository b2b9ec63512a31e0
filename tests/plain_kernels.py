"""Plain-integer stand-ins for the two verify kernels, for tier-1 tests.

Tracing `msm_accumulate_kernel` or `verify_batch_kernel` takes minutes on
XLA:CPU, so tier-1 dispatches neither. These take the same operands and
give the same answers from `ed25519_ref` alone: a `TpuVerifier` whose
`_msm_kernel` / `_item_kernel` are replaced by them runs every line of its
host side — prechecks, fold, staging, counters, epilogue, detours — on real
signatures. They import nothing of the kernels but the layout's constants."""

from __future__ import annotations

import numpy as np

from narwhal_tpu.tpu import ed25519 as k
from narwhal_tpu.tpu import ed25519_ref as ref


class HostArray(np.ndarray):
    """A result that is on the host already."""

    def copy_to_host_async(self) -> None:
        pass


def _multiples(p):
    out = [ref.IDENTITY]
    for _ in range(15):
        out.append(ref.point_add(out[-1], p))
    return out


def _limbs_of(points) -> np.ndarray:
    arr = np.zeros((4, k.NLIMB, len(points)), np.int32)
    for w, p in enumerate(points):
        for c in range(4):
            arr[c, :, w] = k.int_to_limbs(p[c] % ref.P)
    return arr


def msm_kernel(rows) -> HostArray:
    """`msm_accumulate_kernel` on Python integers: uint8[B, ROW_BYTES] ->
    the flat int32 result. Keeps every bucket it was handed in
    `msm_kernel.seen` (copies: the caller's buffer is written again)."""
    rows = np.array(rows, np.uint8)
    assert rows.ndim == 2 and rows.shape[1] == k.ROW_BYTES
    msm_kernel.seen.append(rows)
    va, vr, valid = [ref.IDENTITY] * 64, [ref.IDENTITY] * 32, True
    for row in rows:
        raw = row.tobytes()
        a, r = ref.decompress(raw[0:32]), ref.decompress(raw[32:64])
        if a is None or r is None:
            valid = False
            continue
        ak = int.from_bytes(raw[64:96], "little")
        z = int.from_bytes(raw[96:112], "little")
        if ak:
            table = _multiples(ref.point_neg(a))
            va = [ref.point_add(v, table[(ak >> 4 * (63 - w)) & 15]) for w, v in enumerate(va)]
        if z:
            table = _multiples(ref.point_neg(r))
            vr = [ref.point_add(v, table[(z >> 4 * (31 - w)) & 15]) for w, v in enumerate(vr)]
    flat = np.concatenate([_limbs_of(va).reshape(-1), _limbs_of(vr).reshape(-1), [int(valid)]])
    return flat.astype(np.int32).view(HostArray)


msm_kernel.seen = []


def _point_bytes(y_limbs, sign) -> bytes:
    y = sum(int(v) << (k.RADIX * i) for i, v in enumerate(y_limbs))
    return (y | (int(sign) << 255)).to_bytes(32, "little")


def _scalar(digits) -> int:
    out = 0
    for d in digits:  # MSB first
        out = out * 16 + int(d)
    return out


def item_kernel(a_y, a_sign, r_y, r_sign, k_digits, s_digits):
    """`verify_batch_kernel` on Python integers: the six batch-leading
    operands -> (strict bool[B], cofactored bool[B])."""
    strict, cof = [], []
    for i in range(len(a_sign)):
        r_bytes = _point_bytes(r_y[i], r_sign[i])
        a, r = ref.decompress(_point_bytes(a_y[i], a_sign[i])), ref.decompress(r_bytes)
        if a is None:
            strict.append(False)
            cof.append(False)
            continue
        check = ref.point_add(
            ref.point_mul(_scalar(s_digits[i]), ref.G),
            ref.point_mul(_scalar(k_digits[i]), ref.point_neg(a)),
        )
        strict.append(ref.compress(check) == r_bytes)
        if r is None:
            cof.append(False)
            continue
        diff = ref.point_add(check, ref.point_neg(r))
        for _ in range(3):
            diff = ref.point_double(diff)
        cof.append(ref.point_equal(diff, ref.IDENTITY))
    return np.array(strict).view(HostArray), np.array(cof).view(HostArray)
