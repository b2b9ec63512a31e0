"""The host epilogue of an msm dispatch, `msm_epilogue_check`: its native
walk (native/scalar_ops.cpp `msm_epilogue_native`) against the same check on
Python integers, case by case, and the field and point arithmetic under the
native one against ed25519_ref.

Nothing here dispatches a kernel: what `msm_accumulate_kernel` would leave
for a bucket — per-window sums of the negated A_i and R_i, as loose
radix-2^13 limbs — is computed by plain integers from the signatures."""

from __future__ import annotations

import ctypes
import random

import numpy as np
import pytest

from narwhal_tpu.crypto import KeyPair
from narwhal_tpu.tpu import ed25519 as k
from narwhal_tpu.tpu import ed25519_ref as ref
from narwhal_tpu.tpu import verifier as verifier_mod
from narwhal_tpu.tpu.verifier import TpuVerifier, msm_epilogue_check


@pytest.fixture(scope="module")
def lib():
    lib = verifier_mod._scalar_lib()
    if lib is None:
        pytest.skip("native toolchain unavailable")
    return lib


def device_sums(items, zs):
    """(V_a points[64], V_r points[32], sum_s) for signatures `items` under
    the random weights `zs`: V_a[w] = sum_i [digit_w(z_i k_i)](-A_i), V_r[w]
    = sum_i [digit_w(z_i)](-R_i), MSB-first 4-bit windows."""
    va, vr, sum_s = [ref.IDENTITY] * 64, [ref.IDENTITY] * 32, 0
    for (pk, msg, sig), z in zip(items, zs):
        neg_a = ref.point_neg(ref.decompress(pk))
        neg_r = ref.point_neg(ref.decompress(sig[:32]))
        ak = z * ref.sha512_mod_l(sig[:32], pk, msg) % ref.L
        for w in range(64):
            va[w] = ref.point_add(va[w], ref.point_mul((ak >> 4 * (63 - w)) & 15, neg_a))
        for w in range(32):
            vr[w] = ref.point_add(vr[w], ref.point_mul((z >> 4 * (31 - w)) & 15, neg_r))
        sum_s += z * int.from_bytes(sig[32:], "little")
    return va, vr, sum_s % ref.L


def loosen(limbs, rng, widest: bool):
    """The same value mod p on other limbs: weight moved between neighbours
    (limb i + 8192 c, limb i+1 - c) and multiples of p = 2^255 - 19 added
    (limb 19 + 256 c, limb 0 - 19 c), either sign; `widest` goes to the
    edge of int32."""
    out = [int(v) for v in limbs]
    span = 1 << 17 if widest else 3
    for i in range(19):
        c = rng.randrange(-span, span)
        out[i] += 8192 * c
        out[i + 1] -= c
    c = rng.randrange(-(1 << 22), 1 << 22) if widest else rng.randrange(-3, 3)
    out[19] += 256 * c
    out[0] -= 19 * c
    if widest:  # limb 7 to within 8192 of the top of int32, limb 11 of the bottom
        c = (2**31 - 1 - out[7]) // 8192
        out[7], out[8] = out[7] + 8192 * c, out[8] - c
        c = (out[11] + 2**31) // 8192
        out[11], out[12] = out[11] - 8192 * c, out[12] + c
    assert all(-(2**31) <= v < 2**31 for v in out)
    return np.array(out, np.int32)


def as_limbs(points, rng=None, widest=False):
    arr = np.zeros((4, k.NLIMB, len(points)), np.int32)
    for w, p in enumerate(points):
        for c in range(4):
            limbs = k.int_to_limbs(p[c] % ref.P)
            arr[c, :, w] = loosen(limbs, rng, widest) if rng is not None else limbs
    return arr


def signed(n, tag):
    kps = [KeyPair.generate() for _ in range(n)]
    return [(kp.public, b"%d:%d" % (tag, i), kp.sign(b"%d:%d" % (tag, i))) for i, kp in enumerate(kps)]


def torsion_defect_signature():
    """A signature that only the cofactored rule accepts (its residual is a
    point of small order); tests/test_tpu_ed25519.py builds the same."""
    rng = random.Random(11)
    while True:
        y = rng.randrange(ref.P)
        x = ref.recover_x(y, 0)
        if x is None:
            continue
        t = ref.point_mul(ref.L, (x, y, 1, x * y % ref.P))
        if t[0] % ref.P != 0 or (t[1] - t[2]) % ref.P != 0:
            break
    while True:
        a, r = rng.randrange(ref.L), rng.randrange(ref.L)
        pk = ref.compress(ref.point_add(ref.point_mul(a, ref.G), t))
        r_bytes = ref.compress(ref.point_mul(r, ref.G))
        kk = ref.sha512_mod_l(r_bytes, pk, b"torsion probe")
        if kk % 2 == 1:  # odd: [k]T is no identity whatever T's order
            break
    sig = r_bytes + ((r + kk * a) % ref.L).to_bytes(32, "little")
    assert not ref.verify(pk, b"torsion probe", sig)
    return pk, b"torsion probe", sig


def bucket(case: str):
    """(va, vr, sum_s, verdict the rule gives) for one named case."""
    rng = random.Random(case)
    items = signed(4, tag=len(case))
    loose = None
    if case == "altered_s":
        pk, msg, sig = items[2]
        s = (int.from_bytes(sig[32:], "little") + 1) % ref.L
        items[2] = (pk, msg, sig[:32] + s.to_bytes(32, "little"))
    elif case == "altered_r":
        pk, msg, sig = items[1]
        r = ref.compress(ref.point_add(ref.decompress(sig[:32]), ref.G))
        items[1] = (pk, msg, r + sig[32:])
    elif case == "torsion_defect":
        items[3] = torsion_defect_signature()
    elif case.startswith("loose"):
        loose = rng
        if case.endswith("altered_s"):
            pk, msg, sig = items[0]
            items[0] = (pk, msg, sig[:32] + ((int.from_bytes(sig[32:], "little") + 8) % ref.L).to_bytes(32, "little"))
    zs = [rng.randrange(1, 1 << 128) for _ in items]
    va, vr, sum_s = device_sums(items, zs)
    widest = "widest" in case
    return as_limbs(va, loose, widest), as_limbs(vr, loose, widest), sum_s, not case.endswith(("altered_s", "altered_r"))


CASES = ["valid", "altered_s", "altered_r", "torsion_defect", "loose_signed", "loose_widest", "loose_widest_altered_s"]


@pytest.mark.parametrize("case", CASES)
def test_native_epilogue_agrees_with_the_python_twin(lib, case, monkeypatch):
    va, vr, sum_s, accepted = bucket(case)
    if case.startswith("loose"):
        assert va.min() < 0 and (case == "loose_signed" or (va.max() >= 2**31 - 8192 and va.min() < -(2**31) + 8192))
    assert msm_epilogue_check(va, vr, sum_s, k) is accepted
    assert msm_epilogue_check(va, vr, sum_s, k, lib) is accepted
    # Through the verifier, as the device hands a bucket back: one flat
    # array of both window sums and the all-rows-valid flag. The native
    # twin runs and is counted; with no library, the Python one, with the
    # same verdict. A row the device marked invalid fails the bucket
    # before either. Every call is one readback.
    def result(valid: bool) -> np.ndarray:
        return np.concatenate([va.reshape(-1), vr.reshape(-1), [int(valid)]]).astype(np.int32)

    assert result(True).shape == (k.MSM_RESULT_SIZE,)
    v = TpuVerifier(max_bucket=16)
    assert v._batch_passes(result(True), sum_s) is accepted
    assert (v.counts["epilogue_native"], v.counts["epilogue_python"]) == (1, 0)
    monkeypatch.setattr(verifier_mod, "_scalar_lib", lambda: None)
    assert v._batch_passes(result(True), sum_s) is accepted
    assert (v.counts["epilogue_native"], v.counts["epilogue_python"]) == (1, 1)
    assert v._batch_passes(result(False), sum_s) is False
    assert (v.counts["epilogue_native"], v.counts["epilogue_python"]) == (1, 1)
    assert (v.counts["readback"], v.counts["readback_bytes"]) == (3, 3 * 4 * k.MSM_RESULT_SIZE)
    assert v.counts["upload"] == 0


def test_window_counts_the_native_twin_takes(lib):
    va, vr, sum_s, _ = bucket("valid")
    assert lib.msm_epilogue_native(va.ctypes.data, vr.ctypes.data, 0, sum_s.to_bytes(32, "little")) == -1
    assert lib.msm_epilogue_native(va.ctypes.data, vr.ctypes.data, 65, sum_s.to_bytes(32, "little")) == -1
    # V_r as wide as V_a (zero windows in front) is the same sum.
    wide = np.ascontiguousarray(np.concatenate([as_limbs([ref.IDENTITY] * 32), vr], axis=2))
    assert msm_epilogue_check(va, wide, sum_s, k, lib) is True is msm_epilogue_check(va, wide, sum_s, k)
    # Not the kernels' 64 windows: said, not answered.
    with pytest.raises(ValueError, match="window sums"):
        msm_epilogue_check(np.ascontiguousarray(va[:, :, :32]), vr, sum_s, k, lib)
    # A view (the device hands back what it likes) is made contiguous first.
    assert msm_epilogue_check(np.asfortranarray(va), vr[:, :, ::1], sum_s, k, lib) is True


def _fe(lib, op, a, b):
    out = ctypes.create_string_buffer(32)
    lib.fe_test(op, a.to_bytes(32, "little"), b.to_bytes(32, "little"), out)
    return int.from_bytes(out.raw, "little")


def _pt(lib, op, p, q):
    out = ctypes.create_string_buffer(128)
    lib.pt_test(op, *(b"".join((c % ref.P).to_bytes(32, "little") for c in x) for x in (p, q)), out)
    return tuple(int.from_bytes(out.raw[32 * i : 32 * i + 32], "little") for i in range(4))


def test_native_field_ops_match_bigint(lib):
    rng = random.Random(5)
    edge = [0, 1, 2, 19, ref.P - 1, ref.P - 2, ref.P - 19, (1 << 255) - 20, 1 << 254, (1 << 51) - 1, 1 << 51]
    pairs = [(a, b) for a in edge for b in edge] + [(rng.randrange(ref.P), rng.randrange(ref.P)) for _ in range(500)]
    for a, b in pairs:
        assert _fe(lib, 0, a, b) == (a + b) % ref.P
        assert _fe(lib, 1, a, b) == (a - b) % ref.P
        assert _fe(lib, 2, a, b) == a * b % ref.P
    # Bytes at or over p (bit 255 too) are taken mod p.
    assert _fe(lib, 0, ref.P, 0) == 0 and _fe(lib, 2, (1 << 256) - 1, 1) == ((1 << 256) - 1) % ref.P


def test_native_point_ops_match_reference_coordinate_for_coordinate(lib):
    rng = random.Random(6)
    for _ in range(40):
        p = ref.point_mul(rng.randrange(ref.L), ref.G)
        q = ref.point_mul(rng.randrange(ref.L), ref.G)
        z = rng.randrange(1, ref.P)  # a projective form with Z != 1
        p = tuple(c * z % ref.P for c in p)
        assert _pt(lib, 0, p, q) == tuple(c % ref.P for c in ref.point_add(p, q))
        assert _pt(lib, 1, p, q) == tuple(c % ref.P for c in ref.point_double(p))
    assert _pt(lib, 0, ref.IDENTITY, ref.G) == tuple(c % ref.P for c in ref.point_add(ref.IDENTITY, ref.G))
    for j in range(16):
        assert ref.point_equal(_pt(lib, 2, ref.G, (j, 0, 0, 0)), ref.point_mul(j, ref.G))


def test_native_loose_limbs_reduce_like_limbs_to_int(lib):
    rng = random.Random(8)
    rows = [np.full(20, 2**31 - 1, np.int32), np.full(20, -(2**31), np.int32), np.zeros(20, np.int32),
            np.array([-1] + [0] * 19, np.int32), k.int_to_limbs(ref.P - 1)]
    rows += [np.array([rng.randrange(-(2**31), 2**31) for _ in range(20)], np.int32) for _ in range(300)]
    rows += [np.array([rng.randrange(0, 9500) for _ in range(20)], np.int32) for _ in range(100)]  # the kernel's loose bound
    for limbs in rows:
        out = ctypes.create_string_buffer(32)
        lib.fe_loose13_test(np.ascontiguousarray(limbs).ctypes.data, out)
        assert int.from_bytes(out.raw, "little") == k.limbs_to_int(limbs) % ref.P
