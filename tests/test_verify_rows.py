"""The host<->device interface of a verify flush: one buffer of raw rows up,
one array down. The expansion the kernel does on the device (limbs, sign
bits, digits) against the host's packing helpers, bit for bit; and the
verifier's host side — staging buffers, transfer counters, the detours of a
forged row — on real signatures, with the two kernels replaced by their
plain-integer stand-ins (tests/plain_kernels.py), since tier-1 dispatches
neither."""

from __future__ import annotations

import numpy as np
import pytest

from narwhal_tpu.crypto import KeyPair
from narwhal_tpu.tpu import ed25519 as k
from narwhal_tpu.tpu import verifier as verifier_mod
from narwhal_tpu.tpu.verifier import SERVICE_BYTES, SERVICE_TRANSFERS, TpuVerifier
from tests import plain_kernels

BUCKET = 32
RESULT_BYTES = 4 * k.MSM_RESULT_SIZE


def edge_rows(case: str) -> np.ndarray:
    rows = np.random.default_rng(len(case)).integers(0, 256, (16, k.ROW_BYTES), dtype=np.uint8)
    if case == "zero":
        rows[:] = 0
    elif case == "all_ff":
        rows[:] = 0xFF
    elif case == "sign_bits":  # A's sign set and R's clear, then the other way round
        rows[0::2, 31] |= 0x80
        rows[0::2, 63] &= 0x7F
        rows[1::2, 31] &= 0x7F
        rows[1::2, 63] |= 0x80
    elif case == "z_top_nibble":  # the first digit of z's 32, and of ak's 64
        rows[:, 111] |= 0xF0
        rows[:, 95] |= 0xF0
    elif case == "padded":  # a few useful rows over inert padding, as a flush is
        rows[3:] = 0
    return rows


def host_expansion(rows: np.ndarray) -> list[np.ndarray]:
    """What `submit` sent before the kernel took raw rows: the oracle."""
    a, r, ak = rows[:, 0:32], rows[:, 32:64], rows[:, 64:96]
    z = np.zeros((rows.shape[0], 32), np.uint8)
    z[:, :16] = rows[:, 96:112]
    return [
        k.bytes_to_limbs(a), a[:, 31] >> 7, k.bytes_to_limbs(r), r[:, 31] >> 7,
        k.bytes_to_digits(ak), k.bytes_to_digits(z)[:, 32:],
    ]


def mesh_expand(rows):
    """`expand_rows` as the mesh path jits it: on the data axis of four
    (forced CPU) devices."""
    from jax.sharding import PartitionSpec as P

    from narwhal_tpu.tpu import kernel_registry

    b, bn = P("data"), P("data", None)
    return kernel_registry.sharded(
        k.expand_rows, verifier_mod.data_mesh(4), in_specs=(bn,), out_specs=(bn, b, bn, b, bn, bn)
    )(rows)


@pytest.mark.parametrize("where", ["one_device", "mesh"])
@pytest.mark.parametrize("case", ["random", "zero", "all_ff", "sign_bits", "z_top_nibble", "padded"])
def test_the_kernels_expansion_is_the_hosts_bit_for_bit(case, where):
    import jax

    rows = edge_rows(case)
    got = (jax.jit(k.expand_rows) if where == "one_device" else mesh_expand)(rows)
    want = host_expansion(rows)
    assert len(got) == len(want) == 6
    for name, g, w in zip(("a_y", "a_sign", "r_y", "r_sign", "ak_digits", "z_digits"), got, want):
        g = np.asarray(g)
        assert g.dtype == np.int32 and g.shape == w.shape, name
        assert np.array_equal(g, w), name
    if case == "sign_bits":
        assert want[1].tolist() == [1, 0] * 8 and want[3].tolist() == [0, 1] * 8


def test_the_result_array_splits_into_views():
    flat = np.arange(k.MSM_RESULT_SIZE, dtype=np.int32)
    va, vr, valid = k.split_msm_result(flat)
    assert va.shape == (4, k.NLIMB, 64) and vr.shape == (4, k.NLIMB, 32) and valid is True
    assert va.base is not None and vr.base is not None  # views, not copies
    assert va.flags.c_contiguous and vr.flags.c_contiguous
    assert va[0, 0, 1] == 1 and vr[0, 0, 0] == 4 * k.NLIMB * 64
    flat[-1] = 0
    assert k.split_msm_result(flat)[2] is False


# -- the verifier's host side, on the plain kernels ----------------------------


def plain_verifier(**kw) -> TpuVerifier:
    v = TpuVerifier(max_bucket=BUCKET, msm_min_bucket=16, mode="msm", fixed_bucket=True, **kw)
    v._msm_kernel = plain_kernels.msm_kernel
    v._item_kernel = plain_kernels.item_kernel
    plain_kernels.msm_kernel.seen.clear()
    return v


def signatures(n: int, tag: int = 0) -> list:
    kps = [KeyPair.generate() for _ in range(min(n, 5))]
    out = []
    for i in range(n):
        msg = b"rows:%d:%d" % (tag, i)
        out.append((kps[i % len(kps)].public, msg, kps[i % len(kps)].sign(msg)))
    return out


def certificate_groups(n: int, first_round: int = 1, tamper: int | None = None) -> list:
    """`n` half-aggregated certificate proofs of a committee of four (eight
    kernel rows each); `tamper` names one whose aggregate is altered."""
    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.types import Certificate, Vote

    fx = CommitteeFixture(size=4)
    groups = []
    for g in range(n):
        h = fx.header(author=0, round=first_round + g)
        votes = [Vote.for_header(h, a.public, a.keypair) for a in fx.authorities]
        cc = Certificate.compact_from_votes(
            h, tuple(fx.committee.index_of(a.public) for a in fx.authorities), tuple(v.signature for v in votes)
        )
        if g == tamper:
            cc = Certificate(cc.header, cc.signers, cc.signatures, bytes([cc.agg_s[0] ^ 1]) + cc.agg_s[1:])
        groups.append(cc.aggregate_group(fx.committee))
    return groups


LANES = {
    # lane -> (what fills 30-32 rows, what fills 3-8, submit, collect)
    "singles": (lambda: signatures(30, 1), lambda: signatures(3, 2), "submit", "collect"),
    "groups": (lambda: certificate_groups(4), lambda: certificate_groups(1, 9), "submit_groups", "collect_groups"),
}


def useful_rows(lane: str, work: list) -> int:
    return len(work) if lane == "singles" else sum(2 * len(g[0]) for g in work)


@pytest.mark.parametrize("lane", sorted(LANES))
def test_a_staging_buffer_used_again_carries_no_row_of_its_last_flush(lane):
    """30 rows, then 3 in the same buffer: a row left over from the first
    flush would add its point to the second's sums and fail its check."""
    many, few, submit, collect = LANES[lane]
    many, few = many(), few()
    v = plain_verifier()
    first = getattr(v, submit)(many)
    staged = first.outs[0][-1].staged
    assert staged.shape == (BUCKET, k.ROW_BYTES) and first.padded == BUCKET
    assert getattr(v, collect)(first) == [True] * len(many)
    assert [buf is staged for buf, _ in v._staging[BUCKET]] == [True]  # handed back at collect

    second = getattr(v, submit)(few)
    assert second.outs[0][-1].staged is staged  # the same memory, used again
    assert v._staging[BUCKET] == []  # and nobody else's until its collect
    assert getattr(v, collect)(second) == [True] * len(few)
    reused = plain_kernels.msm_kernel.seen[-1]  # what the second kernel call was handed

    fresh = plain_verifier()
    assert getattr(fresh, collect)(getattr(fresh, submit)(few)) == [True] * len(few)
    clean = plain_kernels.msm_kernel.seen[-1]
    n = useful_rows(lane, few)
    assert n < useful_rows(lane, many)
    assert not reused[n:].any() and not clean[n:].any()
    # The useful rows but for the fresh random weights: A and R (on the
    # group lane the R and z columns are zero).
    assert np.array_equal(reused[:n, :64], clean[:n, :64])
    if lane == "groups":
        assert not reused[:, 32:64].any() and not reused[:, 96:].any()
    assert sum(v.counts[d] for d in ("msm_redispatch", "group_solo_redispatch", "group_host_verify")) == 0


def test_a_buffer_in_flight_is_not_handed_out_again():
    """Two flushes submitted before either is collected write two buffers."""
    v = plain_verifier()
    one, two = v.submit(signatures(5, 3)), v.submit(signatures(2, 4))
    assert one.outs[0][-1].staged is not two.outs[0][-1].staged
    assert v.collect(two) == [True] * 2 and v.collect(one) == [True] * 5
    assert len(v._staging[BUCKET]) == 2
    # The pool keeps a few, not all a burst ever needed.
    burst = [v.submit(signatures(1, 5)) for _ in range(verifier_mod._STAGING_KEPT + 3)]
    for handle in burst:
        assert v.collect(handle) == [True]
    assert len(v._staging[BUCKET]) == verifier_mod._STAGING_KEPT


@pytest.mark.parametrize("lane", sorted(LANES))
def test_a_flush_is_one_array_up_and_one_down(lane):
    _, few, submit, collect = LANES[lane]
    v = plain_verifier()
    up, down = SERVICE_BYTES.labels("up").value, SERVICE_BYTES.labels("down").value
    ups, downs = SERVICE_TRANSFERS.labels("up").value, SERVICE_TRANSFERS.labels("down").value
    for flush in (1, 2, 3):
        handle = getattr(v, submit)(few())
        assert (v.counts["upload"], v.counts["readback"]) == (flush, flush - 1)
        assert all(getattr(v, collect)(handle))
        assert (v.counts["upload"], v.counts["readback"]) == (flush, flush)
        assert v.counts["upload_bytes"] == flush * BUCKET * k.ROW_BYTES
        assert v.counts["readback_bytes"] == flush * RESULT_BYTES
    assert SERVICE_BYTES.labels("up").value == up + 3 * BUCKET * k.ROW_BYTES
    assert SERVICE_BYTES.labels("down").value == down + 3 * RESULT_BYTES
    assert SERVICE_TRANSFERS.labels("up").value == ups + 3
    assert SERVICE_TRANSFERS.labels("down").value == downs + 3
    assert len(plain_kernels.msm_kernel.seen) == 3 and v.counts["item_dispatch"] == 0


def test_a_forged_row_fails_its_bucket_and_the_detour_names_it():
    """The detour derives limbs and digits from the raw rows the handle
    holds: the plain per-item kernel gets them and must find the one row."""
    items = signatures(7, 6)
    pk, msg, sig = items[4]
    s = (int.from_bytes(sig[32:], "little") + 1) % k.ref.L
    items[4] = (pk, msg, sig[:32] + s.to_bytes(32, "little"))
    items.insert(2, (b"short", b"m", b"sig"))  # never reaches the device
    v = plain_verifier()
    assert v(items) == [True, True, False, True, True, False, True, True]
    assert (v.counts["msm_dispatch"], v.counts["msm_redispatch"], v.counts["item_dispatch"]) == (1, 1, 1)
    assert v.counts["epilogue_native"] + v.counts["epilogue_python"] == 1
    # And the buffer of the failed bucket went back clean enough to serve.
    assert v(signatures(2, 7)) == [True, True]
    assert v.counts["msm_redispatch"] == 1


def test_a_forged_certificate_proof_is_bisected_as_before():
    groups = certificate_groups(3, tamper=1)
    v = plain_verifier()
    assert v.collect_groups(v.submit_groups(groups)) == [True, False, True]
    assert (v.counts["group_dispatch"], v.counts["group_solo_redispatch"], v.counts["group_host_verify"]) == (4, 1, 1)
    assert (v.counts["upload"], v.counts["readback"]) == (4, 4)
    assert v.collect_groups(v.submit_groups(certificate_groups(2, 5))) == [True, True]


def test_the_staging_pool_under_more_threads_than_cores():
    """The sealing loops take buffers and the collect thread hands them
    back: a buffer two threads held at once, or one handed out with a row
    of its last use, shows in what each thread reads back from its own."""
    import os
    import sys
    import threading
    import time

    v = plain_verifier()
    faults: list = []
    deadline = time.monotonic() + 20.0

    def worker(tid: int) -> None:
        rng = np.random.default_rng(tid)
        for _ in range(300):
            if time.monotonic() > deadline:
                faults.append("ran out of time")
                return
            rows = int(rng.integers(1, BUCKET + 1))
            buf = v._stage(BUCKET, rows)
            if buf[rows:].any():
                faults.append(f"thread {tid}: a stale row beyond {rows}")
            buf[:rows] = tid
            time.sleep(0)
            if not (buf[:rows] == tid).all():
                faults.append(f"thread {tid}: its buffer was written by another")
            v._unstage(verifier_mod.MsmDispatch(None, 0, buf, rows))

    threads = [threading.Thread(target=worker, args=(t + 1,)) for t in range(2 * (os.cpu_count() or 4))]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert faults == []
    assert 1 <= len(v._staging[BUCKET]) <= verifier_mod._STAGING_KEPT
