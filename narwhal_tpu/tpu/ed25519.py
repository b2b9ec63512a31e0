"""Batched ed25519 verification on TPU: the north-star crypto kernel.

Replaces per-message host verification (the reference's ed25519-dalek calls
behind fastcrypto's `VerifyingKey`, /root/reference/crypto/src/lib.rs:29-46;
hot at `Certificate::verify`, /root/reference/types/src/primary.rs:487-537)
with one device dispatch per batch of signatures.

TPU-first design notes (see /opt/skills/guides/pallas_guide.md, SURVEY §7.8a):

- **Limb-major layout**: a field element batch is int32[NLIMB, B] — the
  batch axis fills the VPU's 128-wide lanes; limbs live on the sublane axis
  so carry shifts are row moves, not lane shuffles. (The transposed [B, 20]
  layout leaves 6/7 of every vector register empty.)
- **Field arithmetic mod p = 2^255-19 in radix 2^13**: 20 limbs. Products of
  13-bit limbs are 26-bit; a 20-term column sum stays under 2^31, so the
  whole multiplier runs in native int32 lanes — no 64-bit emulation.
- **Parallel carries**: overflow moves one limb up per vector round; fixed
  round counts with statically-proven bounds (below) restore the invariant.
- **Shared-doubling Straus**: Rcheck = [S]B + [k](-A) in one run of 252
  doublings + 2x64 windowed table additions under `lax.scan`; the B table is
  a host constant, the -A table is built on device. The extended-Edwards
  addition law is complete here, so identity entries need no branches.
- Verification matches the host library (cofactorless):
  encode([S]B - [k]A) == R, with canonicality prechecks on host.

Bound bookkeeping (all < 2^31):
  loose invariant: limbs in [0, LOOSE = 9500]
  mul columns: 20 * 9500^2 = 1.805e9; fold adds <= 1.94e9; 4 rounds -> ~8800
  add: <= 19000, 2 rounds -> <= 9409
  sub: a + 64p - b with 64p = [15168, 16382 x19] (every limb >= 15168 keeps
       differences positive), 3 rounds -> <= ~8801

The host wrapper lives in narwhal_tpu/tpu/verifier.py.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import enable_compilation_cache
from . import ed25519_ref as ref
from .kernel_registry import tracked_jit

enable_compilation_cache()

NLIMB = 20
RADIX = 13
MASK = (1 << RADIX) - 1
WINDOWS = 64  # 4-bit windows over 256-bit scalars, MSB first
LOOSE = 9500


def int_to_limbs(x: int) -> np.ndarray:
    return np.array([(x >> (RADIX * i)) & MASK for i in range(NLIMB)], np.int32)


def limbs_to_int(limbs) -> int:
    arr = np.asarray(limbs)
    if arr.ndim > 1:
        arr = arr[..., 0] if arr.shape[-1] == 1 else arr.squeeze()
    return sum(int(v) << (RADIX * i) for i, v in enumerate(arr))


def _col(x: int) -> np.ndarray:
    """Constant as a broadcastable [NLIMB, 1] column."""
    return int_to_limbs(x)[:, None]


_P_LIMBS = int_to_limbs(ref.P)
_D = _col(ref.D)
_2D = _col(2 * ref.D % ref.P)
_SQRT_M1 = _col(ref.SQRT_M1)
_ONE = _col(1)

# 64p = 2^261 - 1216 with every limb large: per-limb subtraction bias.
_SUB_BIAS = np.array([15168] + [16382] * (NLIMB - 1), np.int32)[:, None]
assert limbs_to_int(_SUB_BIAS) == 64 * ref.P

# Fixed-base window table: 16 small multiples of B in CACHED affine form
# (y+x, y−x, 2d·t mod p) — identity row is (1, 1, 0), Z == 1 implicitly, so
# each table add is the 7-mul pt_add_cached_z1.
_BT = np.zeros((16, 3, NLIMB), np.int32)
for _dd, (_x, _y, _t) in enumerate(ref.base_window_table()):
    _BT[_dd, 0] = int_to_limbs((_y + _x) % ref.P)
    _BT[_dd, 1] = int_to_limbs((_y - _x) % ref.P)
    _BT[_dd, 2] = int_to_limbs(2 * ref.D * _t % ref.P)


# ---------------------------------------------------------------------------
# Field ops: arrays are [NLIMB] or [NLIMB, B]; the limb axis is ALWAYS 0.
# ---------------------------------------------------------------------------


def _carry_round(r):
    """One parallel carry round; limb-19 overflow (2^260 == 608 mod p) wraps
    to limb 0 — a single rotated add, no scatter."""
    hi = r >> RADIX
    lo = r & MASK
    return lo + jnp.concatenate([608 * hi[-1:], hi[:-1]], axis=0)


def fe_add(a, b):
    return _carry_round(_carry_round(a + b))


def _bcast(const_col, like):
    """[NLIMB, 1] host constant, broadcast-ready against `like`'s shape
    (limb axis 0, any number of trailing batch axes)."""
    return jnp.asarray(const_col[:, 0]).reshape((NLIMB,) + (1,) * (like.ndim - 1))


def fe_sub(a, b):
    r = a + _bcast(_SUB_BIAS, b) - b
    return _carry_round(_carry_round(_carry_round(r)))


def fe_neg(a):
    return _carry_round(_carry_round(_bcast(_SUB_BIAS, a) - a))


def _fold_and_carry(cols: list):
    """39 school-book columns -> loose field element: fold the high half
    (2^260 == 608 mod p) by 13-bit split so nothing overflows int32, then 4
    parallel carry rounds (bounds in the module docstring)."""
    c_lo = jnp.stack(cols[:NLIMB], axis=0)
    zero = jnp.zeros_like(cols[0])
    c_hi = jnp.stack(cols[NLIMB:] + [zero], axis=0)
    d_lo = c_hi & MASK
    d_hi = c_hi >> RADIX
    up = jnp.concatenate([jnp.zeros_like(d_hi[:1]), d_hi[:-1]], axis=0)
    r = c_lo + 608 * d_lo + 608 * up
    for _ in range(4):
        r = _carry_round(r)
    return r


def fe_mul(a, b):
    # Row-wise school-book columns: c[k] = sum_{i+j=k} a_i * b_j. Each term
    # is one [B]-wide multiply-add — no dynamic slicing, pure VPU work.
    rows_a = [a[i] for i in range(NLIMB)]
    rows_b = [b[i] for i in range(NLIMB)]
    cols = []
    for k in range(2 * NLIMB - 1):
        lo = max(0, k - NLIMB + 1)
        hi = min(NLIMB - 1, k)
        s = rows_a[lo] * rows_b[k - lo]
        for i in range(lo + 1, hi + 1):
            s = s + rows_a[i] * rows_b[k - i]
        cols.append(s)
    return _fold_and_carry(cols)


def fe_sq(a):
    # Squaring: c[k] = 2 * sum_{i<j, i+j=k} a_i a_j (+ a_{k/2}^2) — the
    # doubled operand keeps products under 19000 * 9500 * 10 < 2^31.
    rows = [a[i] for i in range(NLIMB)]
    doubled = [r + r for r in rows]
    cols = []
    for k in range(2 * NLIMB - 1):
        lo = max(0, k - NLIMB + 1)
        hi = min(NLIMB - 1, k)
        terms = []
        i, j = lo, hi
        while i < j:
            terms.append(doubled[i] * rows[j])
            i += 1
            j -= 1
        if i == j:
            terms.append(rows[i] * rows[i])
        s = terms[0]
        for t in terms[1:]:
            s = s + t
        cols.append(s)
    return _fold_and_carry(cols)


def _carry_chain_exact(r):
    """Sequential full carry (canonicalization only — off the hot path)."""
    outs = []
    carry = jnp.zeros_like(r[0])
    for i in range(NLIMB):
        v = r[i] + carry
        outs.append(v & MASK)
        carry = v >> RADIX
    return jnp.stack(outs, axis=0), carry


def fe_canonical(a):
    """Full reduction to [0, p) from loose form."""
    for _ in range(2):
        a, overflow = _carry_chain_exact(a)
        top = a[NLIMB - 1]
        hi = (top >> 8) + (overflow << (RADIX - 8))
        a = a.at[NLIMB - 1].set(top & 0xFF)
        a = a.at[0].add(19 * hi)
    a, _ = _carry_chain_exact(a)
    for _ in range(2):  # value < 2^255 + eps: conditionally subtract p
        borrow = jnp.zeros_like(a[0])
        outs = []
        for i in range(NLIMB):
            v = a[i] - int(_P_LIMBS[i]) - borrow
            borrow = (v < 0).astype(jnp.int32)
            outs.append(v + (borrow << RADIX))
        sub = jnp.stack(outs, axis=0)
        a = jnp.where((borrow == 0), sub, a)
    return a


def fe_eq(a, b):
    return jnp.all(fe_canonical(a) == fe_canonical(b), axis=0)


def _ladder(z):
    """Shared exponentiation ladder: returns (z^(2^250-1), z^11)."""
    t0 = fe_sq(z)
    t1 = fe_sq(fe_sq(t0))
    t1 = fe_mul(z, t1)  # z^9
    t0 = fe_mul(t0, t1)  # z^11
    t2 = fe_sq(t0)
    t1 = fe_mul(t1, t2)  # z^31
    z11 = t0

    def times(x, n):
        if n <= 4:
            for _ in range(n):
                x = fe_sq(x)
            return x
        return lax.fori_loop(0, n, lambda _, v: fe_sq(v), x)

    t2 = times(t1, 5)
    t1 = fe_mul(t2, t1)  # 2^10-1
    t2 = times(t1, 10)
    t2 = fe_mul(t2, t1)  # 2^20-1
    t3 = times(t2, 20)
    t2 = fe_mul(t3, t2)  # 2^40-1
    t2 = times(t2, 10)
    t1 = fe_mul(t2, t1)  # 2^50-1
    t2 = times(t1, 50)
    t2 = fe_mul(t2, t1)  # 2^100-1
    t3 = times(t2, 100)
    t2 = fe_mul(t3, t2)  # 2^200-1
    t2 = times(t2, 50)
    t1 = fe_mul(t2, t1)  # 2^250-1
    return t1, z11


def fe_invert(z):
    t1, z11 = _ladder(z)
    for _ in range(5):
        t1 = fe_sq(t1)
    return fe_mul(t1, z11)  # z^(p-2)


def fe_pow22523(z):
    t1, _ = _ladder(z)
    t1 = fe_sq(fe_sq(t1))
    return fe_mul(t1, z)  # z^(2^252-3)


# ---------------------------------------------------------------------------
# Point ops: extended twisted-Edwards coordinates as (X, Y, Z, T) tuples of
# limb-major arrays. The addition law is complete on ed25519.
# ---------------------------------------------------------------------------


def pt_identity(batch_shape=()):
    zero = jnp.zeros((NLIMB,) + batch_shape, jnp.int32)
    one = zero.at[0].set(1)
    return (zero, one, one, zero)


def pt_add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = fe_mul(fe_sub(y1, x1), fe_sub(y2, x2))
    b = fe_mul(fe_add(y1, x1), fe_add(y2, x2))
    c = fe_mul(fe_mul(t1, _bcast(_2D, t1)), t2)
    d = fe_mul(fe_add(z1, z1), z2)
    e, f, g, h = fe_sub(b, a), fe_sub(d, c), fe_add(d, c), fe_add(b, a)
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_double(p):
    x1, y1, z1, _ = p
    a = fe_sq(x1)
    b = fe_sq(y1)
    c = fe_add(fe_sq(z1), fe_sq(z1))
    h = fe_add(a, b)
    e = fe_sub(h, fe_sq(fe_add(x1, y1)))
    g = fe_sub(a, b)
    f = fe_add(c, g)
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_neg(p):
    x, y, z, t = p
    return (fe_neg(x), y, z, fe_neg(t))


# Cached-form addition (the dalek/ref10 "cached point" trick): a table
# entry stored as (Y+X, Y−X, Z, 2D·T) turns the complete 9-mul pt_add into
# an 8-mul add — the (t1·2D)·t2 double-multiply collapses into one t1·t2d.
# Table entries are added ~100x each (once per window lane), so the one
# extra mul spent caching each entry buys back 64-96 muls per point.


def pt_cache(p):
    """Projective (X, Y, Z, T) -> cached (Y+X, Y−X, Z, 2D·T). All outputs
    stay inside the loose bound (add <= 9409, sub <= 8801, mul <= 8800)."""
    x, y, z, t = p
    return (fe_add(y, x), fe_sub(y, x), z, fe_mul(t, _bcast(_2D, t)))


def pt_add_cached(p, q):
    """p projective + q cached: 8 fe_muls (vs pt_add's 9)."""
    x1, y1, z1, t1 = p
    yp2, ym2, z2, t2d = q
    a = fe_mul(fe_sub(y1, x1), ym2)
    b = fe_mul(fe_add(y1, x1), yp2)
    c = fe_mul(t1, t2d)
    d = fe_mul(fe_add(z1, z1), z2)
    e, f, g, h = fe_sub(b, a), fe_sub(d, c), fe_add(d, c), fe_add(b, a)
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


def pt_add_cached_z1(p, q):
    """p projective + q cached with Z2 == 1 (affine table constants): the
    d term needs no multiply — 7 fe_muls."""
    x1, y1, z1, t1 = p
    yp2, ym2, t2d = q
    a = fe_mul(fe_sub(y1, x1), ym2)
    b = fe_mul(fe_add(y1, x1), yp2)
    c = fe_mul(t1, t2d)
    d = fe_add(z1, z1)
    e, f, g, h = fe_sub(b, a), fe_sub(d, c), fe_add(d, c), fe_add(b, a)
    return (fe_mul(e, f), fe_mul(g, h), fe_mul(f, g), fe_mul(e, h))


# ---------------------------------------------------------------------------
# Decompression and batched verification (limb-major, batch in the lanes).
# ---------------------------------------------------------------------------


def decompress(y_limbs, sign):
    """Recover x from canonical y [NLIMB, B] and sign [B]. Returns (point,
    valid[B])."""
    y2 = fe_sq(y_limbs)
    u = fe_sub(y2, jnp.asarray(_ONE))
    v = fe_add(fe_mul(y2, jnp.asarray(_D)), jnp.asarray(_ONE))
    v3 = fe_mul(fe_sq(v), v)
    v7 = fe_mul(fe_sq(v3), v)
    x = fe_mul(fe_mul(u, v3), fe_pow22523(fe_mul(u, v7)))
    vx2 = fe_mul(v, fe_sq(x))
    correct = fe_eq(vx2, u)
    flipped = fe_eq(vx2, fe_neg(u))
    valid = correct | flipped
    x = jnp.where(flipped, fe_mul(x, jnp.asarray(_SQRT_M1)), x)
    x_can = fe_canonical(x)
    x_zero = jnp.all(x_can == 0, axis=0)
    valid = valid & ~(x_zero & (sign == 1))
    parity = x_can[0] & 1
    x = jnp.where(parity != sign, fe_neg(x), x)
    one = jnp.zeros_like(x).at[0].set(1)
    return (x, y_limbs, one, fe_mul(x, y_limbs)), valid


def _select(table, digit):
    """table [16, NLIMB, B], digit [B] -> [NLIMB, B]: binary where-tree on
    the digit bits — (8+4+2+1) masked rows instead of the one-hot einsum's
    16 multiply-accumulate rows (~2x fewer lane ops per lookup)."""
    cur = table
    for bit in (3, 2, 1, 0):
        half = cur.shape[0] // 2
        take_hi = ((digit >> bit) & 1).astype(bool)[None, None, :]
        cur = jnp.where(take_hi, cur[half:], cur[:half])
    return cur[0]


def _select_const(table, digit):
    """table [16, NLIMB] (host constant), digit [B] -> [NLIMB, B]."""
    cur = jnp.broadcast_to(
        jnp.asarray(table)[:, :, None], (16, table.shape[1], digit.shape[0])
    )
    for bit in (3, 2, 1, 0):
        half = cur.shape[0] // 2
        take_hi = ((digit >> bit) & 1).astype(bool)[None, None, :]
        cur = jnp.where(take_hi, cur[half:], cur[:half])
    return cur[0]


@tracked_jit
def verify_batch_kernel(a_y, a_sign, r_y, r_sign, k_digits, s_digits):
    """Per-lane check of [S]B + [k](−A) against R, under BOTH rules:

    strict (cofactorless, the host library's): encode(Rcheck) == (r_y,
    r_sign); cofactored (RFC 8032 / dalek batch): [8](Rcheck − R) ==
    identity. Computing both in one pass costs one R decompression + four
    point ops (~10%) and lets the msm fallback use a DETERMINISTIC
    device-side cofactored verdict — no per-item host bigint recheck an
    attacker could amplify, no budget that would make verdicts depend on
    flush composition.

    Host-facing shapes (batch-leading): a_y/r_y int[B, NLIMB] canonical y
    limbs; a_sign/r_sign int[B]; k_digits/s_digits int[B, 64] 4-bit digits
    MSB-first. Narrow dtypes welcome — limbs fit int16 and digits int8, so
    the host sends ~3x fewer bytes over the device link; everything is
    widened to int32 lanes here. Returns (strict bool[B], cofactored
    bool[B]).
    """
    a_y = a_y.T.astype(jnp.int32)  # -> limb-major [NLIMB, B]
    r_y = r_y.T.astype(jnp.int32)
    a_sign = a_sign.astype(jnp.int32)
    r_sign = r_sign.astype(jnp.int32)
    k_digits = k_digits.T.astype(jnp.int32)  # -> [64, B]
    s_digits = s_digits.T.astype(jnp.int32)
    B = a_y.shape[1]

    a_point, valid = decompress(a_y, a_sign)

    # 16 cached multiples of -A built on device; 16 cached multiples of B
    # from the host. Every window add is then the 8-mul (device table) or
    # 7-mul (affine host table) cached form instead of the 9-mul pt_add.
    table_a = _pt_cached_table(pt_neg(a_point), B)
    ident = pt_identity((B,))

    def step(acc, digits):
        kd, sd = digits
        for _ in range(4):
            acc = pt_double(acc)
        qa = tuple(_select(table_a[i], kd) for i in range(4))
        acc = pt_add_cached(acc, qa)
        qb = (
            _select_const(_BT[:, 0], sd),
            _select_const(_BT[:, 1], sd),
            _select_const(_BT[:, 2], sd),
        )
        acc = pt_add_cached_z1(acc, qb)
        return acc, None

    acc, _ = lax.scan(step, ident, (k_digits, s_digits))

    zinv = fe_invert(acc[2])
    x = fe_mul(acc[0], zinv)
    y = fe_mul(acc[1], zinv)
    x_can = fe_canonical(x)
    ok_strict = fe_eq(y, r_y) & ((x_can[0] & 1) == r_sign) & valid

    # Cofactored verdict: [8](Rcheck + (−R)) == identity.
    r_point, r_valid = decompress(r_y, r_sign)
    diff = pt_add(acc, pt_neg(r_point))
    for _ in range(3):
        diff = pt_double(diff)
    ok_cof = fe_eq(diff[0], jnp.zeros_like(diff[0])) & fe_eq(diff[1], diff[2])
    return ok_strict, ok_cof & valid & r_valid


# ---------------------------------------------------------------------------
# Staged per-item verification: the monolithic trace split into three
# dispatchable stages. The monolith above compiles as ONE XLA module whose
# graph holds ~3.5 exponentiation-ladder instances (A decompress, R
# decompress, the final fe_invert) plus the 64-window scan — minutes of
# LLVM per (kernel, mesh shape) on XLA:CPU. The staged pipeline compiles
# three bounded modules instead:
#
#   decompress (ONE ladder, dispatched twice: A then R — one compile
#   serves both point sets, and the msm pipeline reuses the same stage)
#   -> straus scan (table build + 64-window walk)
#   -> verdict (fe_invert ladder + strict/cofactored epilogue)
#
# Intermediates stay on device between stages (stacked [4, NLIMB, B]
# coordinate tensors, donated forward so XLA reuses the buffers); the
# per-lane arithmetic is IDENTICAL to the monolith — decompress, the scan
# body and the epilogue are the same functions, batched the same way — so
# verdicts are bit-equal (pinned by tests/test_multichip.py). The mesh-
# sharded verifier dispatches these; the single-chip path keeps the
# monolith (one dispatch per bucket). Which family a locally attached
# chip prefers is unmeasured — ROADMAP D1 picks one from chip numbers.
# ---------------------------------------------------------------------------


@tracked_jit
def verify_decompress_kernel(y_rows, signs):
    """Stage 1: decompress one point set. y_rows int[B, NLIMB] canonical y
    limbs (host layout), signs int[B]. Returns (points int32[4, NLIMB, B]
    extended coords, valid bool[B]). Dispatched once for the A set and
    once for the R set — same shape, one compile."""
    y = y_rows.T.astype(jnp.int32)
    point, valid = decompress(y, signs.astype(jnp.int32))
    return jnp.stack(point, axis=0), valid


@tracked_jit
def verify_straus_kernel(a_pt, k_digits, s_digits):
    """Stage 2: the shared-doubling Straus walk. a_pt int32[4, NLIMB, B]
    decompressed A points; k_digits/s_digits int[B, 64] 4-bit MSB-first.
    Returns acc int32[4, NLIMB, B] = [S]B + [k](-A), projective."""
    a_point = tuple(a_pt[i] for i in range(4))
    k_digits = k_digits.T.astype(jnp.int32)
    s_digits = s_digits.T.astype(jnp.int32)
    B = a_pt.shape[2]

    table_a = _pt_cached_table(pt_neg(a_point), B)
    ident = pt_identity((B,))

    def step(acc, digits):
        kd, sd = digits
        for _ in range(4):
            acc = pt_double(acc)
        qa = tuple(_select(table_a[i], kd) for i in range(4))
        acc = pt_add_cached(acc, qa)
        qb = (
            _select_const(_BT[:, 0], sd),
            _select_const(_BT[:, 1], sd),
            _select_const(_BT[:, 2], sd),
        )
        acc = pt_add_cached_z1(acc, qb)
        return acc, None

    acc, _ = lax.scan(step, ident, (k_digits, s_digits))
    return jnp.stack(acc, axis=0)


@tracked_jit
def verify_verdict_kernel(acc_pt, r_pt, r_y, r_sign, a_valid, r_valid):
    """Stage 3: both verdicts from the scan accumulator and the
    decompressed R set — the monolith's epilogue verbatim. Returns
    (strict bool[B], cofactored bool[B])."""
    acc = tuple(acc_pt[i] for i in range(4))
    r_y_lm = r_y.T.astype(jnp.int32)
    r_sign = r_sign.astype(jnp.int32)

    zinv = fe_invert(acc[2])
    x = fe_mul(acc[0], zinv)
    y = fe_mul(acc[1], zinv)
    x_can = fe_canonical(x)
    ok_strict = fe_eq(y, r_y_lm) & ((x_can[0] & 1) == r_sign) & a_valid

    diff = pt_add(acc, pt_neg(tuple(r_pt[i] for i in range(4))))
    for _ in range(3):
        diff = pt_double(diff)
    ok_cof = fe_eq(diff[0], jnp.zeros_like(diff[0])) & fe_eq(diff[1], diff[2])
    return ok_strict, ok_cof & a_valid & r_valid


@tracked_jit(static_argnames=("chunk",))
def msm_window_kernel(pts, digits, chunk=128):
    """Staged msm stage 2: cached-table build from -P plus the window-lane
    accumulate over ONE point set (the monolith fused A and R into a
    single concatenated trace). pts int32[4, NLIMB, B] decompressed
    points, digits int[B, W]. Returns V int32[4, NLIMB, W] loose limbs per
    window lane. Under mesh sharding the batch axis is partitioned and V
    (no batch axis left) comes back replicated: per-device partial
    accumulates with one XLA-inserted cross-device reduce."""
    point = tuple(pts[i] for i in range(4))
    table = _pt_cached_table(pt_neg(point), pts.shape[2])
    v = _accumulate_windows(table, digits.astype(jnp.int32), chunk)
    return jnp.stack(v, axis=0)


# ---------------------------------------------------------------------------
# Random-linear-combination batch verification (one shared doubling chain).
#
# Per-item Straus pays 252 doublings + 128 table adds PER LANE. The batch
# equation  [Σ z_i S_i]B − Σ [z_i k_i]A_i − Σ [z_i]R_i == 0  (z_i random
# 128-bit, ed25519-dalek's batch rule) needs each point added into the sum
# ONCE per scalar window, with all doublings shared by the whole batch:
#
#   - window lanes: an accumulator [NLIMB, W, C] holds, per (window w,
#     chain c), Σ over that chain's points of digit·point — points stream
#     through in chunks of C (a lax.scan), one vectorized pt_add per chunk;
#   - chain reduction: log2(C) pairwise pt_adds;
#   - Horner: a log2(W) tree of (4·2^r doublings + add) collapses the
#     window lanes into Σ_w 16^(W-1-w) V_w — ~252 doublings total for the
#     ENTIRE batch instead of per signature;
#   - the R_i terms carry only the 128-bit z_i, so their accumulator has 32
#     window lanes instead of 64 (half the add work);
#   - the fixed-base [Σ z_i S_i]B term drops into the A accumulator's
#     window lanes as one extra add from the host B table.
#
# Net lane-op count per signature is ~2x below the per-item kernel (the
# decompression of R_i is the new cost; the 3200-fe-mul main loop shrinks
# to ~900). Soundness: a forged item passes only with probability ~2^-128
# over the verifier's choice of z_i. On failure the caller falls back to
# the per-item kernel to locate offenders (verifier.py).
# ---------------------------------------------------------------------------


def _select_lanes(table, digits):
    """table [16, NLIMB, C], digits [C, W] -> [NLIMB, W, C]: the binary
    where-tree of _select, broadcast so every window lane of every chain
    picks its own table row."""
    mask_src = digits.T  # [W, C]
    cur = table[:, :, None, :]  # [16, NLIMB, 1, C]
    for bit in (3, 2, 1, 0):
        half = cur.shape[0] // 2
        take_hi = ((mask_src >> bit) & 1).astype(bool)[None, None, :, :]
        cur = jnp.where(take_hi, cur[half:], cur[:half])
    return cur[0]


def _pt_cached_table(neg_p, batch):
    """16 multiples (identity, P, 2P, ... 15P) of each lane's point in
    CACHED form (Y+X, Y−X, Z, 2D·T): 4 coord arrays [16, NLIMB, B]. The
    chain itself runs on the cached base (8-mul adds); each emitted entry
    pays one extra mul (2D·T) so every later window add saves one."""
    base_c = pt_cache(neg_p)

    def next_multiple(prev, _):
        nxt = pt_add_cached(prev, base_c)
        return nxt, pt_cache(nxt)

    _, higher = lax.scan(next_multiple, neg_p, None, length=14)
    zero = jnp.zeros((NLIMB, batch), jnp.int32)
    one = zero.at[0].set(1)
    ident_c = (one, one, one, zero)  # cached identity: yp=ym=z=1, t2d=0
    return tuple(
        jnp.concatenate([ident_c[i][None], base_c[i][None], higher[i]], axis=0)
        for i in range(4)
    )


def _accumulate_windows(table, digits, chunk):
    """Stream the M points through the window-lane accumulator.

    table: 4 CACHED coords [16, NLIMB, M]; digits [M, W]. Returns V: 4
    projective coords [NLIMB, W] = per window lane, Σ_j digit_{j,w}·P_j.
    Every reduction is a fixed-shape scan so the compiled program stays
    one body per stage (the unrolled pairwise tree tripled compile time).
    """
    M, W = digits.shape
    C = min(chunk, M)
    S = M // C
    xs_table = tuple(
        t.reshape(16, NLIMB, S, C).transpose(2, 0, 1, 3) for t in table
    )  # each [S, 16, NLIMB, C]
    xs_digits = digits.reshape(S, C, W)

    def step(acc, xs):
        tab, dig = xs
        q = tuple(_select_lanes(tab[i], dig) for i in range(4))
        return pt_add_cached(acc, q), None

    acc0 = pt_identity((W, C))
    acc, _ = lax.scan(step, acc0, (jnp.stack(xs_table, 1), xs_digits))

    # Chain reduction [NLIMB, W, C] -> [NLIMB, W]: log2(C) halving rounds
    # expressed at FIXED width — each round adds the lane C/2^{r+1} to the
    # right of every live lane (dead lanes compute garbage that is never
    # read) — so the whole tree is one scan body with one pt_add.
    rounds = (C - 1).bit_length()
    offsets = jnp.asarray([C >> (r + 1) for r in range(rounds)], jnp.int32)

    def reduce_round(acc, off):
        idx = (jnp.arange(C, dtype=jnp.int32) + off) % C
        partner = tuple(jnp.take(a, idx, axis=-1) for a in acc)
        return pt_add(acc, partner), None

    acc, _ = lax.scan(reduce_round, acc, offsets)
    return tuple(a[..., 0] for a in acc)  # [NLIMB, W]


ROW_BYTES = 112  # A (32) | R (32) | ak (32) | z (16): one raw row of a bucket


def expand_rows(rows):
    """A bucket of raw rows -> the operands the msm stages take.

    rows uint8[B, ROW_BYTES]; a row is A (32 bytes) | R (32) | ak = z*k mod
    L (32) | z (16), each little-endian, as the host holds them; an
    all-zero row is inert padding (y = 0 decompresses, every digit 0 picks
    the identity). Returns, batch-leading and int32: a_y [B, NLIMB], a_sign
    [B], r_y [B, NLIMB], r_sign [B] (the 13-bit limbs and the sign bit of
    `bytes_to_limbs`), ak_digits [B, 64], z_digits [B, 32] (the 4-bit
    MSB-first digits of `bytes_to_digits`). Everything here scales with
    the bucket and nothing with the useful rows, so it runs on the device:
    the single-chip kernel calls it inside its program, the mesh path jits
    it on the data axis in front of its stages. The arithmetic runs with
    the batch in the lanes ([bytes, B]); the transposes to the
    batch-leading layout cancel against the stages' own."""
    t = rows.astype(jnp.int32).T  # [ROW_BYTES, B]

    def limbs(raw):  # [32, B] -> ([NLIMB, B], sign [B])
        top = raw[31]
        raw = jnp.concatenate(
            [raw[:31], (top & 0x7F)[None], jnp.zeros_like(raw[:1])], axis=0
        )  # sign bit cleared; one zero row for limb 19's third byte
        out = []
        for i in range(NLIMB):
            bit = RADIX * i
            b, shift = bit >> 3, bit & 7
            val = raw[b] | (raw[b + 1] << 8)
            if shift + RADIX > 16:
                val = val | (raw[b + 2] << 16)
            out.append((val >> shift) & MASK)
        return jnp.stack(out, axis=0), top >> 7

    def digits(raw):  # [n, B] little-endian bytes -> [2n, B] MSB-first nibbles
        rev = raw[::-1]
        return jnp.stack([rev >> 4, rev & 0xF], axis=1).reshape(2 * raw.shape[0], -1)

    a_y, a_sign = limbs(t[0:32])
    r_y, r_sign = limbs(t[32:64])
    return a_y.T, a_sign, r_y.T, r_sign, digits(t[64:96]).T, digits(t[96:112]).T


def msm_result(v_a, v_r, valid):
    """The one array a bucket's check comes back in: V_a [4, NLIMB, 64],
    V_r [4, NLIMB, 32] and the all-rows-valid flag, flattened to int32
    (`split_msm_result` is its inverse on the host)."""
    flag = jnp.all(valid).astype(jnp.int32)
    return jnp.concatenate([v_a.reshape(-1), v_r.reshape(-1), flag[None]])


MSM_RESULT_SIZE = 4 * NLIMB * (64 + 32) + 1


def split_msm_result(flat: np.ndarray):
    """Host views of `msm_result`'s array: (V_a, V_r, all rows valid)."""
    n_a = 4 * NLIMB * 64
    return (
        flat[:n_a].reshape(4, NLIMB, 64),
        flat[n_a:-1].reshape(4, NLIMB, 32),
        bool(flat[-1]),
    )


@tracked_jit(static_argnames=("chunk",), persist=True)
def msm_accumulate_kernel(rows, chunk=128):
    """Device half of the batch check Σ [z_ik_i](−A_i) + Σ [z_i](−R_i):
    per-window point sums over the whole batch.

    One operand up, one array down. rows uint8[B, ROW_BYTES] are the raw
    bytes of the bucket (`expand_rows` has the layout and derives limbs,
    signs and digits here, on the device); zero rows are inert padding.
    Returns `msm_result`'s flat int32 array: V_a int32[4, NLIMB, 64], V_r
    int32[4, NLIMB, 32] — X/Y/Z/T loose limbs per window lane — and
    whether every row decompressed.

    The A and R points share one decompress + cached-table build
    (concatenated batch axis) but run SEPARATE window accumulates: the R
    scalars are the raw 128-bit z_i, so their accumulator needs only 32
    window lanes — the r4 kernel zero-extended them to 64 and paid ~32
    inert 9-mul adds per R point (~16% of the whole kernel's multiplies).
    The host epilogue Horner-merges both lane sets (the last 32 windows of
    the chain take V_a[w] + V_r[w-32]) — see verifier.msm_epilogue_check;
    the ~300 sequential width-1 point ops of that chain would cost ~500 ms
    as sub-tile device work, vs ~2 ms of host bigint on the tiny readback.
    """
    a_y, a_sign, r_y, r_sign, ak_digits, z_digits = expand_rows(rows)
    B = rows.shape[0]

    ys = jnp.concatenate([a_y.T, r_y.T], axis=1)  # [NLIMB, 2B]
    signs = jnp.concatenate([a_sign, r_sign])

    points, valid = decompress(ys, signs)
    table = _pt_cached_table(pt_neg(points), 2 * B)
    table_a = tuple(t[..., :B] for t in table)
    table_r = tuple(t[..., B:] for t in table)
    v_a = _accumulate_windows(table_a, ak_digits, chunk)  # [NLIMB, 64] x4
    v_r = _accumulate_windows(table_r, z_digits, chunk)  # [NLIMB, 32] x4
    return msm_result(jnp.stack(v_a, axis=0), jnp.stack(v_r, axis=0), valid)


def msm_field_muls_per_signature(batch: int, chunk: int = 128) -> float:
    """Analytic fe_mul-equivalent cost per signature of the msm path —
    the roofline denominator for BENCH utilization accounting (VERDICT r4
    item 2: place the kernel against the measured VPU fe_mul rate).

    An fe_sq counts at its limb-product ratio, 210/400 of an fe_mul (the
    schoolbook column sums; carries are included in both measured rates).
    Per SIGNATURE (one A point + one R point):

      decompress x2: the shared exponentiation ladder is 251 sq + ~12 mul
        (_ladder + pow22523), plus ~4 sq + ~9 mul of surrounding ops;
      cached table x2: 14 chain adds x 8 mul (pt_add_cached) + 15 cache
        muls (2D*T per emitted entry incl. the base);
      accumulate: one 8-mul cached add per window lane — 64 lanes for the
        A scalar (z*k mod L, 256-bit) + 32 for the R scalar (z, 128-bit);
      chain reduction: log2(C) pt_adds (9 mul) over (64+32)*C lanes,
        amortized over the bucket.

    The host Horner epilogue is not counted (it overlaps device compute in
    the pipelined flow)."""
    sq = 210.0 / 400.0
    decompress = 2 * ((251 + 4) * sq + 21)
    table = 2 * (14 * 8 + 15)
    accumulate = 8 * (64 + 32)
    c = min(chunk, batch)
    rounds = (c - 1).bit_length()
    reduction = 9.0 * rounds * c * (64 + 32) / batch
    return decompress + table + accumulate + reduction


# ---------------------------------------------------------------------------
# Host-side packing helpers (numpy, vectorized over the batch).
# ---------------------------------------------------------------------------


def bytes_to_limbs(raw: np.ndarray) -> np.ndarray:
    """[B, 32] uint8 little-endian -> [B, NLIMB] int32 (sign bit cleared).

    Direct 3-byte gathers per limb (limb i = bits [13i, 13i+13), which span
    at most 3 bytes): ~60 vectorized ops total, ~10x faster than the
    unpackbits route — this runs in the host packing loop that bounds the
    pipelined verify rate."""
    raw32 = np.zeros((raw.shape[0], 33), np.int32)  # +1 zero column for i=19
    raw32[:, :32] = raw
    raw32[:, 31] &= 0x7F
    out = np.empty((raw.shape[0], NLIMB), np.int32)
    for i in range(NLIMB):
        bit = RADIX * i
        b, shift = bit >> 3, bit & 7
        val = raw32[:, b] | (raw32[:, b + 1] << 8)
        if shift + RADIX > 16:
            val |= raw32[:, b + 2] << 16
        out[:, i] = (val >> shift) & MASK
    return out


def bytes_to_digits(raw: np.ndarray) -> np.ndarray:
    """[B, 32] uint8 little-endian scalars -> [B, WINDOWS] 4-bit digits MSB
    first."""
    hi = (raw >> 4).astype(np.int32)
    lo = (raw & 0xF).astype(np.int32)
    digits = np.stack([lo, hi], axis=2).reshape(-1, 64)  # LSB-first nibbles
    return digits[:, ::-1]
