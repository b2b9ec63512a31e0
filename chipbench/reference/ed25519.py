"""Plain Ed25519 over Python integers: the yardstick for device verdicts.

Written from RFC 8032 and imports nothing of the program. Cofactored
acceptance ([8] times the verification equation is the identity), the rule
the configurations state (`verify_rule: cofactored`). Slow by design: one
double-and-add per scalar, a few milliseconds each.
"""

from __future__ import annotations

import hashlib

P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)

# Points are extended coordinates (X, Y, Z, T).
IDENTITY = (0, 1, 1, 0)


def add(p, q):
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = (y1 - x1) * (y2 - x2) % P
    b = (y1 + x1) * (y2 + x2) % P
    c = 2 * t1 * t2 * D % P
    d = 2 * z1 * z2 % P
    e, f, g, h = b - a, d - c, d + c, b + a
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def mul(k: int, p):
    acc = IDENTITY
    while k:
        if k & 1:
            acc = add(acc, p)
        p = add(p, p)
        k >>= 1
    return acc


def neg(p):
    x, y, z, t = p
    return (-x % P, y, z, -t % P)


def is_identity(p) -> bool:
    x, y, z, _ = p
    return x % P == 0 and (y - z) % P == 0


def decompress(s: bytes):
    """RFC 8032 5.1.3; None for a non-canonical or off-curve encoding."""
    if len(s) != 32:
        return None
    y = int.from_bytes(s, "little")
    sign = y >> 255
    y &= (1 << 255) - 1
    if y >= P:
        return None
    u = (y * y - 1) % P
    v = (D * y * y + 1) % P
    x = pow(u * pow(v, P - 2, P) % P, (P + 3) // 8, P)
    if (v * x * x - u) % P:
        x = x * SQRT_M1 % P
        if (v * x * x - u) % P:
            return None
    if x == 0 and sign:
        return None
    if x & 1 != sign:
        x = P - x
    return (x, y, 1, x * y % P)


_GY = 4 * pow(5, P - 2, P) % P
G = decompress(_GY.to_bytes(32, "little"))


def challenge(r: bytes, a: bytes, msg: bytes) -> int:
    return int.from_bytes(hashlib.sha512(r + a + msg).digest(), "little") % L


def verify(public_key: bytes, msg: bytes, signature: bytes) -> bool:
    """[8]([s]B - [k]A - R) == identity, s canonical."""
    if len(signature) != 64:
        return False
    a = decompress(public_key)
    r = decompress(signature[:32])
    s = int.from_bytes(signature[32:], "little")
    if a is None or r is None or s >= L:
        return False
    k = challenge(signature[:32], public_key, msg)
    acc = add(mul(s, G), neg(add(mul(k, a), r)))
    return is_identity(mul(8, acc))


def verify_half_aggregate(items, weights, agg_s: int) -> bool:
    """One half-aggregated quorum proof (Chalkias et al., non-interactive
    half-aggregation of EdDSA): items are (public key, message, R_i), and
    [8]([agg_s]B - sum([z_i k_i]A_i + [z_i]R_i)) must be the identity."""
    acc = IDENTITY
    for (pk, msg, r_bytes), z in zip(items, weights):
        a = decompress(pk)
        r = decompress(r_bytes)
        if a is None or r is None:
            return False
        k = challenge(r_bytes, pk, msg)
        acc = add(acc, add(mul(z * k % L, a), mul(z % L, r)))
    acc = add(mul(agg_s % L, G), neg(acc))
    return is_identity(mul(8, acc))
