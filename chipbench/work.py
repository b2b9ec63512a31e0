"""Bytes a verify bucket needs, from its shapes alone.

Whatever kernel family does the work, one row of a batched Ed25519
verification reads a public key A (32 B), a nonce point R (32 B), a response
scalar s (32 B), the challenge k = H(R, A, m) mod L (32 B; the hash runs on
the host) and a 128-bit random weight z (16 B), and writes one verdict byte.
The kernel is int32 limb arithmetic on the vector unit, for which the chip
has no published peak, so its roofline here is the memory one and says so.
"""

from __future__ import annotations

ROW_IN_BYTES = 32 + 32 + 32 + 32 + 16
ROW_OUT_BYTES = 1


def verify_bucket_bytes(rows: int) -> int:
    return rows * (ROW_IN_BYTES + ROW_OUT_BYTES)


def least_seconds(rows: int, peaks: dict) -> float:
    """The least time the chip could take for one bucket: bytes over the
    peak memory bandwidth (the only bound with a published peak)."""
    return verify_bucket_bytes(rows) / peaks["hbm_bytes_per_s"]
