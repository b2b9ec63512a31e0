"""Plain readers of what a validator leaves on disk, and the digests the
protocol signs. Written from the on-disk and wire layouts; imports nothing
of the program.

WAL (`<store>/wal.log`): records of `<u32 len, u32 crc32>` + body; a body is
`u32 count` then per op `u8 op, u16 name_len, name, u32 klen, key` and, for
a put (op 0), `u32 vlen, value`. The log is append-only, so replaying puts
and ignoring deletes recovers everything the node ever stored, garbage
collected or not.
"""

from __future__ import annotations

import hashlib
import mmap
import os
import struct
import zlib
from dataclasses import dataclass

_HDR = struct.Struct("<II")
_U32 = struct.Struct("<I")
_U64 = struct.Struct("<Q")


def digest256(data: bytes) -> bytes:
    return hashlib.sha256(data).digest()


def read_wal(path: str, families: set[str], check_crc: bool = True, info: dict | None = None):
    """Every put of the named column families, in log order:
    {family: [(key, value), ...]}. The walk ends where a recovering node's
    would: at the first record that is cut short or fails its checksum.
    `info`, if given, learns how far the walk got and, where it stopped
    short of the end, how many whole records lie beyond the break (a torn
    tail has none; a record damaged in mid-log has)."""
    out: dict[str, list[tuple[bytes, bytes]]] = {f: [] for f in families}
    log = os.path.join(path, "wal.log")
    if not os.path.exists(log) or os.path.getsize(log) == 0:
        return out
    with open(log, "rb") as f, mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as m:
        pos, end = 0, len(m)
        while pos + _HDR.size <= end:
            plen, crc = _HDR.unpack_from(m, pos)
            body_at = pos + _HDR.size
            if body_at + plen > end:
                break
            if check_crc and zlib.crc32(m[body_at : body_at + plen]) != crc:
                break
            if info is not None:
                info["records"] = info.get("records", 0) + 1
            at = body_at
            (count,) = _U32.unpack_from(m, at)
            at += 4
            for _ in range(count):
                op, name_len = struct.unpack_from("<BH", m, at)
                at += 3
                name = m[at : at + name_len].decode()
                at += name_len
                (klen,) = _U32.unpack_from(m, at)
                at += 4
                key_at = at
                at += klen
                if op == 0:
                    (vlen,) = _U32.unpack_from(m, at)
                    at += 4
                    if name in out:
                        out[name].append((m[key_at : key_at + klen], m[at : at + vlen]))
                    at += vlen
            pos = body_at + plen
        if info is not None:
            info["unread_bytes"] = end - pos
            info["records_beyond_break"] = _records_beyond(m, pos, end) if pos < end else 0
    return out


def _records_beyond(m, broke_at: int, end: int, reach: int = 1 << 20) -> int:
    """Whole, checksummed records found by sliding forward from a break."""
    found = 0
    at = broke_at + 1
    stop = min(end, broke_at + reach)
    while at + _HDR.size <= stop:
        plen, crc = _HDR.unpack_from(m, at)
        body_at = at + _HDR.size
        if 4 <= plen <= end - body_at and zlib.crc32(m[body_at : body_at + plen]) == crc:
            found += 1
            at = body_at + plen
            stop = min(end, at + reach)
        else:
            at += 1
    return found


@dataclass(frozen=True)
class Cert:
    author: bytes
    round: int
    epoch: int
    payload: tuple[tuple[bytes, int], ...]  # (batch digest, worker id), sorted
    parents: tuple[bytes, ...]  # sorted
    signature: bytes
    signers: tuple[int, ...]
    rs: tuple[bytes, ...]  # compact form: one R_i per signer
    agg_s: int
    header_digest: bytes
    digest: bytes


def decode_certificate(raw: bytes) -> Cert:
    """author[32] round u64 epoch u64 | map(batch digest[32] -> u32) |
    seq(parent[32]) | bytes(signature) | seq(u32 signer) | u8 form |
    seq(R[32]) agg_s[32]  (form 1, compact; the configurations state it)."""
    at = 0

    def take(n: int) -> bytes:
        nonlocal at
        if at + n > len(raw):
            raise ValueError("certificate truncated")
        at += n
        return raw[at - n : at]

    def u32() -> int:
        return _U32.unpack(take(4))[0]

    author = take(32)
    rnd = _U64.unpack(take(8))[0]
    epoch = _U64.unpack(take(8))[0]
    payload = tuple((take(32), u32()) for _ in range(u32()))
    parents = tuple(take(32) for _ in range(u32()))
    signature = take(u32())
    signers = tuple(u32() for _ in range(u32()))
    form = take(1)[0]
    if form != 1:
        raise ValueError(f"certificate form {form}: the configuration states compact")
    rs = tuple(take(32) for _ in range(u32()))
    agg_s = int.from_bytes(take(32), "little")
    if at != len(raw):
        raise ValueError("trailing bytes after certificate")
    if list(payload) != sorted(payload) or list(parents) != sorted(parents):
        raise ValueError("payload or parents not in canonical order")
    # The header digest covers everything but the signature.
    core = [author, _U64.pack(rnd), _U64.pack(epoch), _U32.pack(len(payload))]
    for d, w in payload:
        core += [d, _U32.pack(w)]
    core.append(_U32.pack(len(parents)))
    core += parents
    header_digest = digest256(b"".join(core))
    return Cert(
        author, rnd, epoch, payload, parents, signature, signers, rs, agg_s,
        header_digest, digest256(b"CERT" + header_digest),
    )


def vote_digest(cert: Cert, voter: bytes) -> bytes:
    return digest256(
        cert.header_digest + _U64.pack(cert.round) + _U64.pack(cert.epoch)
        + cert.author + voter
    )


def aggregate_weights(cert: Cert) -> list[int]:
    """128-bit Fiat-Shamir weights over the whole transcript."""
    parts = [b"narwhal-tpu-halfagg-v1", cert.header_digest, _U32.pack(len(cert.signers))]
    parts += [_U32.pack(i) for i in cert.signers]
    parts.append(_U32.pack(len(cert.rs)))
    parts += cert.rs
    base = hashlib.sha512(b"".join(parts)).digest()
    return [
        int.from_bytes(hashlib.sha512(base + i.to_bytes(4, "little")).digest()[:16], "little")
        for i in range(len(cert.signers))
    ]


def batch_transactions(raw: bytes) -> list[bytes]:
    """u32 count | per transaction u32 len, bytes."""
    (count,) = _U32.unpack_from(raw, 0)
    at, out = 4, []
    for _ in range(count):
        (n,) = _U32.unpack_from(raw, at)
        at += 4
        out.append(raw[at : at + n])
        at += n
    if at != len(raw) or len(out) != count:
        raise ValueError("batch does not parse")
    return out
