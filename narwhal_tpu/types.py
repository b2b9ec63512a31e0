"""Wire types: Batch, Header, Vote, Certificate and inter-role messages.

Reference data model: /root/reference/types/src/primary.rs:32-789 (Batch :32-73,
Header :75-256, Vote :258-384, Certificate :386-644, message enums :646-789)
and /root/reference/types/src/worker.rs:17-62.

TPU-first deltas from the reference:
  * Certificates carry an ed25519 signature *vector* + signer index list
    instead of one aggregate BLS signature + roaring bitmap (see crypto.py for
    the rationale); verification is a batch verify over the vote digests —
    the exact shape the TPU verifier consumes.
  * All digests are SHA-256 of the canonical codec encoding (crypto.digest256;
    the reference uses blake2b-256 — see the rationale there), so the
    reference's `serialized_batch_digest` zero-copy optimization
    (/root/reference/types/src/worker.rs:44-62) holds by construction: hashing
    the wire bytes IS hashing the batch.
"""

from __future__ import annotations

import struct
from dataclasses import dataclass, field
from functools import cached_property
from types import MappingProxyType
from typing import Iterable, Mapping

from .bounded_cache import BoundedCache
from .codec import CodecError, Reader, Writer
from .crypto import DIGEST_LEN, PUBLIC_KEY_LEN, SIGNATURE_LEN, digest256, verify

Digest = bytes  # 32 bytes
PublicKey = bytes  # 32 bytes
WorkerId = int
Round = int
Epoch = int


class DagError(Exception):
    """Protocol-level rejection, mirroring /root/reference/types/src/error.rs:46-93."""


class InvalidEpoch(DagError):
    pass


class TooOld(DagError):
    pass


class InvalidSignatureError(DagError):
    pass


class QuorumNotReached(DagError):
    pass


class UnknownWorker(DagError):
    pass


# ---------------------------------------------------------------------------
# Batch
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Batch:
    """A list of opaque transactions (/root/reference/types/src/primary.rs:32-73)."""

    transactions: tuple[bytes, ...]

    def encode(self, w: Writer) -> None:
        w.seq(self.transactions, lambda w_, t: w_.bytes(t))

    @staticmethod
    def decode(r: Reader) -> "Batch":
        return Batch(tuple(r.seq(lambda r_: r_.bytes())))

    def to_bytes(self) -> bytes:
        w = Writer()
        self.encode(w)
        return w.finish()

    @staticmethod
    def from_bytes(data: bytes) -> "Batch":
        r = Reader(data)
        b = Batch.decode(r)
        r.done()
        return b

    @cached_property
    def digest(self) -> Digest:
        return digest256(self.to_bytes())

    @property
    def size_bytes(self) -> int:
        return sum(len(t) for t in self.transactions)


def serialized_batch_digest(wire_bytes: bytes) -> Digest:
    """Digest a serialized batch without deserializing it — the worker receive
    path optimization (/root/reference/types/src/worker.rs:44-62). Valid
    because Batch.digest hashes exactly the canonical wire encoding."""
    return digest256(wire_bytes)


_U32 = struct.Struct("<I")


def validate_tx_frames(frames: bytes, count: int) -> None:
    """Structurally validate a chunk of `count` length-prefixed transactions
    (the body of a client burst / batch, minus its leading count word).

    Client bursts flow through batching and dissemination in wire form — this
    walk (two unpacks per tx, no copies) is the only per-transaction work the
    trusted path does, and it keeps a malformed client chunk from ever
    reaching a sealed batch (where it would poison executor decode
    committee-wide)."""
    pos, end = 0, len(frames)
    unpack = _U32.unpack_from
    for _ in range(count):
        if pos + 4 > end:
            raise CodecError("truncated transaction chunk")
        (n,) = unpack(frames, pos)
        pos += 4 + n
        if pos > end:
            raise CodecError("transaction overruns chunk")
    if pos != end:
        raise CodecError("trailing bytes in transaction chunk")


def assemble_serialized_batch(count: int, frame_parts: list[bytes]) -> bytes:
    """Concatenate validated tx chunks into a canonical serialized Batch:
    u32 count | per-tx (u32 len | bytes). Identical bytes to
    Batch(txs).to_bytes() — the seal path never touches individual
    transactions."""
    return _U32.pack(count) + b"".join(frame_parts)


def iter_serialized_batch_txs(wire_bytes: bytes):
    """Yield (offset, length) of each transaction inside a serialized batch
    without copying — the benchmark sample scan."""
    (count,) = _U32.unpack_from(wire_bytes, 0)
    pos = 4
    unpack = _U32.unpack_from
    for _ in range(count):
        (n,) = unpack(wire_bytes, pos)
        pos += 4
        yield pos, n
        pos += n


@dataclass(frozen=True)
class SealedBatch:
    """A sealed batch in wire form: what the worker pipeline actually moves.

    The reference's BatchMaker hands `Batch` values around and re-serializes
    at each edge; here the serialized form is the value (sealed once, hashed
    once, broadcast as-is) and `Batch` is only materialized where individual
    transactions are needed (the executor)."""

    serialized: bytes
    count: int

    @cached_property
    def digest(self) -> Digest:
        return digest256(self.serialized)

    @property
    def size_bytes(self) -> int:
        # Payload bytes excluding the count word and per-tx length prefixes.
        return len(self.serialized) - 4 - 4 * self.count

    @cached_property
    def transactions(self) -> tuple[bytes, ...]:
        return Batch.from_bytes(self.serialized).transactions


# ---------------------------------------------------------------------------
# Header
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Header:
    """A round-r proposal (/root/reference/types/src/primary.rs:75-256).

    payload maps BatchDigest -> WorkerId; parents are certificate digests of
    round r-1. The digest covers everything but the signature; the signature
    covers the digest.
    """

    author: PublicKey
    round: Round
    epoch: Epoch
    payload: Mapping[Digest, WorkerId]
    parents: frozenset[Digest]
    signature: bytes = b""

    def _encode_core(self, w: Writer) -> None:
        w.raw(self.author)
        w.u64(self.round)
        w.u64(self.epoch)
        w.sorted_map(
            dict(self.payload),
            lambda w_, k: w_.raw(k),
            lambda w_, v: w_.u32(v),
        )
        w.seq(sorted(self.parents), lambda w_, p: w_.raw(p))

    @cached_property
    def digest(self) -> Digest:
        w = Writer()
        self._encode_core(w)
        return digest256(w.finish())

    def encode(self, w: Writer) -> None:
        self._encode_core(w)
        w.bytes(self.signature)

    @staticmethod
    def decode(r: Reader) -> "Header":
        author = r.raw(PUBLIC_KEY_LEN)
        rnd = r.u64()
        epoch = r.u64()
        # Decoded headers are shared process-wide by the decode caches
        # (messages._DECODE_CACHE and the store caches): every hosted node
        # sees the SAME object, so the payload must be read-only — one
        # node writing through it would corrupt every other node's view
        # (ADVICE r5 medium). MappingProxyType keeps dict-speed reads.
        payload = MappingProxyType(
            r.map(lambda r_: r_.raw(DIGEST_LEN), lambda r_: r_.u32())
        )
        parents = frozenset(r.seq(lambda r_: r_.raw(DIGEST_LEN)))
        signature = r.bytes()
        return Header(author, rnd, epoch, payload, parents, signature)

    def to_bytes(self) -> bytes:
        w = Writer()
        self.encode(w)
        return w.finish()

    @staticmethod
    def from_bytes(data: bytes) -> "Header":
        r = Reader(data)
        h = Header.decode(r)
        r.done()
        return h

    @staticmethod
    def build(
        author: PublicKey,
        round: Round,
        epoch: Epoch,
        payload: Mapping[Digest, WorkerId],
        parents: Iterable[Digest],
        signer,
    ) -> "Header":
        """Reference Header::new signs via the SignatureService
        (/root/reference/types/src/primary.rs:130-148).

        The payload is canonicalized (sorted by batch digest) at construction
        so local iteration order matches the wire encoding (Writer.sorted_map)
        — executors on every node, including the author and its post-crash
        replay, walk batches in the same order."""
        canonical = MappingProxyType(dict(sorted(payload.items())))
        h = Header(author, round, epoch, canonical, frozenset(parents))
        return Header(
            author, round, epoch, canonical, frozenset(parents), signer.sign(h.digest)
        )

    def verify(self, committee, worker_cache, check_signature: bool = True) -> None:
        """Mirrors Header::verify (/root/reference/types/src/primary.rs:180-233):
        epoch, authority known + has stake, worker ids valid, signature.
        `check_signature=False` runs only the structural checks — callers
        batching signatures elsewhere (the TPU verification stage) use it
        together with `signature_item()`."""
        if self.epoch != committee.epoch:
            raise InvalidEpoch(f"header epoch {self.epoch} != {committee.epoch}")
        if committee.stake(self.author) == 0:
            raise DagError(f"unknown authority {self.author.hex()[:16]}")
        for digest, worker_id in self.payload.items():
            if not worker_cache.has_worker(self.author, worker_id):
                raise UnknownWorker(f"worker {worker_id} not in cache")
        if check_signature and not verify(self.author, self.digest, self.signature):
            raise InvalidSignatureError("bad header signature")

    def signature_item(self) -> tuple[bytes, bytes, bytes]:
        """(pubkey, message, signature) for batch verification."""
        return (self.author, self.digest, self.signature)


# ---------------------------------------------------------------------------
# Vote
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Vote:
    """A signed endorsement of a header
    (/root/reference/types/src/primary.rs:258-384). origin = header author,
    author = the voter."""

    header_digest: Digest
    round: Round
    epoch: Epoch
    origin: PublicKey
    author: PublicKey
    signature: bytes = b""

    def _encode_core(self, w: Writer) -> None:
        w.raw(self.header_digest)
        w.u64(self.round)
        w.u64(self.epoch)
        w.raw(self.origin)
        w.raw(self.author)

    @cached_property
    def digest(self) -> Digest:
        w = Writer()
        self._encode_core(w)
        return digest256(w.finish())

    def encode(self, w: Writer) -> None:
        self._encode_core(w)
        w.bytes(self.signature)

    @staticmethod
    def decode(r: Reader) -> "Vote":
        return Vote(
            r.raw(DIGEST_LEN),
            r.u64(),
            r.u64(),
            r.raw(PUBLIC_KEY_LEN),
            r.raw(PUBLIC_KEY_LEN),
            r.bytes(),
        )

    @staticmethod
    def for_header(header: "Header", author: PublicKey, signer) -> "Vote":
        v = Vote(header.digest, header.round, header.epoch, header.author, author)
        return Vote(
            v.header_digest, v.round, v.epoch, v.origin, v.author, signer.sign(v.digest)
        )

    def verify(self, committee, check_signature: bool = True) -> None:
        """Vote::verify (/root/reference/types/src/primary.rs:344-371)."""
        if self.epoch != committee.epoch:
            raise InvalidEpoch(f"vote epoch {self.epoch} != {committee.epoch}")
        if committee.stake(self.author) == 0:
            raise DagError(f"unknown voter {self.author.hex()[:16]}")
        if check_signature and not verify(self.author, self.digest, self.signature):
            raise InvalidSignatureError("bad vote signature")

    def signature_item(self) -> tuple[bytes, bytes, bytes]:
        """(pubkey, message, signature) for batch verification."""
        return (self.author, self.digest, self.signature)


def vote_digest(
    header_digest: Digest, round: Round, epoch: Epoch, origin: PublicKey, author: PublicKey
) -> Digest:
    """Digest a vote without constructing it — used by certificate batch
    verification to rebuild each signer's signed message."""
    w = Writer()
    w.raw(header_digest)
    w.u64(round)
    w.u64(epoch)
    w.raw(origin)
    w.raw(author)
    return digest256(w.finish())


# ---------------------------------------------------------------------------
# Certificate
# ---------------------------------------------------------------------------

# Domain separator for the half-aggregation Fiat-Shamir weights. Versioned:
# changing anything about the transcript encoding must change this tag.
_AGG_DOMAIN = b"narwhal-tpu-halfagg-v1"


def aggregate_weights(
    header_digest: Digest, signers: tuple[int, ...], rs: tuple[bytes, ...]
) -> list[int]:
    """128-bit Fiat-Shamir weights z_i for certificate half-aggregation,
    bound to the whole transcript (header digest, signer set, every nonce
    point R_i). Deterministic, so verifier and aggregator agree; transcript-
    bound, so an adversary cannot craft per-signature errors that cancel —
    the soundness argument of Schnorr/EdDSA half-aggregation (Chalkias,
    Garillot, Kondi, Nikolaenko: "Non-interactive half-aggregation of EdDSA
    and variants", public construction; original implementation)."""
    import hashlib

    w = Writer()
    w.raw(_AGG_DOMAIN)
    w.raw(header_digest)
    w.seq(signers, lambda w_, i: w_.u32(i))
    w.seq(rs, lambda w_, r: w_.raw(r))
    base = hashlib.sha512(w.finish()).digest()
    return [
        int.from_bytes(
            hashlib.sha512(base + i.to_bytes(4, "little")).digest()[:16], "little"
        )
        for i in range(len(signers))
    ]


def host_verify_aggregate(
    items: list[tuple[bytes, bytes, bytes]], zs: list[int], agg_s: int
) -> bool:
    """Per-item host (pure-Python) check of ONE half-aggregated certificate:
    [8]([agg_s]B - sum([z_i k_i]A_i) - sum([z_i]R_i)) == identity, with
    k_i = SHA512(R_i || A_i || m_i) mod L. Cofactored, matching the device
    msm rule. Deliberately naive (~one double-and-add scalar-mul per term):
    this is the readable reference the batched verifier below is tested
    against, and the authoritative last-resort fallback of the device
    group lane (tpu/verifier.collect_groups). Production host paths go
    through `host_batch_verify_aggregates`, which amortizes one
    bucket-method MSM across many certificates."""
    from .tpu import ed25519_ref as ref

    acc = ref.IDENTITY
    for (pk, msg, r_bytes), z in zip(items, zs):
        a = ref.decompress(pk)
        r = ref.decompress(r_bytes)
        if a is None or r is None:
            return False
        k = ref.sha512_mod_l(r_bytes, pk, msg)
        acc = ref.point_add(acc, ref.point_mul(z * k % ref.L, a))
        acc = ref.point_add(acc, ref.point_mul(z % ref.L, r))
    acc = ref.point_add(ref.point_mul(agg_s % ref.L, ref.G), ref.point_neg(acc))
    for _ in range(3):  # cofactor 8
        acc = ref.point_double(acc)
    return ref.point_equal(acc, ref.IDENTITY)


# One aggregate-verification group, the unit `Certificate.aggregate_group`
# produces: ([(pubkey, message, R_i)], fiat-shamir weights z_i, agg scalar).
AggregateGroup = tuple[list[tuple[bytes, bytes, bytes]], list[int], int]


def _msm(terms: list[tuple[int, tuple]]):
    """Multi-scalar multiplication sum([s_i]P_i) over the ed25519_ref group
    via the bucket (Pippenger) method: per c-bit window, points land in
    2^c - 1 buckets (one add each) and the buckets collapse with ~2^(c+1)
    adds, so the per-point cost is ~ceil(253/c) adds instead of a full
    double-and-add ladder — the amortization that makes the host batched
    compact-verify path fast. Scalars must be reduced mod L."""
    from .tpu import ed25519_ref as ref

    n = len(terms)
    if n == 0:
        return ref.IDENTITY
    # Window width minimizing the add count: ceil(253/c) windows each cost
    # ~n bucket adds + ~2^(c+1) collapse adds.
    c = min(range(3, 13), key=lambda w: -(-253 // w) * (n + (1 << (w + 1))))
    mask = (1 << c) - 1
    nwin = -(-253 // c)  # scalars < L < 2^253
    point_add, point_double = ref.point_add, ref.point_double
    acc = ref.IDENTITY
    for w in range(nwin - 1, -1, -1):
        for _ in range(c):
            acc = point_double(acc)
        shift = w * c
        buckets: list = [None] * (1 << c)
        for s, p in terms:
            d = (s >> shift) & mask
            if d:
                b = buckets[d]
                buckets[d] = p if b is None else point_add(b, p)
        running = None
        total = None
        for d in range(mask, 0, -1):
            b = buckets[d]
            if b is not None:
                running = b if running is None else point_add(running, b)
            if running is not None:
                total = running if total is None else point_add(total, running)
        if total is not None:
            acc = point_add(acc, total)
    return acc


# Decompressed-point cache for signer public keys: a committee is a handful
# of keys whose points recur in EVERY certificate forever, and decompression
# (one ~255-bit pow) is the floor of the batched proof check. R nonce points
# are fresh per signature and never cached.
_PK_POINT_CACHE = BoundedCache(max_entries=1 << 12)


def _decompress_pk(pk: bytes):
    from .tpu import ed25519_ref as ref

    pt = _PK_POINT_CACHE.get(pk)
    if pt is None:
        pt = ref.decompress(pk)
        _PK_POINT_CACHE.put(pk, pt if pt is not None else False)
    return None if pt is False else pt


def _group_msm_terms(
    items: list[tuple[bytes, bytes, bytes]], zs: list[int]
) -> list[tuple[bytes, int, tuple]] | None:
    """The MSM terms of one group's -sum([z_i k_i]A_i) - sum([z_i]R_i)
    (negated so the verification sum targets the identity) as
    (point-identity key, scalar, point) triples, or None when any point
    fails to decompress — the same rejection `host_verify_aggregate`
    applies. The key (the compressed encoding) lets the combined batch
    check accumulate scalars per DISTINCT point: signer keys repeat in
    every certificate of a flush, so a batch of G groups over a quorum of
    Q signers carries ~Q + G*Q distinct points, not 2*G*Q."""
    from .tpu import ed25519_ref as ref

    terms: list[tuple[bytes, int, tuple]] = []
    for (pk, msg, r_bytes), z in zip(items, zs):
        a = _decompress_pk(pk)
        r = ref.decompress(r_bytes)
        if a is None or r is None:
            return None
        k = ref.sha512_mod_l(r_bytes, pk, msg)
        terms.append((pk, -(z * k), a))
        terms.append((r_bytes, -z, r))
    return terms


def _cofactored_identity(point) -> bool:
    """[8]point == identity (extended coordinates: X = 0 and Y = Z)."""
    from .tpu import ed25519_ref as ref

    for _ in range(3):
        point = ref.point_double(point)
    return point[0] % ref.P == 0 and (point[1] - point[2]) % ref.P == 0


def _verify_group_msm(
    items: list[tuple[bytes, bytes, bytes]], zs: list[int], agg_s: int
) -> bool:
    """Deterministic single-group check via one MSM — the exact equation of
    `host_verify_aggregate` (bit-equal verdicts, asserted by tests), ~4x
    faster, and the bisect step of the batched verifier below."""
    from .tpu import ed25519_ref as ref

    rows = _group_msm_terms(items, zs)
    if rows is None:
        return False
    terms = [(s % ref.L, p) for _, s, p in rows]
    terms.append((agg_s % ref.L, ref.G))
    return _cofactored_identity(_msm(terms))


# Aggregate-verdict cache: a compact certificate's proof check is a pure
# deterministic function of (items, zs, agg_s), and in a multi-node-per-host
# process EVERY hosted node verifies the same broadcast proof — the exact
# dedup the per-item _VERIFY_CACHE exploits for full signatures (the N=50
# profile: verification overwhelmingly duplicates). Keyed by a digest of the
# whole group transcript; thread-safe (verification runs on executor
# threads).
_AGG_VERDICT_CACHE = BoundedCache(max_entries=1 << 15)


# Entropy seam for the batched verifier's outer combination weights.
# Production draws from os.urandom (the adversary must not predict the
# weights); simnet's seeded scenarios install a deterministic stream so a
# replayed run performs bit-identical group arithmetic — same contract as
# `network.auth.set_entropy` for handshake nonces. The weights never
# influence VERDICTS (a failed combined check bisects deterministically),
# so this seam is about reproducible execution, not correctness.
def _default_weight_entropy(n: int) -> bytes:
    import os

    # This IS the seam's production default: seeded scenarios replace it
    # via set_weight_entropy; everything else must draw through it.
    return os.urandom(n)  # lint: allow(raw-entropy)


_weight_entropy = _default_weight_entropy


def set_weight_entropy(fn) -> "object":
    """Install an entropy source for the batch verifier's outer weights;
    returns the previous source so callers can restore it (pass None to
    reset to os.urandom)."""
    global _weight_entropy
    prev = _weight_entropy
    _weight_entropy = fn if fn is not None else _default_weight_entropy
    return prev


def _aggregate_cache_key(
    items: list[tuple[bytes, bytes, bytes]], zs: list[int], agg_s: int
) -> bytes:
    import hashlib

    h = hashlib.sha256()
    for pk, msg, r in items:
        h.update(pk)
        h.update(msg)
        h.update(r)
    for z in zs:
        h.update(z.to_bytes(16, "little"))
    h.update((agg_s % (1 << 256)).to_bytes(32, "little"))
    return h.digest()


def host_batch_verify_aggregates(groups: list[AggregateGroup]) -> list[bool]:
    """Batched cofactored verification of half-aggregated certificate
    proofs on the host — the randomized-linear-combination batch rule the
    device msm lane runs, in pure Python over ONE bucket-method MSM:

      [8]( [sum_g w_g s_g]B - sum_g w_g (sum_i [z_i k_i]A_i + [z_i]R_i) )
        == identity

    with a fresh 128-bit outer weight w_g per group per call (os.urandom —
    the adversary must not predict them, so adversarially related groups
    cannot cancel each other). One MSM serves every group in the dispatch,
    so the per-signature cost falls with batch size.

    Verdicts are verdict-equivalent to per-item cofactored verification and
    DETERMINISTIC despite the random weights: a failed combined check
    bisects to the deterministic single-group MSM (the same equation
    `host_verify_aggregate` evaluates), so no group's fate ever depends on
    its batch-mates — one adversarial certificate costs its own group a
    solo check, never the honest groups' acceptance (the r4-advisor
    amplification rule, host edition). Groups with undecodable points are
    rejected before the combined dispatch. Results are memoized in the
    process-wide aggregate-verdict cache."""
    from .tpu import ed25519_ref as ref

    ok = [False] * len(groups)
    pending: list[tuple[int, list[tuple[bytes, int, tuple]], int, bytes]] = []
    for g, (items, zs, s_agg) in enumerate(groups):
        key = _aggregate_cache_key(items, zs, s_agg)
        hit = _AGG_VERDICT_CACHE.get(key)
        if hit is not None:
            ok[g] = hit
            continue
        rows = _group_msm_terms(items, zs)
        if rows is None:
            _AGG_VERDICT_CACHE.put(key, False)
            continue
        pending.append((g, rows, s_agg, key))

    if not pending:
        return ok
    if len(pending) > 1:
        # Accumulate scalars per DISTINCT point across every group: the
        # signer keys A_i recur in every certificate of the flush, so the
        # combined MSM carries each committee key once with the summed
        # (w_g z_i k_i) scalar — cutting the term count nearly in half at
        # quorum scale (sound under the random linear combination: scalars
        # on one point are additive).
        by_point: dict[bytes, list] = {}
        sum_s = 0
        for _, rows, s_agg, _key in pending:
            w = int.from_bytes(_weight_entropy(16), "little")
            sum_s += w * s_agg
            for pkey, s, p in rows:
                entry = by_point.get(pkey)
                if entry is None:
                    by_point[pkey] = [w * s, p]
                else:
                    entry[0] += w * s
        combined = [(s % ref.L, p) for s, p in by_point.values()]
        combined.append((sum_s % ref.L, ref.G))
        if _cofactored_identity(_msm(combined)):
            for g, _rows, _s, key in pending:
                ok[g] = True
                _AGG_VERDICT_CACHE.put(key, True)
            return ok
    # Single group, or the combined check failed: deterministic per-group
    # verdicts (same equation, no outer weights).
    for g, rows, s_agg, key in pending:
        terms = [(s % ref.L, p) for _, s, p in rows]
        terms.append((s_agg % ref.L, ref.G))
        verdict = _cofactored_identity(_msm(terms))
        ok[g] = verdict
        _AGG_VERDICT_CACHE.put(key, verdict)
    return ok


@dataclass(frozen=True)
class Certificate:
    """A header plus a quorum of votes
    (/root/reference/types/src/primary.rs:386-644). The reference stores one
    aggregate BLS signature + a roaring bitmap of signers; we store the signer
    committee-indices (sorted) and the matching ed25519 vote signatures —
    batch-verifiable in one TPU call. The certificate digest depends only on
    the header (as in the reference), so certificates assembled from different
    vote subsets dedup to the same identity.

    Two wire forms (the `agg_s` field discriminates):

    - FULL: `signatures[i]` is signer i's 64-byte ed25519 vote signature.
    - COMPACT (half-aggregated, Parameters.cert_format="compact"): the
      per-vote scalars s_i are collapsed into one 32-byte `agg_s` =
      sum(z_i * s_i) mod L under Fiat-Shamir weights z_i bound to the whole
      transcript (aggregate_weights), and `signatures[i]` keeps only the
      32-byte R_i nonce point. This is Schnorr/EdDSA half-aggregation: the
      proof shrinks from 64 to ~32 bytes per signer — the capability the
      reference gets from BLS aggregation (O(1) certs,
      /root/reference/crypto/src/bls12377/mod.rs:45-120), recovered
      TPU-first: the verification equation
        [8]([agg_s]B - sum([z_i k_i]A_i) - sum([z_i]R_i)) == identity
      is EXACTLY the random-linear-combination shape the msm batch kernel
      computes, so devices verify compact certificates natively (and many
      of them fused in one dispatch under an outer random combination)."""

    header: Header
    signers: tuple[int, ...] = ()
    signatures: tuple[bytes, ...] = ()
    agg_s: bytes = b""

    @property
    def is_compact(self) -> bool:
        return len(self.agg_s) == 32

    @property
    def round(self) -> Round:
        return self.header.round

    @property
    def epoch(self) -> Epoch:
        return self.header.epoch

    @property
    def origin(self) -> PublicKey:
        return self.header.author

    @cached_property
    def digest(self) -> Digest:
        w = Writer()
        w.raw(b"CERT")
        w.raw(self.header.digest)
        return digest256(w.finish())

    def encode(self, w: Writer) -> None:
        self.header.encode(w)
        w.seq(self.signers, lambda w_, i: w_.u32(i))
        if self.is_compact:
            w.u8(1)
            w.seq(self.signatures, lambda w_, s: w_.raw(s))  # 32B R_i each
            w.raw(self.agg_s)
        else:
            w.u8(0)
            w.seq(self.signatures, lambda w_, s: w_.raw(s))

    @staticmethod
    def decode(r: Reader) -> "Certificate":
        header = Header.decode(r)
        signers = tuple(r.seq(lambda r_: r_.u32()))
        form = r.u8()
        if form == 1:
            rs = tuple(r.seq(lambda r_: r_.raw(32)))
            agg_s = r.raw(32)
            return Certificate(header, signers, rs, agg_s)
        if form != 0:
            raise CodecError(f"unknown certificate form {form}")
        sigs = tuple(r.seq(lambda r_: r_.raw(SIGNATURE_LEN)))
        return Certificate(header, signers, sigs)

    def to_bytes(self) -> bytes:
        w = Writer()
        self.encode(w)
        return w.finish()

    @staticmethod
    def from_bytes(data: bytes) -> "Certificate":
        r = Reader(data)
        c = Certificate.decode(r)
        r.done()
        return c

    @staticmethod
    def genesis(committee) -> list["Certificate"]:
        """One empty certificate per authority at round 0
        (/root/reference/types/src/primary.rs:402-420)."""
        return [
            Certificate(
                Header(author=pk, round=0, epoch=committee.epoch, payload={}, parents=frozenset())
            )
            for pk in committee.authorities
        ]

    def is_genesis(self) -> bool:
        return self.round == 0

    def _signer_checks(self, committee) -> tuple[bytes, ...] | None:
        """Shared structural checks: epoch, genesis well-formedness, arity,
        duplicate signers, index range, quorum stake. Returns the signer
        public keys in order (None for genesis)."""
        if self.epoch != committee.epoch:
            raise InvalidEpoch(f"certificate epoch {self.epoch} != {committee.epoch}")
        if self.is_genesis():
            if self not in Certificate.genesis(committee):
                raise DagError("malformed genesis certificate")
            return None
        if len(self.signers) != len(self.signatures):
            raise DagError("signer/signature arity mismatch")
        # Duplicate/range validation and the O(N) key+stake walk are
        # memoized per (committee, signer tuple): in the relay fan-out the
        # same certificate reaches every member N-1 times and each copy
        # used to re-pay the walk (a top-3 term of the N=200 wall).
        try:
            pks, stake = committee.signer_group(self.signers)
        except ValueError as e:
            raise DagError(str(e)) from e
        if stake < committee.quorum_threshold():
            raise QuorumNotReached(
                f"certificate carries {stake} stake < quorum {committee.quorum_threshold()}"
            )
        return pks

    def structural_verify(self, committee) -> None:
        """Only the structural/stake checks (epoch, arity, duplicate
        signers, quorum) — for callers whose signatures were already
        batch-verified elsewhere (the Core's preverified path). Works for
        both wire forms without recomputing messages or Fiat-Shamir
        weights."""
        self._signer_checks(committee)

    def verify_items(self, committee) -> list[tuple[bytes, bytes, bytes]]:
        """Structural checks + return the (pubkey, message, signature) batch
        to verify. Mirrors Certificate::verify
        (/root/reference/types/src/primary.rs:487-537): epoch, quorum stake of
        signers, then the signature check — here a batch of per-voter ed25519
        verifies instead of one aggregate-verify. FULL form only; compact
        certificates expose `aggregate_group` instead."""
        if self.is_compact:
            raise DagError("compact certificate has no per-item signatures")
        pks = self._signer_checks(committee)
        if pks is None:
            return []
        return [
            (
                pk,
                vote_digest(
                    self.header.digest, self.round, self.epoch, self.origin, pk
                ),
                sig,
            )
            for pk, sig in zip(pks, self.signatures)
        ]

    def aggregate_group(
        self, committee
    ) -> tuple[list[tuple[bytes, bytes, bytes]], list[int], int] | None:
        """Structural checks + the half-aggregation verification group:
        ([(pubkey, message, R)], fiat-shamir weights z_i, agg scalar). None
        for genesis. The check to perform is
          [8]([agg_s]B - sum([z_i k_i]A_i) - sum([z_i]R_i)) == identity
        with k_i = SHA512(R_i || A_i || m_i) mod L."""
        if not self.is_compact:
            raise DagError("aggregate_group on a full certificate")
        pks = self._signer_checks(committee)
        if pks is None:
            return None
        zs = aggregate_weights(self.header.digest, self.signers, self.signatures)
        items = [
            (
                pk,
                vote_digest(
                    self.header.digest, self.round, self.epoch, self.origin, pk
                ),
                r,
            )
            for pk, r in zip(pks, self.signatures)
        ]
        return items, zs, int.from_bytes(self.agg_s, "little")

    @staticmethod
    def compact_from_votes(
        header: "Header",
        signers: tuple[int, ...],
        signatures: tuple[bytes, ...],
        committee=None,
    ) -> "Certificate":
        """Half-aggregate a quorum of full 64-byte vote signatures into a
        compact certificate (the assembly-side counterpart of
        `aggregate_group`; Parameters.cert_format="compact").

        When the assembling node passes its `committee`, the aggregate
        verdict is pre-seeded into the process-wide cache IF every
        constituent full signature is already known-valid (a True entry in
        crypto's verified-signature cache — vote receipt verified them, or
        a co-hosted signer seeded them at sign time). That is sound: a
        strictly (cofactorless) valid signature satisfies
        [s_i]B - [k_i]A_i - R_i == identity exactly, so any z-weighted sum
        of valid equations satisfies the cofactored aggregate equation.
        Every co-hosted peer's verify of this certificate then hits the
        cache instead of paying the MSM."""
        from .tpu.ed25519_ref import L

        rs = tuple(sig[:32] for sig in signatures)
        zs = aggregate_weights(header.digest, signers, rs)
        agg = 0
        for z, sig in zip(zs, signatures):
            agg += z * int.from_bytes(sig[32:64], "little")
        cert = Certificate(header, signers, rs, (agg % L).to_bytes(32, "little"))
        if committee is not None:
            cert._seed_aggregate_verdict(committee, signatures)
        return cert

    def aggregate_proof_key(self, committee) -> bytes:
        """Content key for the aggregate-verdict FRONT cache: one hash
        over the certificate's raw proof fields plus the committee's
        memoized transcript digest. The proof verdict is a pure function
        of exactly these inputs (the Fiat-Shamir weights and every vote
        message derive from them), so equal keys mean equal verdicts —
        but unlike `_aggregate_cache_key` this never rebuilds the
        per-signer transcript, so a cache HIT costs O(certificate bytes)
        hashing instead of O(signers) vote-digest/weight recomputation.
        At co-hosting scale that is the difference: every hosted peer
        (and every relay duplicate) of a broadcast pays one flat hash."""
        from .crypto import digest256

        parts = [
            b"narwhal-agg-front-v1",
            committee.transcript_digest(),
            self.header.digest,
            int(self.round).to_bytes(8, "little"),
            int(self.epoch).to_bytes(8, "little"),
            self.origin,
            len(self.signers).to_bytes(4, "little"),
        ]
        parts.extend(int(i).to_bytes(4, "little") for i in self.signers)
        parts.extend(self.signatures)
        parts.append(self.agg_s)
        return digest256(b"".join(parts))

    def cached_aggregate_verdict(self, committee) -> bool | None:
        """Process-wide known verdict for this compact proof under this
        committee, or None. True/False only certify the PROOF MATH —
        callers still run the structural checks (`_signer_checks`) and
        the header's own verification."""
        return _AGG_VERDICT_CACHE.get(self.aggregate_proof_key(committee))

    def record_aggregate_verdict(self, committee, verdict: bool) -> None:
        """Publish a decided proof verdict under the front key (called by
        whoever paid for the MSM: the verifier stage, `verify`, or the
        assembler's seeding path)."""
        _AGG_VERDICT_CACHE.put(self.aggregate_proof_key(committee), bool(verdict))

    def _seed_aggregate_verdict(self, committee, full_signatures) -> None:
        from .crypto import _VERIFY_CACHE

        try:
            group = self.aggregate_group(committee)
        except DagError:
            return
        if group is None:
            return
        items, zs, s_agg = group
        for (pk, msg, _r), sig in zip(items, full_signatures):
            if _VERIFY_CACHE.get((pk, msg, sig)) is not True:
                return
        _AGG_VERDICT_CACHE.put(_aggregate_cache_key(items, zs, s_agg), True)
        self.record_aggregate_verdict(committee, True)

    def verify(self, committee, worker_cache) -> None:
        if self.is_compact:
            verdict = self.cached_aggregate_verdict(committee)
            if verdict is not None:
                # Front-cache hit: the proof math for this exact
                # (certificate content, committee) pair is already decided
                # somewhere in the process. Structural checks and the
                # header's own verification still run — only the
                # per-signer transcript rebuild and the MSM are skipped.
                if self._signer_checks(committee) is None:
                    return
                self.header.verify(committee, worker_cache)
                if not verdict:
                    raise InvalidSignatureError("aggregate certificate proof invalid")
                return
            group = self.aggregate_group(committee)
            if group is None:
                return
            self.header.verify(committee, worker_cache)
            # Single-group dispatch of the batched verifier: same verdict
            # as host_verify_aggregate (deterministic MSM), ~4x cheaper,
            # and shared with every co-hosted node via the process-wide
            # aggregate-verdict cache — the Core's loopback re-verification
            # of block-synchronizer fetches becomes a cache hit.
            ok = host_batch_verify_aggregates([group])[0]
            self.record_aggregate_verdict(committee, ok)
            if not ok:
                raise InvalidSignatureError("aggregate certificate proof invalid")
            return
        items = self.verify_items(committee)
        if not items:
            return
        self.header.verify(committee, worker_cache)
        from .crypto import batch_verify

        if not all(batch_verify(items)):
            raise InvalidSignatureError("certificate vote signature invalid")

    # DAG affiliation (reference: Affiliated for Certificate,
    # /root/reference/types/src/primary.rs:633-644): parents are hash
    # pointers; certificates with empty payload are compressible.
    def parent_digests(self) -> frozenset[Digest]:
        return self.header.parents

    def compressible(self) -> bool:
        return not self.header.payload


# ---------------------------------------------------------------------------
# Consensus output / sequence numbers
# ---------------------------------------------------------------------------

SequenceNumber = int


@dataclass(frozen=True)
class ConsensusOutput:
    """An ordered certificate with its global consensus index
    (/root/reference/types/src/consensus.rs:14-40)."""

    certificate: Certificate
    consensus_index: SequenceNumber


@dataclass(frozen=True)
class ReconfigureNotification:
    """Committee change / shutdown broadcast on the reconfigure watch channel
    (/root/reference/types/src/primary.rs:646-668 ReconfigureNotification).
    kind: 'new_epoch' | 'update_committee' | 'shutdown'."""

    kind: str
    committee: object | None = None
