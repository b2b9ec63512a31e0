"""Test fixtures: committees, signed headers/votes/certificates, DAG generators.

Reference: /root/reference/test_utils/src/lib.rs — CommitteeFixture :602-793,
synthetic DAG generators make_optimal_certificates / make_certificates(...,
failure_probability) / make_signed_certificates / mock_certificate :397-599.
Lives in the package (not tests/) because the benchmark launcher, chipbench
and chip_smoke.py also build committees from it, like the reference's
test_utils crate being a workspace member.
"""

from __future__ import annotations

import random
from dataclasses import dataclass

from .config import Authority, Committee, Parameters, WorkerCache, WorkerInfo
from .crypto import KeyPair, SignatureService
from .types import Certificate, Digest, Header, PublicKey, Round, Vote, WorkerId


@dataclass
class AuthorityFixture:
    keypair: KeyPair
    network_keypair: KeyPair
    worker_keypairs: dict[WorkerId, KeyPair]

    @property
    def public(self) -> PublicKey:
        return self.keypair.public

    def signature_service(self) -> SignatureService:
        return SignatureService(self.keypair)


class CommitteeFixture:
    """Deterministic committee of `size` authorities with `workers` workers
    each, equal stake, loopback addresses
    (/root/reference/test_utils/src/lib.rs:602-793)."""

    def __init__(
        self,
        size: int = 4,
        workers: int = 1,
        epoch: int = 0,
        seed: int = 0,
        base_port: int = 0,
        stakes: list[int] | None = None,
    ):
        self.size = size
        self.workers_per_authority = workers
        self.epoch = epoch
        self.authorities: list[AuthorityFixture] = []
        for i in range(size):
            kp = KeyPair.from_seed(f"authority-{seed}-{i}".encode().ljust(32, b"\0")[:32])
            nk = KeyPair.from_seed(f"network-{seed}-{i}".encode().ljust(32, b"\0")[:32])
            wks = {
                w: KeyPair.from_seed(
                    f"worker-{seed}-{i}-{w}".encode().ljust(32, b"\0")[:32]
                )
                for w in range(workers)
            }
            self.authorities.append(AuthorityFixture(kp, nk, wks))
        # Sort fixtures into committee canonical (pubkey-sorted) order so
        # authority index i here == committee dense index i.
        self.authorities.sort(key=lambda a: a.public)
        stakes = stakes or [1] * size
        port = [base_port]  # 0 => addresses are placeholders until bound

        def addr() -> str:
            if base_port == 0:
                return "127.0.0.1:0"
            port[0] += 1
            return f"127.0.0.1:{port[0]}"

        self.committee = Committee(
            {
                a.public: Authority(
                    stake=stakes[i], primary_address=addr(), network_key=a.network_keypair.public
                )
                for i, a in enumerate(self.authorities)
            },
            epoch=epoch,
        )
        self.worker_cache = WorkerCache(
            {
                a.public: {
                    w: WorkerInfo(
                        name=a.worker_keypairs[w].public,
                        transactions=addr(),
                        worker_address=addr(),
                    )
                    for w in range(workers)
                }
                for a in self.authorities
            },
            epoch=epoch,
        )
        self.parameters = Parameters()

    def authority(self, i: int) -> AuthorityFixture:
        return self.authorities[i]

    def keypair(self, name: PublicKey) -> KeyPair:
        for a in self.authorities:
            if a.public == name:
                return a.keypair
        raise KeyError(name.hex())

    # -- protocol object builders ----------------------------------------
    def header(
        self,
        author: int = 0,
        round: Round = 1,
        payload: dict[Digest, WorkerId] | None = None,
        parents: set[Digest] | None = None,
    ) -> Header:
        if parents is None:
            parents = {c.digest for c in Certificate.genesis(self.committee)}
        a = self.authorities[author]
        return Header.build(
            a.public, round, self.epoch, payload or {}, parents, a.keypair
        )

    def votes(self, header: Header, exclude_author: bool = True) -> list[Vote]:
        out = []
        for a in self.authorities:
            if exclude_author and a.public == header.author:
                continue
            out.append(Vote.for_header(header, a.public, a.keypair))
        return out

    def certificate(self, header: Header) -> Certificate:
        """Fully-signed certificate with a quorum of votes (header author's
        own implicit vote included, as the reference's VotesAggregator counts
        the author's stake)."""
        signers, sigs = [], []
        for a in self.authorities:
            v = Vote.for_header(header, a.public, a.keypair)
            signers.append(self.committee.index_of(a.public))
            sigs.append(v.signature)
        return Certificate(header, tuple(signers), tuple(sigs))


def mock_certificate(
    committee: Committee,
    origin: PublicKey,
    round: Round,
    parents: frozenset[Digest] | set[Digest],
    payload: dict[Digest, WorkerId] | None = None,
) -> Certificate:
    """Unsigned certificate for consensus/DAG tests
    (/root/reference/test_utils/src/lib.rs:575-599)."""
    return Certificate(
        Header(
            author=origin,
            round=round,
            epoch=committee.epoch,
            payload=payload or {},
            parents=frozenset(parents),
        )
    )


def make_optimal_certificates(
    committee: Committee,
    start_round: Round,
    end_round: Round,
    initial_parents: set[Digest],
    keys: list[PublicKey] | None = None,
) -> tuple[list[Certificate], set[Digest]]:
    """Fully-connected DAG rounds [start, end]
    (/root/reference/test_utils/src/lib.rs:397-420)."""
    return make_certificates(
        committee, start_round, end_round, initial_parents, keys, failure_probability=0.0
    )


def make_certificates(
    committee: Committee,
    start_round: Round,
    end_round: Round,
    initial_parents: set[Digest],
    keys: list[PublicKey] | None = None,
    failure_probability: float = 0.0,
    rng: random.Random | None = None,
) -> tuple[list[Certificate], set[Digest]]:
    """Possibly-lossy DAG: each certificate links to each previous-round parent
    with probability 1-failure_probability, but always keeps a quorum of links
    (/root/reference/test_utils/src/lib.rs:430-500)."""
    rng = rng or random.Random(0)
    keys = keys or committee.authority_keys()
    certificates: list[Certificate] = []
    parents = set(initial_parents)
    for r in range(start_round, end_round + 1):
        next_parents: set[Digest] = set()
        for pk in keys:
            parent_list = sorted(parents)
            if failure_probability > 0.0:
                quorum = (2 * len(parent_list)) // 3 + 1
                kept = [
                    p for p in parent_list if rng.random() >= failure_probability
                ]
                if len(kept) < quorum:
                    kept = rng.sample(parent_list, quorum)
                parent_list = kept
            cert = mock_certificate(committee, pk, r, set(parent_list))
            certificates.append(cert)
            next_parents.add(cert.digest)
        parents = next_parents
    return certificates, parents


def make_certificates_with_epoch(
    committee: Committee,
    start_round: Round,
    end_round: Round,
    epoch: int,
    initial_parents: set[Digest],
    keys: list[PublicKey] | None = None,
) -> tuple[list[Certificate], set[Digest]]:
    """(/root/reference/test_utils/src/lib.rs:502-540)."""
    keys = keys or committee.authority_keys()
    certificates: list[Certificate] = []
    parents = set(initial_parents)
    for r in range(start_round, end_round + 1):
        next_parents: set[Digest] = set()
        for pk in keys:
            cert = Certificate(
                Header(
                    author=pk,
                    round=r,
                    epoch=epoch,
                    payload={},
                    parents=frozenset(parents),
                )
            )
            certificates.append(cert)
            next_parents.add(cert.digest)
        parents = next_parents
    return certificates, parents


def make_signed_certificates(
    fixture: CommitteeFixture,
    start_round: Round,
    end_round: Round,
    initial_parents: set[Digest],
) -> tuple[list[Certificate], set[Digest]]:
    """Fully-signed DAG (/root/reference/test_utils/src/lib.rs:542-573)."""
    certificates: list[Certificate] = []
    parents = set(initial_parents)
    for r in range(start_round, end_round + 1):
        next_parents: set[Digest] = set()
        for i, a in enumerate(fixture.authorities):
            header = Header.build(
                a.public, r, fixture.epoch, {}, parents, a.keypair
            )
            cert = fixture.certificate(header)
            certificates.append(cert)
            next_parents.add(cert.digest)
        parents = next_parents
    return certificates, parents
