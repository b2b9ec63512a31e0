"""Plain Bullshark commit rule over a finished DAG.

From the Bullshark paper's partially synchronous rule as Narwhal deploys
it: the leader of even round r commits once round r+1 certificates that
name it as a parent carry f+1 stake; committing a leader first commits every
earlier leader it is linked to; each leader flattens its uncommitted causal
history, ordered by (round, author). The sequence is a function of the DAG
alone, so replaying the union of all validators' certificates in round order
must give a sequence of which every validator's own is a prefix. Imports
nothing of the program; the configuration states the leader schedule.
"""

from __future__ import annotations

import hashlib


def leader_of(round_: int, epoch: int, authors: list[bytes]) -> bytes:
    """Equal stake: sha256(round u64 LE | epoch u64 LE), first 8 bytes LE,
    modulo the committee size, over the authors in key order."""
    h = hashlib.sha256(round_.to_bytes(8, "little") + epoch.to_bytes(8, "little")).digest()
    return authors[int.from_bytes(h[:8], "little") % len(authors)]


def commit_sequence(certs, gc_depth: int) -> list[bytes]:
    """Digests in commit order. `certs`: objects with author, round, epoch,
    parents, digest."""
    authors = sorted({c.author for c in certs})
    n = len(authors)
    validity = (n + 2) // 3
    dag: dict[int, dict[bytes, object]] = {}
    last_committed = {a: 0 for a in authors}
    last_committed_round = 0
    sequence: list[bytes] = []

    def leader(r: int):
        return dag.get(r, {}).get(leader_of(r, epoch, authors))

    def linked(later, earlier) -> bool:
        frontier = [later]
        for r in range(later.round - 1, earlier.round - 1, -1):
            wanted = {p for c in frontier for p in c.parents}
            frontier = [c for c in dag.get(r, {}).values() if c.digest in wanted]
        return any(c.digest == earlier.digest for c in frontier)

    def flatten(lead):
        ordered, seen, stack = [], set(), [lead]
        while stack:
            c = stack.pop()
            ordered.append(c)
            below = {p.digest: p for p in dag.get(c.round - 1, {}).values()}
            for d in c.parents:
                p = below.get(d)
                if p is None or d in seen or last_committed[p.author] >= p.round:
                    continue
                seen.add(d)
                stack.append(p)
        ordered = [c for c in ordered if c.round + gc_depth >= last_committed_round]
        ordered.sort(key=lambda c: (c.round, c.author))
        return ordered

    for cert in sorted(certs, key=lambda c: (c.round, c.author)):
        epoch = cert.epoch
        dag.setdefault(cert.round, {})[cert.author] = cert
        r = cert.round - 1
        if r % 2 or r < 2 or r <= last_committed_round:
            continue
        lead = leader(r)
        if lead is None:
            continue
        support = sum(1 for c in dag[cert.round].values() if lead.digest in c.parents)
        if support < validity:
            continue
        chain, current = [lead], lead
        for back in range(r - 2, last_committed_round + 1, -2):
            prev = leader(back)
            if prev is not None and linked(current, prev):
                chain.append(prev)
                current = prev
        for chain_leader in reversed(chain):
            for c in flatten(chain_leader):
                last_committed[c.author] = max(last_committed[c.author], c.round)
                last_committed_round = max(last_committed.values())
                sequence.append(c.digest)
    return sequence
