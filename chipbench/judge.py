"""What decides `correct`: every number compared, each beside its limit.

Two sources. What the clients saw while the window ran (every transaction
each validator executed, in order, byte for byte against what was offered),
and what the validators left on disk, read back with the plain reference
once the committee is shut down: the certificates each stored, the commit
sequence each recorded, the batches each worker holds. The plain reference
(chipbench/reference) imports nothing of the program. The commit sequence is
held to the plain rule of the engine the configuration names
(`consensus_protocol`): `chipbench/reference/<engine>.py`, found by that name,
so a configuration on a new engine brings its file and edits nothing here.

All counts; every limit is 0 (an exact comparison) and is the guarantee the
configuration states. PERF.md gives the readings the limits stand between.

`executed_again` is the guarantee "exactly once": a batch that two committed
certificates carry executes once for each, on all validators alike, so no
other number sees it (`exec_vs_store` holds the executor to the committed
sequence, repeats included). The stores' side is its second witness:
`notes.batches_committed_twice` and who carried each.
"""

from __future__ import annotations

import importlib
import os
import random

from .reference import ed25519, formats

REFERENCE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "reference")

LIMITS = {
    # clients' side
    "executed_unknown": 0,      # executed bytes that no client offered
    "executed_again": 0,        # executions of a transaction its validator had already executed
    "order_diverged": 0,        # validators whose order leaves validator 0's
    "lost_acked": 0,            # acknowledged, not executed by the end of the drain, and in no worker's store
    # the verify stage: a detour means the device refused a valid signature
    "detours": 0,
    # stores on disk, against the plain reference
    "walk_mismatch": 0,         # validators whose commit sequence is no prefix of the reference's
    "exec_vs_store": 0,         # validators whose executed order is not what their stores imply
    "bad_certificates": 0,      # sampled certificates failing the plain Ed25519 / digest checks
    "bad_batches": 0,           # stored batches whose digest or transactions are wrong
}


def ordering_reference(engine: str):
    """The plain commit rule of `engine`: the module
    `chipbench/reference/<engine>.py`, whose `commit_sequence(certs,
    gc_depth)` every validator's recorded sequence is held to. LookupError,
    naming the file, where the engine has none."""
    if not os.path.isfile(os.path.join(REFERENCE_DIR, f"{engine}.py")):
        raise LookupError(
            f"consensus_protocol {engine!r} has no plain commit rule: "
            f"chipbench/reference/{engine}.py is missing"
        )
    return importlib.import_module(f"{__package__}.reference.{engine}")


def _common_prefix_equal(a: list[int], b: list[int]) -> bool:
    n = min(len(a), len(b))
    return a[:n] == b[:n]


def client_side(rec: dict) -> dict:
    orders = rec["orders"]
    return {
        "executed_unknown": sum(rec["unknown"]),
        "executed_again": sum(rec["twice"]),
        "order_diverged": sum(
            0 if _common_prefix_equal(orders[0], o) else 1 for o in orders[1:]
        ),
        "detours": rec["detours"],
    }


def stores_side(rec: dict, seed: int, sample: int) -> tuple[dict, dict]:
    """Read every validator's stores back. Returns (numbers, notes)."""
    n = rec["validators"]
    base = rec["store_base"]
    gc_depth = rec["gc_depth"]
    engine = ordering_reference(rec["consensus_protocol"])
    txs = rec["txs"]
    certs: dict[bytes, formats.Cert] = {}
    sequences: list[list[bytes]] = []
    bad_certs = 0
    wal_breaks = []
    for v in range(n):
        info: dict = {}
        wal = formats.read_wal(
            os.path.join(base, f"node-{v}-primary"), {"certificates", "sequence"}, info=info
        )
        if info.get("unread_bytes"):
            wal_breaks.append({"validator": v, **info})
        for key, raw in wal["certificates"]:
            if key in certs:
                continue
            try:
                cert = formats.decode_certificate(raw)
            except ValueError:
                bad_certs += 1
                continue
            if cert.digest != key:
                bad_certs += 1
                continue
            certs[key] = cert
        seq: dict[int, bytes] = {}
        for key, digest in wal["sequence"]:
            seq[int.from_bytes(key, "big")] = digest
        sequences.append([seq[i] for i in sorted(seq)])

    # The commit walk: each validator's recorded sequence against the plain
    # rule of its engine run over the union of everybody's certificates.
    reference = engine.commit_sequence(list(certs.values()), gc_depth)
    walk_mismatch = 0
    for seq in sequences:
        if len(seq) > len(reference) or seq != reference[: len(seq)]:
            walk_mismatch += 1

    # The verify stage: a seeded sample of what the device let through,
    # with the longest quorum in it, under the plain Ed25519.
    authors = sorted({c.author for c in certs.values()})
    rng = random.Random(f"chipbench-sample:{seed}")
    committed = [d for d in reference if d in certs]
    picked = rng.sample(committed, min(sample, len(committed)))
    if committed:
        widest = max(committed, key=lambda d: len(certs[d].signers))
        if widest not in picked:
            picked.append(widest)
    quorum = 2 * len(authors) // 3 + 1
    for d in picked:
        c = certs[d]
        ok = ed25519.verify(c.author, c.header_digest, c.signature)
        ok = ok and len(set(c.signers)) == len(c.signers) >= quorum
        ok = ok and all(i < len(authors) for i in c.signers)
        if ok:
            voters = [authors[i] for i in c.signers]
            items = [(pk, formats.vote_digest(c, pk), r) for pk, r in zip(voters, c.rs)]
            ok = ed25519.verify_half_aggregate(items, formats.aggregate_weights(c), c.agg_s)
        bad_certs += 0 if ok else 1

    # The executor and the workers' stores: what each validator's own disk
    # says it should have executed, against what its clients saw.
    exec_vs_store = 0
    bad_batches = 0
    batches_checked = 0
    stored_ids: set[int] = set()
    for v in range(n):
        held: dict[bytes, list[int]] = {}
        for w in range(rec["workers"]):
            wal = formats.read_wal(
                os.path.join(base, f"node-{v}-worker-{w}"), {"batches"}, check_crc=False
            )
            for key, raw in wal["batches"]:
                if key in held:
                    continue
                ids = []
                try:
                    if formats.digest256(raw) != key:
                        raise ValueError("digest")
                    for tx in formats.batch_transactions(raw):
                        tx_id = int.from_bytes(tx[1:9], "big")
                        if tx[:1] != b"\x00" or not 0 < tx_id < len(txs) or txs[tx_id] != tx:
                            raise ValueError("transaction")
                        ids.append(tx_id)
                except ValueError:
                    bad_batches += 1
                    continue
                held[key] = ids
                stored_ids.update(ids)
                batches_checked += 1
        implied: list[int] = []
        short = False
        for d in sequences[v]:
            for batch_digest, _worker in certs[d].payload if d in certs else ():
                ids = held.get(batch_digest)
                if ids is None:
                    short = True  # the tail may still be in flight at shutdown
                    break
                implied.extend(ids)
            if short:
                break
        seen = rec["orders"][v]
        m = min(len(seen), len(implied))
        if seen[:m] != implied[:m] or (not short and len(seen) > len(implied)):
            exec_vs_store += 1
    # An acknowledged transaction that has not executed by the end of the
    # drain is late, and `failed`, while a sealed batch on some worker's disk
    # still holds it; it is lost, and wrong, when none does.
    late = [t for t in rec["unexecuted_acked"] if t in stored_ids]
    # A second witness for executed_again: a batch digest that two committed
    # certificates carry is executed once for each.
    carried: dict[bytes, list] = {}
    for d in reference:
        for batch_digest, _worker in certs[d].payload if d in certs else ():
            carried.setdefault(batch_digest, []).append(
                [authors.index(certs[d].author), certs[d].round]
            )
    repeats = [c for c in carried.values() if len(c) > 1]
    numbers = {
        "lost_acked": len(rec["unexecuted_acked"]) - len(late),
        "walk_mismatch": walk_mismatch,
        "exec_vs_store": exec_vs_store,
        "bad_certificates": bad_certs,
        "bad_batches": bad_batches,
    }
    notes = {
        "certificates_read": len(certs),
        "certificates_sampled": len(picked),
        "reference_commits": len(reference),
        "validator_commits": [len(s) for s in sequences],
        "batches_checked": batches_checked,
        "acked_late_not_lost": len(late),
        "primary_wal_breaks": wal_breaks,
        "batches_committed_twice": len(repeats),
        "committed_twice_by": repeats[:8],  # [validator, round] of each carrier
    }
    return numbers, notes


def verdict(numbers: dict) -> tuple[bool, dict]:
    """(correct, {name: [number, limit]})."""
    checks = {k: [numbers[k], LIMITS[k]] for k in LIMITS if k in numbers}
    ok = len(checks) == len(LIMITS) and all(v <= lim for v, lim in checks.values())
    return ok, checks
