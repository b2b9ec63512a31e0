#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path starts on the chip.

    python chip_smoke.py                      # on a machine with a TPU

ONE process, the first and only one to touch JAX, drives three phases and
fails if any of them fails:

  verify_width  the verify plane through the object nodes use
                (`VerifyService.shared`, fixed 2,048-row bucket): seeded
                buckets of signatures from 50 keys — all-valid, then with
                forgeries and non-canonical encodings at seeded positions
                — in msm (cofactored) and item (strict) mode, and 50
                compact certificates of a 50-validator committee (34
                signers each, then one corrupted) through the group lane;
                verdicts equal the host library item for item.
  walk_width    the commit walk at N = 50, gc_depth 50 (window
                [64, 50, 50]): one seeded lossy DAG, long enough that the
                window slides, through host Bullshark/Tusk and
                TpuBullshark/TpuTusk; commit sequences identical digest
                for digest.
  served        `Cluster`, 4 validators x 1 worker, default `Parameters()`,
                `crypto_backend="tpu"` + `dag_backend="tpu"`, Bullshark:
                boot, progress, then a client streams >= 1,000 512-byte
                transactions to each validator's worker; every validator
                executes every transaction exactly once, all four in one
                identical order, with none of the verifier's detours fired.

It runs the defaults a deployed node runs and sets no NARWHAL_* variable.
The last two lines of stdout are one JSON object each: first the report
(environment, every phase with its first-dispatch and steady wall, detours,
`"claim": null`), then, last, the verdict and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as JAX reports it. `"ok": true` (and exit code 0) only on a
TPU, with every phase passed, zero detours on all-valid input and a clean
shutdown. With no TPU it exits non-zero before any phase and prints no
JSON. The numbers it prints are observations of one run, not metrics.

The only CPU mode is an explicit rehearsal at small sizes, which never
reports a chip pass (`"ok"` stays false):

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal [--phases walk_width]

`--chips 4` runs verify_width and walk_width sharded over four devices
(`--verify-shards` / `--dag-shards` paths); it is a side check, not a pass.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import hashlib
import importlib.metadata
import json
import logging
import random
import statistics
import sys
import time
import traceback
from dataclasses import dataclass, replace

# Verifier counts that mean "the device's answer was not taken as is".
DETOURS = ("msm_redispatch", "group_solo_redispatch", "group_host_verify")


@dataclass(frozen=True)
class Sizes:
    bucket: int  # VerifyService fixed bucket rows
    keys: int  # distinct signing keys in the singles buckets
    cert_committee: int  # validators in the compact-certificate committee
    certs: int  # compact certificates through the group lane
    walk_n: int  # committee size of the commit walk
    gc_depth: int
    walk_rounds: int  # long enough that the [gc_depth + 14] window slides
    lane_bursts: tuple[int, ...]  # transactions per stream message, per lane
    tx_size: int = 512
    # Parameters() fields the served committee runs with, besides the
    # committee-wide cofactored rule the tpu backend takes.
    served_overrides: tuple[tuple[str, object], ...] = ()


# The first burst alone crosses the 500,000-byte batch_size (1,000 x 512 B),
# so each worker seals at least one batch by size; the second is sealed by
# the timer.
FULL = Sizes(2048, 50, 50, 50, 50, 50, 80, (1000, 24))
# XLA:CPU takes ~0.4 s per verify dispatch even at 64 rows, which puts the
# commit latency above the 4 s admission target and the workers shed every
# submission; the rehearsal lifts that one target, and nothing else.
REHEARSAL = Sizes(
    64, 8, 7, 7, 7, 6, 40, (1000, 24),
    served_overrides=(("commit_latency_target", 60.0),),
)


_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[smoke +{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    """A smoke check is not an assert: it must hold under python -O too."""
    if not cond:
        raise RuntimeError(f"check failed: {what}")


class Ctx:
    def __init__(self, jax, sizes: Sizes, seed: int, chips: int):
        self.jax = jax
        self.sizes = sizes
        self.seed = seed
        self.chips = chips
        self.platform = jax.devices()[0].platform

    def rng(self, label: str) -> random.Random:
        return random.Random(f"{self.seed}:{label}")

    def check_placement(self, arrays, what: str, span: int | None = None) -> None:
        """The device did the work: every given array lives on the
        accelerator (and, sharded, some array spans `span` devices)."""
        arrays = list(arrays)
        check(bool(arrays), f"{what}: no device arrays to inspect")
        platforms = {d.platform for a in arrays for d in a.devices()}
        check(platforms == {self.platform}, f"{what}: arrays on {platforms}")
        if span is not None:
            widest = max(len(a.devices()) for a in arrays)
            check(widest == span, f"{what}: widest array spans {widest} != {span}")


# ---------------------------------------------------------------------------
# verify_width
# ---------------------------------------------------------------------------


def _signed_items(ctx: Ctx, n: int, adversarial: bool):
    """`n` (pk, 32-byte msg, sig) items from `sizes.keys` seeded keys; with
    `adversarial`, roughly one position in twelve is a forgery or a
    non-canonical encoding. Returns (items, expected verdicts)."""
    from narwhal_tpu.crypto import KeyPair
    from narwhal_tpu.tpu import ed25519_ref as ref

    rng = ctx.rng(f"items:{adversarial}")
    keys = [
        KeyPair.from_seed(hashlib.sha256(f"smoke-key-{ctx.seed}-{i}".encode()).digest())
        for i in range(ctx.sizes.keys)
    ]
    items, expected = [], []
    for i in range(n):
        kp = keys[rng.randrange(len(keys))]
        msg = rng.randbytes(32)
        # Not KeyPair.sign: that seeds the process-wide verified-signature
        # cache, and the host reference below must do its own verifying.
        sig = kp._private.sign(msg)
        kind = rng.randrange(12 * 6) if adversarial else -1
        if kind == 0:  # wrong message
            items.append((kp.public, rng.randbytes(32), sig))
        elif kind == 1:  # corrupt R
            bad = bytearray(sig)
            bad[rng.randrange(32)] ^= 1 << rng.randrange(8)
            items.append((kp.public, msg, bytes(bad)))
        elif kind == 2:  # corrupt S
            bad = bytearray(sig)
            bad[32 + rng.randrange(31)] ^= 1 << rng.randrange(8)
            items.append((kp.public, msg, bytes(bad)))
        elif kind == 3:  # wrong key
            other = keys[(keys.index(kp) + 1) % len(keys)]
            items.append((other.public, msg, sig))
        elif kind == 4:  # non-canonical S (S + L still fits 32 bytes)
            s = int.from_bytes(sig[32:], "little") + ref.L
            items.append((kp.public, msg, sig[:32] + s.to_bytes(32, "little")))
        elif kind == 5:  # non-canonical R (y >= p)
            r = (ref.P + rng.randrange(19)).to_bytes(32, "little")
            items.append((kp.public, msg, r + sig[32:]))
        else:
            items.append((kp.public, msg, sig))
        expected.append(kind < 0 or kind > 5)
    return items, expected


def _certificate_groups(ctx: Ctx):
    """Aggregate groups of `sizes.certs` compact certificates, each signed
    by a bare quorum of a `sizes.cert_committee`-validator committee, plus
    the same list with one proof corrupted at a seeded position."""
    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.types import Certificate, Vote

    fx = CommitteeFixture(size=ctx.sizes.cert_committee, seed=ctx.seed)
    quorum = 2 * fx.size // 3 + 1
    rng = ctx.rng("groups")
    certs = []
    for i in range(ctx.sizes.certs):
        header = fx.header(author=i % fx.size, round=1 + i)
        signers = sorted(rng.sample(range(fx.size), quorum))
        sigs = tuple(
            Vote.for_header(
                header, fx.authorities[s].public, fx.authorities[s].keypair
            ).signature
            for s in signers
        )
        idx = tuple(fx.committee.index_of(fx.authorities[s].public) for s in signers)
        # No committee argument: nothing is seeded into the verdict cache.
        certs.append(Certificate.compact_from_votes(header, idx, sigs))
    valid = [c.aggregate_group(fx.committee) for c in certs]
    bad_at = rng.randrange(len(certs))
    c = certs[bad_at]
    forged = Certificate(
        c.header, c.signers, c.signatures, bytes([c.agg_s[0] ^ 1]) + c.agg_s[1:]
    )
    corrupted = list(valid)
    corrupted[bad_at] = forged.aggregate_group(fx.committee)
    return valid, corrupted, quorum


def phase_verify_width(ctx: Ctx) -> dict:
    import numpy as np

    from narwhal_tpu import crypto
    from narwhal_tpu.tpu.verifier import VerifyService
    from narwhal_tpu.types import host_verify_aggregate

    jax, sz = ctx.jax, ctx.sizes
    shards = ctx.chips
    span = shards if shards > 1 else None
    msm = VerifyService.shared("msm", shards=shards, bucket=sz.bucket)
    strict = VerifyService.shared("item", shards=shards, bucket=sz.bucket)
    check(msm.verifier.max_bucket == sz.bucket, "msm service bucket")
    counts = msm.verifier.counts
    obs: dict = {"bucket": sz.bucket, "shards": shards}

    # One all-valid bucket, dispatched directly so its device outputs can
    # be inspected before the readback.
    valid, _ = _signed_items(ctx, sz.bucket, adversarial=False)
    t0 = time.perf_counter()
    handle = msm.verifier.submit(valid)
    ctx.check_placement(jax.live_arrays(), "msm dispatch outputs", span)
    got = msm.verifier.collect(handle)
    obs["msm_first_bucket_s"] = round(time.perf_counter() - t0, 3)
    check(all(got) and len(got) == sz.bucket, "all-valid msm bucket accepted")
    steady = []
    for _ in range(5):
        t0 = time.perf_counter()
        check(all(msm.verifier(valid)), "all-valid msm bucket accepted (steady)")
        steady.append(time.perf_counter() - t0)
    obs["msm_bucket_steady_ms"] = round(1000 * statistics.median(steady), 2)
    obs["detours_on_valid_input"] = sum(counts[d] for d in DETOURS)
    check(
        obs["detours_on_valid_input"] == 0,
        f"no detour on all-valid singles: {dict(counts)}",
    )

    # Forgeries and non-canonical encodings, through the service as nodes
    # call it: the failed bucket re-dispatches per item (the item kernel).
    mixed, expected = _signed_items(ctx, sz.bucket, adversarial=True)
    check(not all(expected), "adversarial bucket holds rejects")
    host = crypto._host_batch_verify(mixed)
    check(host == expected, "host library matches the construction")

    async def singles(svc):
        return await asyncio.gather(*(svc.verify(*it) for it in mixed))

    t0 = time.perf_counter()
    check(asyncio.run(singles(msm)) == host, "msm-mode verdicts == host, item for item")
    obs["msm_mixed_bucket_s"] = round(time.perf_counter() - t0, 3)
    check(counts["msm_redispatch"] >= 1, "forged bucket was re-dispatched per item")
    t0 = time.perf_counter()
    check(asyncio.run(singles(strict)) == host, "item-mode verdicts == host, item for item")
    obs["item_mixed_bucket_s"] = round(time.perf_counter() - t0, 3)
    check(msm.flushes["singles"] >= 1 and strict.flushes["singles"] >= 1, "singles flushed")

    # Compact certificates through the group lane.
    groups, corrupted, quorum = _certificate_groups(ctx)
    obs["cert_signers"] = quorum

    async def aggregates(gs):
        return await asyncio.gather(*(msm.verify_aggregate(*g) for g in gs))

    t0 = time.perf_counter()
    check(all(asyncio.run(aggregates(groups))), "all-valid certificate proofs accepted")
    obs["groups_valid_s"] = round(time.perf_counter() - t0, 3)
    obs["detours_on_valid_input"] += (
        counts["group_solo_redispatch"] + counts["group_host_verify"]
    )
    check(
        obs["detours_on_valid_input"] == 0,
        f"no detour on all-valid certificate proofs: {dict(counts)}",
    )
    t0 = time.perf_counter()
    host_groups = [host_verify_aggregate(*g) for g in corrupted]
    obs["groups_host_reference_s"] = round(time.perf_counter() - t0, 3)
    check(host_groups.count(False) == 1, "host rejects exactly the corrupted proof")
    t0 = time.perf_counter()
    check(asyncio.run(aggregates(corrupted)) == host_groups, "group verdicts == host")
    obs["groups_corrupted_s"] = round(time.perf_counter() - t0, 3)
    check(msm.flushes["groups"] >= 2, "group lane flushed")
    if shards > 1:
        from narwhal_tpu.tpu import kernel_registry

        sharded = {r["kernel"] for r in kernel_registry.compile_walls()
                   if r["mesh"] == f"{shards}:data"}
        check(
            {"msm_window_kernel", "verify_straus_kernel"} <= sharded,
            f"staged kernels dispatched on the {shards}-device data mesh: {sharded}",
        )

    # One-way and round-trip host<->device latencies, for ROADMAP D1.
    small = np.zeros((8,), np.int32)
    bump = jax.jit(lambda x: x + 1)
    dev = jax.device_put(small)
    np.asarray(bump(dev))
    up, trip = [], []
    for _ in range(20):
        t0 = time.perf_counter()
        dev = jax.device_put(small)
        dev.block_until_ready()
        up.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(bump(dev))
        trip.append(time.perf_counter() - t0)
    obs["host_to_device_put_ms"] = round(1000 * statistics.median(up), 3)
    obs["dispatch_plus_readback_ms"] = round(1000 * statistics.median(trip), 3)
    obs["expected_detours_on_adversarial_input"] = {d: counts[d] for d in DETOURS}
    return obs


# ---------------------------------------------------------------------------
# walk_width
# ---------------------------------------------------------------------------


def phase_walk_width(ctx: Ctx) -> dict:
    from narwhal_tpu.consensus import Bullshark, ConsensusState, Tusk
    from narwhal_tpu.fixtures import CommitteeFixture, make_certificates
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.tpu.dag_kernels import TpuBullshark, TpuTusk
    from narwhal_tpu.types import Certificate

    import numpy as np

    from narwhal_tpu.tpu import device_mesh

    sz = ctx.sizes
    mesh = device_mesh(ctx.chips, "auth", "--chips") if ctx.chips > 1 else None
    fx = CommitteeFixture(size=sz.walk_n, seed=ctx.seed)
    genesis = Certificate.genesis(fx.committee)
    t0 = time.perf_counter()
    certs, _ = make_certificates(
        fx.committee, 1, sz.walk_rounds, {c.digest for c in genesis},
        failure_probability=0.2, rng=ctx.rng("dag"),
    )
    obs: dict = {
        "window": [sz.gc_depth + 14, sz.walk_n, sz.walk_n],
        "certificates": len(certs),
        "dag_build_s": round(time.perf_counter() - t0, 3),
    }

    def store():
        return NodeStorage(None).consensus_store

    for name, host_cls, dev_cls in (
        ("bullshark", Bullshark, TpuBullshark),
        ("tusk", Tusk, TpuTusk),
    ):
        host, state, host_seq = host_cls(fx.committee, store(), sz.gc_depth), ConsensusState(genesis), []
        t0 = time.perf_counter()
        for c in certs:
            host_seq.extend(
                o.certificate.digest for o in host.process_certificate(state, len(host_seq), c)
            )
        host_s = time.perf_counter() - t0

        # The device engine takes the stream the way the Consensus runner
        # hands it over: seeded bursts of up to 64 certificates, a single
        # one through process_certificate, more through process_batch.
        dev = dev_cls(fx.committee, store(), sz.gc_depth, mesh=mesh)
        state, dev_seq, rng, at = ConsensusState(genesis), [], ctx.rng(f"bursts:{name}"), 0
        t0 = time.perf_counter()
        while at < len(certs):
            burst = certs[at : at + rng.randrange(1, 65)]
            at += len(burst)
            if len(burst) == 1:
                outs = dev.process_certificate(state, len(dev_seq), burst[0])
            else:
                outs = dev.process_batch(state, len(dev_seq), burst)
            dev_seq.extend(o.certificate.digest for o in outs)
        dev_s = time.perf_counter() - t0
        check(len(host_seq) > 0, f"{name}: the DAG commits")
        check(dev_seq == host_seq, f"{name}: device commit sequence == host, digest for digest")
        check(dev.win.round_base > 0, f"{name}: the window slid (base {dev.win.round_base})")
        if mesh is None:
            ctx.check_placement(dev.win.device_view(), f"{name}: resident window")
        else:
            # One more dispatch of the engine's own sharded program (the
            # steady K=1 shape), held so its placement can be read.
            n = dev.win.N
            masks = dev._chain_commit(
                dev.win.parent, dev.win.present, np.int32(sz.gc_depth),
                np.zeros((n,), np.int32), np.int32(-1),
                np.zeros((1,), np.int32), np.zeros((1, n), np.uint8),
            )
            ctx.check_placement([masks], f"{name}: sharded commit masks", ctx.chips)
        obs[name] = {
            "committed": len(host_seq),
            "window_base": dev.win.round_base,
            "host_stream_s": round(host_s, 3),
            "device_stream_s": round(dev_s, 3),
        }
    return obs


# ---------------------------------------------------------------------------
# served
# ---------------------------------------------------------------------------


class _NeverHits:
    """Stands in for `types._AGG_VERDICT_CACHE` while the served phase runs.

    That cache is process-wide and the certificate's assembler seeds it, so
    in a co-hosted committee one validator's verdict answers for all four
    and the group lane never dispatches (observed: zero group flushes in a
    whole run). Validators on their own machines each verify each proof;
    with the cache out of the way, so do these. ROADMAP D7 is to scope the
    co-hosting caches to a node, which retires this stand-in."""

    def get(self, key):
        return None

    def put(self, key, value, weight: int = 0) -> None:
        pass


async def _served(ctx: Ctx) -> dict:
    from narwhal_tpu.cluster import Cluster
    from narwhal_tpu.config import Parameters
    from narwhal_tpu.messages import SubmitTransactionStreamMsg
    from narwhal_tpu.network import NetworkClient
    from narwhal_tpu.tpu.verifier import VerifyService

    sz = ctx.sizes
    # The instance every node of this process takes (2,048 rows, the
    # default, unless this is the rehearsal).
    svc = VerifyService.shared("msm", bucket=sz.bucket)
    # Default Parameters() — 500,000-byte batches, 1,000-byte headers,
    # gc_depth 50, 100 ms delays — passed whole, so Cluster's test-fast
    # delay arguments do not apply.
    cluster = Cluster(
        size=4, workers=1, crypto_backend="tpu", dag_backend="tpu",
        consensus_protocol="bullshark",
        parameters=replace(
            Parameters(), verify_rule="cofactored", **dict(sz.served_overrides)
        ),
    )
    obs: dict = {}
    t0 = time.perf_counter()
    await cluster.start()
    drains: list[asyncio.Task] = []
    client = NetworkClient()
    try:
        check(
            all(a.primary.crypto_pool is svc for a in cluster.authorities),
            "nodes share the process-wide service",
        )
        check(svc.verifier.max_bucket == sz.bucket, "served bucket size")
        counts0 = collections.Counter(svc.verifier.counts)
        flushes0 = collections.Counter(svc.flushes)
        rounds = await cluster.assert_progress(commit_threshold=4, timeout=600.0)
        obs["boot_to_4_rounds_s"] = round(time.perf_counter() - t0, 3)
        obs["rounds_at_submit"] = sorted(rounds.values())

        orders: list[list[bytes]] = [[] for _ in cluster.authorities]

        async def drain(i: int) -> None:
            ch = cluster.authorities[i].primary.tx_execution_output
            while True:
                _, tx = await ch.recv()
                orders[i].append(bytes(tx))

        drains = [asyncio.ensure_future(drain(i)) for i in range(4)]
        rng = ctx.rng("txs")
        submitted: list[bytes] = []
        resubmits = 0
        t0 = time.perf_counter()
        for a in cluster.authorities:
            lane = a.worker_transactions_address(0)
            for burst in sz.lane_bursts:
                txs = []
                for _ in range(burst):
                    sid = len(submitted) + len(txs) + 1
                    txs.append(b"\x00" + sid.to_bytes(8, "big") + rng.randbytes(sz.tx_size - 9))
                while True:
                    try:
                        await client.request(lane, SubmitTransactionStreamMsg(tuple(txs)))
                        break
                    except Exception as e:
                        # A shed burst never entered the system; the
                        # client's part is to offer it again.
                        check("RESOURCE_EXHAUSTED" in str(e), f"submit failed: {e}")
                        resubmits += 1
                        check(resubmits < 100, "worker kept shedding")
                        await asyncio.sleep(0.25)
                submitted.extend(txs)
        obs["submitted"] = len(submitted)
        obs["shed_resubmits"] = resubmits
        deadline = time.monotonic() + 600.0
        while any(len(o) < len(submitted) for o in orders):
            check(
                time.monotonic() < deadline,
                f"executed {[len(o) for o in orders]} of {len(submitted)} in time",
            )
            await asyncio.sleep(0.1)
        obs["submit_to_all_executed_s"] = round(time.perf_counter() - t0, 3)
        await asyncio.sleep(1.0)  # a duplicate execution would land now

        check(all(o == orders[0] for o in orders), "one identical execution order on all four")
        check(len(orders[0]) == len(submitted), "every transaction executed exactly once")
        check(set(orders[0]) == set(submitted), "executed bytes == submitted bytes")

        size_sealed = []
        for a in cluster.authorities:
            h = a.workers[0].registry.get("worker_created_batch_size")._default()
            over = h.count - h.counts[h.buckets.index(cluster.parameters.batch_size)]
            size_sealed.append(over)
        check(min(size_sealed) >= 1, f"a size-sealed batch per worker: {size_sealed}")
        obs["size_sealed_batches"] = size_sealed
        obs["committed_rounds"] = sorted(
            a.metric("consensus_last_committed_round") for a in cluster.authorities
        )

        flushes = svc.flushes - flushes0
        counts = svc.verifier.counts - counts0
        obs["verify_flushes"] = dict(flushes)
        obs["verify_dispatches"] = dict(counts)
        check(flushes["singles"] >= 1, "a singles flush during the served phase")
        check(flushes["groups"] >= 1, "a group flush during the served phase")
        check(
            not (flushes["submit_failed"] or flushes["collect_failed"]),
            f"no failed flush: {dict(flushes)}",
        )
        obs["detours_on_valid_input"] = sum(counts[d] for d in DETOURS)
        check(
            obs["detours_on_valid_input"] == 0,
            f"no detour on the served (all-valid) workload: {dict(counts)}",
        )
        ctx.check_placement(ctx.jax.live_arrays(), "live arrays under the served load")
    finally:
        for d in drains:
            d.cancel()
        client.close()
        await cluster.shutdown()
    return obs


def phase_served(ctx: Ctx) -> dict:
    from narwhal_tpu import types

    shared_cache = types._AGG_VERDICT_CACHE  # AttributeError if it moved
    types._AGG_VERDICT_CACHE = _NeverHits()
    try:
        return asyncio.run(_served(ctx))
    finally:
        types._AGG_VERDICT_CACHE = shared_cache


RUNNERS = {
    "verify_width": phase_verify_width,
    "walk_width": phase_walk_width,
    "served": phase_served,
}
PHASES = tuple(RUNNERS)


def _required_walls(sz: Sizes) -> list[tuple[str, str]]:
    """First-dispatch entries the single-chip pass must have seen on mesh
    "1": (kernel, substring of its operand shapes)."""
    wide = f"uint8[{sz.gc_depth + 14},{sz.walk_n},{sz.walk_n}]"
    return [
        ("msm_accumulate_kernel", f"uint8[{sz.bucket},112]"),
        ("verify_batch_kernel", f"int16[{sz.bucket},20]"),
        ("chain_commit", wide),
        ("roll_window", wide),
        ("place_batch", wide),
        ("chain_commit", "uint8[64,4,4]"),  # the served committee's walk
    ]


def _shutdown() -> list[str]:
    """Stop every thread the device plane started; returns what would not
    stop. A daemon thread frozen in XLA during interpreter finalization
    aborts the process AFTER a success line, so a pass needs this clean."""
    from narwhal_tpu.tpu import dag_kernels
    from narwhal_tpu.tpu.verifier import VerifyService

    stuck = []
    for key, svc in list(VerifyService._shared.items()):
        if not svc.shutdown():
            stuck.append(f"verify service {key}")
    alive = dag_kernels.join_prewarm_threads(300.0)
    if alive:
        stuck.append(f"{alive} prewarm compile thread(s)")
    return stuck


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21, help="seeds every input")
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dress rehearsal at small sizes (needs JAX_PLATFORMS=cpu)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: verify_width + walk_width sharded over four devices")
    ap.add_argument("--phases", default=None,
                    help="comma-separated subset of " + ",".join(PHASES))
    args = ap.parse_args(argv)

    # First line of business: what JAX finds. Nothing before this touches it.
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if not args.rehearsal and device["platform"] != "tpu":
        print(
            f"chip_smoke: JAX found no TPU (platform={device['platform']}); nothing ran. "
            "A CPU dress rehearsal is JAX_PLATFORMS=cpu python chip_smoke.py --rehearsal",
            file=sys.stderr,
        )
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has {len(devs)} device(s)",
              file=sys.stderr)
        return 2

    default_phases = PHASES if args.chips == 1 else PHASES[:2]
    phases = tuple(args.phases.split(",")) if args.phases else default_phases
    unknown = [p for p in phases if p not in default_phases]
    if unknown:
        print(f"chip_smoke: cannot run {unknown} with --chips {args.chips}", file=sys.stderr)
        return 2

    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    try:
        from narwhal_tpu import crypto, native
        from narwhal_tpu.tpu import cpu_platform_named, kernel_registry
        from narwhal_tpu.tpu import dag_kernels  # noqa: F401 (its import enables the cache)
    except ImportError as e:
        print(f"chip_smoke: needs the narwhal_tpu package beside it ({e})", file=sys.stderr)
        return 2
    if args.rehearsal:
        if device["platform"] != "cpu" or not cpu_platform_named():
            print("chip_smoke: --rehearsal is the CPU mode; run it with JAX_PLATFORMS=cpu",
                  file=sys.stderr)
            return 2
        say("REHEARSAL on CPU at small sizes — this is not a chip run and reports no pass")
    libs = {"storage": native.load() is not None, "scalar": native.load_scalar() is not None}
    env = {
        "jax": jax.__version__,
        "jaxlib": importlib.metadata.version("jaxlib"),
        "libtpu": importlib.metadata.version("libtpu"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "have_openssl": crypto.HAVE_OPENSSL,
        "native_libraries": libs,
    }
    say(f"device {device}")
    say(f"environment {env}")
    if not all(libs.values()):
        print(f"chip_smoke: native libraries did not build/load: {libs}", file=sys.stderr)
        return 2

    ctx = Ctx(jax, REHEARSAL if args.rehearsal else FULL, args.seed, args.chips)
    report: dict = {}
    failed = False
    for name in phases:
        say(f"phase {name}: start")
        walls0 = {(r["kernel"], r["mesh"], r["shapes"]) for r in kernel_registry.compile_walls()}
        t0 = time.perf_counter()
        try:
            obs = RUNNERS[name](ctx)
            passed = True
        except Exception:
            traceback.print_exc()
            obs, passed, failed = {}, False, True
        wall = time.perf_counter() - t0
        first: dict[str, float] = collections.Counter()
        for r in kernel_registry.compile_walls():
            if (r["kernel"], r["mesh"], r["shapes"]) not in walls0:
                # One row per kernel and leading operand; the shapes that
                # differ only in a padded batch length add up.
                first[f"{r['kernel']}@{r['mesh']}[{r['shapes'].split(';')[0]}]"] += r["wall_s"]
        first = {k: round(v, 3) for k, v in first.items()}
        report[name] = {
            "passed": passed,
            "wall_s": round(wall, 3),
            # Trace + compile (or cache load) + enqueue of each kernel's
            # first dispatch in this phase; steady is the rest of the wall.
            "first_dispatch_s": round(sum(first.values()), 3),
            "steady_s": round(wall - sum(first.values()), 3),
            "first_dispatch_walls_s": first,
            "observed": obs,
        }
        say(f"phase {name}: {'passed' if passed else 'FAILED'} in {wall:.1f}s "
            f"(first dispatches {sum(first.values()):.1f}s)")

    ran_all = phases == PHASES and args.chips == 1
    if ran_all and not failed:
        rows = kernel_registry.compile_walls()
        missing = [
            f"{k} {shape}" for k, shape in _required_walls(ctx.sizes)
            if not any(r["kernel"] == k and r["mesh"] == "1" and shape in r["shapes"]
                       for r in rows)
        ]
        if missing:
            say(f"FAILED: no first-dispatch record on mesh 1 for {missing}")
            failed = True
    stuck = _shutdown()
    if stuck:
        say(f"FAILED: unclean shutdown, still running: {stuck}")
        failed = True

    # Not a rehearsal means a TPU: anything else returned above.
    ok = ran_all and not failed and not args.rehearsal
    summary = {
        "ok": ok,
        "device": device,
        "rehearsal": args.rehearsal,
        "chips": args.chips,
        "seed": args.seed,
        "environment": env,
        "phases": report,
        "phases_passed": not failed,
        "detours_on_valid_input": sum(
            r["observed"].get("detours_on_valid_input", 0) for r in report.values()
        ),
        "clean_shutdown": not stuck,
        "total_wall_s": round(time.monotonic() - _T0, 1),
        "claim": None,
    }
    print(json.dumps(summary), flush=True)
    # The last line is the verdict alone, in exactly this shape: it is what
    # the driver parses. Everything else is in the report line above it.
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
