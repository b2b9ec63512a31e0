"""Fused double-buffered device pipeline: verify -> DAG insert -> commit.

The per-certificate hot path on a device-backed node crosses the host
boundary three times (verify readback, window scatter, commit-walk
readback) with the host re-touching the certificate at each stage. This
module fuses the three stages into one pipelined flow over BATCHES of
accepted certificates:

  feed(batch k+1)  — host packs the signature items and dispatches the
                     verify kernels; the device computes batch k+1's
                     verify WHILE batch k's DAG walk/readback completes
                     (jax dispatch is asynchronous, and TpuVerifier.submit
                     front-loads the device->host copies);
  _resolve(batch k)— verdicts gathered; accepted certificates enter the
                     consensus engine through ONE `process_batch` call:
                     one `place_batch` scatter for the whole batch, the
                     commit rule evaluated per trigger, each commit
                     event's chain_commit readback deferred one event so
                     it overlaps the next event's host bookkeeping.

The host therefore touches each certificate once at pack time and once at
accept time — never per stage — and with `depth` batches in flight the
device never idles between verify and walk dispatches (double-buffered at
the default depth=2).

Output equivalence: the commit sequence is identical to feeding the same
certificates one at a time through `process_certificate` (Bullshark's
commit rule is re-evaluated on every support-round certificate, so
batching arrivals can only move WHERE a commit is yielded, never its
content or order — pinned by tests/test_multichip.py).
"""

from __future__ import annotations

import collections
import logging
from typing import Iterable, Sequence

from ..types import Certificate, ConsensusOutput

logger = logging.getLogger("narwhal.tpu.pipeline")


class FusedCertificatePipeline:
    """verify -> place_batch -> chain_commit over certificate batches.

    verifier: a TpuVerifier (mesh-sharded or not) — its submit/collect
    halves are the pipeline's stage boundary; engine: a TpuBullshark (or
    TpuTusk); state: the ConsensusState the engine mutates. `depth` is
    the number of verify batches kept in flight (2 = double-buffered)."""

    def __init__(
        self, verifier, engine, state, start_index: int = 0, depth: int = 2
    ):
        self.verifier = verifier
        self.engine = engine
        self.state = state
        self.consensus_index = start_index
        self.depth = max(1, depth)
        self._inflight: collections.deque = collections.deque()
        self.outputs: list[ConsensusOutput] = []
        self.rejected: list[Certificate] = []

    def feed(self, certs: Sequence[Certificate], committee=None) -> None:
        """Pack + dispatch one verify batch; resolves the oldest in-flight
        batch first when the pipeline is full, so at most `depth` batches
        ride the device at once. Full-format certificates dispatch their
        per-vote signature items; compact certificates ride the verifier's
        aggregate group lane (submit_groups — the default dispatch shape
        now that compact is the committee-wide default), both halves of one
        batch in flight together."""
        while len(self._inflight) >= self.depth:
            self._resolve_one()
        committee = committee or self.engine.committee
        items: list = []
        groups: list = []
        # Input order preserved: ("item", cert, lo, hi) spans index into the
        # item verdicts, ("group", cert, g) into the group verdicts; g/lo of
        # None marks a signature-free certificate (genesis): valid.
        spans: list[tuple] = []
        for cert in certs:
            if cert.is_compact:
                group = cert.aggregate_group(committee)
                if group is None:
                    spans.append(("group", cert, None))
                else:
                    spans.append(("group", cert, len(groups)))
                    groups.append(group)
            else:
                cert_items = cert.verify_items(committee)
                spans.append(("item", cert, len(items), len(items) + len(cert_items)))
                items.extend(cert_items)
        handle = self.verifier.submit(items)
        ghandle = self.verifier.submit_groups(groups) if groups else None
        self._inflight.append((spans, handle, ghandle))

    def _resolve_one(self) -> None:
        spans, handle, ghandle = self._inflight.popleft()
        ok = self.verifier.collect(handle)
        gok = self.verifier.collect_groups(ghandle) if ghandle is not None else []
        accepted: list[Certificate] = []
        for span in spans:
            if span[0] == "group":
                _, cert, g = span
                passed = True if g is None else gok[g]
            else:
                _, cert, lo, hi = span
                # Genesis certificates carry no signatures (empty span):
                # valid.
                passed = all(ok[lo:hi])
            if passed:
                accepted.append(cert)
            else:
                self.rejected.append(cert)
        if accepted:
            outs = self.engine.process_batch(
                self.state, self.consensus_index, accepted
            )
            self.consensus_index += len(outs)
            self.outputs.extend(outs)

    def drain(self) -> list[ConsensusOutput]:
        """Resolve every in-flight batch and return the full committed
        sequence so far."""
        while self._inflight:
            self._resolve_one()
        return self.outputs

    def run(self, batches: Iterable[Sequence[Certificate]]) -> list[ConsensusOutput]:
        for batch in batches:
            self.feed(batch)
        return self.drain()
