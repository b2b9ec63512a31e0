"""Async pre-verification stage: pipeline signature checks off the Core.

The reference verifies every header/vote/certificate inline in the Core's
single-threaded loop (core.rs sanitize_*, the crypto hot path named by the
north star). Here, when a crypto pool is configured, the RPC handlers hand
messages to this stage instead: structural checks run immediately, signature
items go to the AsyncVerifierPool (which coalesces across ALL concurrently
arriving messages into fixed-shape device batches), and only successfully
verified messages are forwarded to the Core wrapped in `PreVerified` so its
sanitize step skips redundant signature work. The Core state machine stays
single-threaded; only crypto becomes pipelined + batched.

Every header, vote and certificate that passes through leaves one `stage`
record in the process flight ring (tracing.flight): (kind, the header digest
the message is about, node, t_in, t_verdict, t_forwarded, outcome), where
t_verdict is when the pool's answers were all in (t_in where nothing had to be
asked) and t_forwarded when `tx_out.send` returned. With NARWHAL_TRACE on the
same interval is a `verify_stage` span on the node's tracer, keyed by that
header digest, so `waterfall()` shows it under `certify`.
"""

from __future__ import annotations

import asyncio
import logging

from .. import tracing
from ..channels import Channel
from ..clock import now
from ..config import Committee, WorkerCache
from ..types import Certificate, DagError, Header, InvalidEpoch, Vote

logger = logging.getLogger("narwhal.primary")
# The loop account's owner of a message's first turn through the stage.
_OWNER = {kind: f"stage:{kind}" for kind in ("header", "vote", "certificate")}


class PreVerified:
    """Marker carrying a message whose signatures have been checked."""

    __slots__ = ("inner",)

    def __init__(self, inner):
        self.inner = inner


class VerifierStage:
    def __init__(
        self,
        committee: Committee,
        worker_cache: WorkerCache,
        pool,  # AsyncVerifierPool-compatible: await pool.verify(pk, msg, sig)
        tx_out: Channel,
        rx_reconfigure=None,  # Watch[ReconfigureNotification]: epoch swaps
        max_pending: int = 1_024,
        tracer=None,  # tracing.Tracer: the node's span sink (and its label)
    ):
        self._committee = committee
        self.worker_cache = worker_cache
        self.pool = pool
        self.tx_out = tx_out
        self.rx_reconfigure = rx_reconfigure
        self._sem = asyncio.Semaphore(max_pending)
        self._tasks: set[asyncio.Task] = set()
        self.tracer = tracer
        self.node = tracer.node if tracer is not None else ""

    @property
    def committee(self) -> Committee:
        """Latest committee: epoch changes land on the reconfigure watch, and
        a stage pinned to the boot committee would silently drop every
        new-epoch message."""
        if self.rx_reconfigure is not None:
            note = self.rx_reconfigure.value
            if note is not None and getattr(note, "committee", None) is not None:
                self._committee = note.committee
        return self._committee

    async def submit(self, msg) -> None:
        await self._sem.acquire()
        task = asyncio.ensure_future(self._verify(msg))
        self._tasks.add(task)

        def _done(t: asyncio.Task) -> None:
            self._tasks.discard(t)
            self._sem.release()

        task.add_done_callback(_done)

    async def _verify(self, msg) -> None:
        if isinstance(msg, Header):
            kind, key = "header", msg.digest
        elif isinstance(msg, Vote):
            kind, key = "vote", msg.header_digest
        elif isinstance(msg, Certificate):
            kind, key = "certificate", msg.header.digest
        else:
            await self.tx_out.send(msg)
            return
        tracing.charge(_OWNER[kind])
        t_in = now()
        forward, outcome, t_verdict = await self._decide(kind, msg, t_in)
        if forward is not None:
            await self.tx_out.send(forward)
        t_forwarded = now()
        tracing.flight(
            "stage", kind, key.hex(), self.node, t_in, t_verdict, t_forwarded, outcome
        )
        tracer = self.tracer
        if tracer is not None and tracer.enabled and tracer.sampled(key):
            tracer.span("verify_stage", key, t_in, t_forwarded, {"kind": kind})
            if kind == "certificate":
                # A validator that is not the author never records the
                # certify hop: give its own dump the header -> certificate edge.
                tracer.link("verify_stage", key, msg.digest)

    async def _decide(self, kind: str, msg, t_in: float):
        """(what to forward or None, outcome, t_verdict) for one header,
        vote or certificate."""
        agg_group = None
        agg_committee = None
        try:
            if kind == "header":
                msg.verify(self.committee, self.worker_cache, check_signature=False)
                items = [msg.signature_item()]
            elif kind == "vote":
                msg.verify(self.committee, check_signature=False)
                items = [msg.signature_item()]
            elif msg.is_compact:
                # Half-aggregated proof: one aggregate check for the vote
                # quorum + the embedded header's own signature. The
                # content-keyed front cache short-circuits the transcript
                # rebuild (Fiat-Shamir weights + per-signer vote digests)
                # whenever any co-hosted node — or an earlier relay copy
                # arriving at this one — already decided this exact proof
                # under this committee. Structural checks always run, so
                # the InvalidEpoch/DagError semantics below are unchanged.
                agg_committee = self.committee
                verdict = msg.cached_aggregate_verdict(agg_committee)
                items = []
                if verdict is not None:
                    msg.structural_verify(agg_committee)
                    if not verdict:
                        logger.debug(
                            "verifier stage dropped compact certificate with "
                            "known-bad aggregate proof"
                        )
                        return None, "known_bad", t_in
                    if not msg.is_genesis():
                        msg.header.verify(
                            agg_committee, self.worker_cache, check_signature=False
                        )
                        items.append(msg.header.signature_item())
                else:
                    agg_group = msg.aggregate_group(agg_committee)
                    if agg_group is not None:
                        msg.header.verify(
                            agg_committee, self.worker_cache, check_signature=False
                        )
                        items.append(msg.header.signature_item())
            else:
                items = msg.verify_items(self.committee)
                if items:
                    msg.header.verify(
                        self.committee, self.worker_cache, check_signature=False
                    )
                    items.append(msg.header.signature_item())
        except InvalidEpoch:
            # NOT this stage's call: the Core buffers exactly-one-epoch-ahead
            # messages for replay after its reconfigure notification lands
            # (the epoch-change deadlock fix) and logs the stale drops.
            # Forward RAW (un-preverified): the Core re-runs the full
            # sanitize path — including signatures, against whatever
            # committee it holds when the message is finally handled.
            return msg, "other_epoch", t_in
        except DagError as e:
            logger.debug("verifier stage dropped malformed message: %s", e)
            return None, "malformed", t_in
        if not items and agg_group is None:
            return PreVerified(msg), "nothing_to_ask", t_in
        try:
            awaitables = [self.pool.verify(pk, m, sig) for pk, m, sig in items]
            if agg_group is not None:
                awaitables.append(self.pool.verify_aggregate(*agg_group))
            results = await asyncio.gather(*awaitables)
        except Exception:
            # Backend dispatch failure with the host fallback disabled
            # (cofactored committees: a strict-rule fallback would be a
            # consensus-split hazard). Drop the message — conservative
            # rejection affects liveness, never safety — and say so.
            logger.exception(
                "verify backend failed; dropping %s (no host fallback "
                "under this committee's accept rule)",
                type(msg).__name__,
            )
            return None, "backend_failed", now()
        t_verdict = now()
        if agg_group is not None:
            # Publish the paid-for MSM verdict under the front key so
            # every later copy of this certificate — same node's relay
            # duplicates or a co-hosted peer's — skips the transcript.
            msg.record_aggregate_verdict(agg_committee, bool(results[-1]))
        if not all(results):
            logger.warning(
                "verifier stage rejected %s with bad signature",
                type(msg).__name__,
            )
            return None, "rejected", t_verdict
        return PreVerified(msg), "verified", t_verdict

    def shutdown(self) -> None:
        for t in list(self._tasks):
            t.cancel()
