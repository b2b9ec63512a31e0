"""Vectorized DAG kernels: the consensus commit walk as adjacency tensors.

Reference hot loop: /root/reference/consensus/src/utils.rs:11-101 — per-commit
pointer-chasing DFS (order_dag), frontier filtering (linked) and per-round
leader support counting — all O(window x committee) sequential work on CPU.

TPU-first redesign (SURVEY §5.8, §7.8b): the DAG window is dense tensors
  present[W, N]   uint8 — certificate exists at (round offset, authority)
  parent [W, N, N] uint8 — parent[w, a, p] = cert (w, a) links (w-1, p)
  stakes [N]      int32
with W = round-window size (>= gc_depth + slack) and N = committee size.
Reachability from any certificate is a backward scan of N x N bitwise matmuls
(MXU/VPU work, no pointer chasing); leader support is one masked dot product.
Commit traversal must not pass *through* already-committed certificates
(the DFS skip in utils.rs:86-89), so propagation masks them out via
last_committed[N].

All kernels are jit-compiled with static shapes; round offsets and indices
are traced scalars so one compilation serves every call. `TpuBullshark`
wraps them behind the exact ConsensusProtocol interface and is
equivalence-tested against the host engine on random lossy DAGs
(tests/test_dag_kernels.py).
"""

from __future__ import annotations

import functools
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import enable_compilation_cache
from . import kernel_registry

enable_compilation_cache()

from ..config import Committee
from ..stores import ConsensusStore
from ..types import Certificate, ConsensusOutput, Digest, Round, SequenceNumber
from ..consensus.state import ConsensusState


@kernel_registry.tracked_jit
def reach_mask(parent, uncommitted, start_off, start_onehot):
    """Reachability mask [W, N]: certificates reachable from the start
    certificate by walking parent links down the window, propagating only
    through uncommitted certificates (the vectorized order_dag/linked core).

    parent: uint8 [W, N, N]; uncommitted: uint8 [W, N] (present & not yet
    committed); start_off: int32 round offset; start_onehot: uint8 [N].
    """
    W, N, _ = parent.shape

    def step(frontier_above, w):
        # frontier_above = mask row already computed for offset w+1
        links = jnp.take(parent, jnp.minimum(w + 1, W - 1), axis=0)  # [N, N]
        from_above = (links.astype(jnp.int32).T @ frontier_above.astype(jnp.int32)) > 0
        here = jnp.where(
            w == start_off,
            start_onehot.astype(bool),
            jnp.where(w < start_off, from_above, False),
        )
        here = here & uncommitted[w].astype(bool)
        # Certificates below the start that are committed must not relay the
        # frontier; `here` is already masked by uncommitted, and the start
        # row is the leader itself (always explored, like the DFS root).
        return here.astype(jnp.int32), here

    ws = jnp.arange(W - 1, -1, -1)
    _, rows = lax.scan(step, jnp.zeros((N,), jnp.int32), ws)
    return rows[::-1]  # [W, N] bool, row w = offset w


@kernel_registry.tracked_jit(donate_argnums=(0, 1))
def roll_window(parent, present, shift):
    """Slide the device-resident window by `shift` rounds: drop the oldest
    `shift` rows and zero the vacated tail. One on-device shuffle instead of
    a full [W, N, N] host->device re-upload when GC advances the base.
    The window tensors are donated: the previous generation is dead the
    moment the roll dispatches, so XLA reuses its buffers instead of
    holding two [W, N, N] copies live."""
    W = present.shape[0]
    rows = jnp.arange(W, dtype=jnp.int32)
    keep = rows < (W - shift)
    present = jnp.roll(present, -shift, axis=0) * keep[:, None].astype(present.dtype)
    parent = jnp.roll(parent, -shift, axis=0) * keep[:, None, None].astype(parent.dtype)
    return parent, present


@kernel_registry.tracked_jit(donate_argnums=(0, 1))
def place_batch(parent, present, offs, idxs, rows, valid):
    """Scatter a batch of certificate placements into the device-resident
    window: for each valid slot t, present[offs[t], idxs[t]] = 1 and
    parent[offs[t], idxs[t], :] = rows[t]. Padded slots (valid=0) are
    no-ops, so power-of-two padded batches reuse one compilation per size.
    Donates the window tensors (see roll_window)."""

    def body(carry, inp):
        parent, present = carry
        off, idx, row, v = inp
        live = v.astype(bool)
        cur_row = parent[off, idx]
        cur_p = present[off, idx]
        parent = parent.at[off, idx].set(jnp.where(live, row, cur_row))
        present = present.at[off, idx].set(
            jnp.where(live, jnp.uint8(1), cur_p).astype(present.dtype)
        )
        return (parent, present), jnp.int32(0)

    (parent, present), _ = lax.scan(body, (parent, present), (offs, idxs, rows, valid))
    return parent, present


@kernel_registry.tracked_jit
def leader_support(parent, present, stakes, support_off, leader_idx):
    """Stake carried by certificates at `support_off` linking to the leader at
    the round below (bullshark.rs:66-76 / tusk.rs:66-74)."""
    links = jnp.take(parent, support_off, axis=0)[:, leader_idx]  # [N]
    voters = links.astype(bool) & jnp.take(present, support_off, axis=0).astype(bool)
    return jnp.sum(jnp.where(voters, stakes, 0))


@kernel_registry.tracked_jit
def chain_commit(parent, present, gc_depth, lc_rel, lcr_rel, offs, onehots):
    """One fused dispatch per commit event: the full chain flatten — a
    lax.scan over the chain's leaders (oldest first), each step computing
    that leader's reach mask through the certificates still uncommitted *at
    that point in the chain* and advancing the per-authority last-committed
    vector exactly as the host's state.update does between order_dag calls.

    parent [W,N,N] u8, present [W,N] u8; gc_depth i32;
    lc_rel [N] i32 = last committed round per authority, relative to the
    window base (may be negative); lcr_rel i32 = last committed round
    (max over authorities), relative; offs [K] i32 / onehots [K,N] u8 =
    chain leaders oldest-first, zero-padded (a zero onehot is a no-op slot).

    Returns masks [K,W,N] bool: post-GC-filter commit sets per leader; the
    host only gathers certificates and appends outputs from them.
    """
    W, N, _ = parent.shape
    rows = jnp.arange(W, dtype=jnp.int32)

    def per_leader(carry, inp):
        lc, lcr = carry
        off, onehot = inp
        uncommitted = (present.astype(bool) & (rows[:, None] > lc[None, :])).astype(
            jnp.uint8
        )
        mask = reach_mask(parent, uncommitted, off, onehot)  # [W, N] bool
        # order_dag's GC filter (utils.rs:93-97): drop certificates whose
        # round has fallen gc_depth behind the pre-flatten committed round.
        keep = mask & (rows[:, None] + gc_depth >= lcr)
        committed_rounds = jnp.max(
            jnp.where(keep, rows[:, None], jnp.int32(-(2**30))), axis=0
        )
        lc = jnp.maximum(lc, committed_rounds)
        lcr = jnp.maximum(lcr, jnp.max(committed_rounds))
        return (lc, lcr), keep

    _, masks = lax.scan(per_leader, (lc_rel, lcr_rel), (offs, onehots))
    return masks


# (W, N, auth-shards) chain_commit shapes already queued for background
# compilation in this process (prewarm dedupe across engine instances).
_PREWARMED_SHAPES: set[tuple[int, int, int]] = set()
# Live prewarm threads, joined at interpreter exit: a daemon thread frozen
# inside XLA C++ during Python finalization aborts the whole process
# ("FATAL: exception not rethrown"), so exit must wait for in-flight
# compiles. Long-lived nodes finish them long before shutdown; one-shot
# tools pass prewarm=False and never start them.
_PREWARM_THREADS: list = []
_PREWARM_ATEXIT = False


def _prune_prewarm_threads() -> None:
    """Drop finished threads so a long-lived node doesn't accumulate one
    Thread object per window doubling."""
    _PREWARM_THREADS[:] = [t for t in _PREWARM_THREADS if t.is_alive()]


def _join_prewarm_threads(grace: float = 60.0) -> int:
    # Bounded join: waiting forever would let a compile stuck in XLA C++
    # block process exit outright. A thread still alive after the grace
    # is logged and abandoned — a daemon thread, so it cannot keep the
    # interpreter alive.
    deadline = time.monotonic() + grace
    for t in list(_PREWARM_THREADS):
        t.join(timeout=max(0.0, deadline - time.monotonic()))
        if t.is_alive():
            logging.getLogger("narwhal.tpu.dag").warning(
                "prewarm compile thread %s did not finish within the exit "
                "join window; abandoning it",
                t.name,
            )
    _prune_prewarm_threads()
    return len(_PREWARM_THREADS)


def join_prewarm_threads(grace: float = 60.0) -> int:
    """Bounded-join every in-flight background window compile; returns how
    many were still running when the grace ran out. Called from
    `PrimaryNode.shutdown` (off-loop) so a node's prewarm threads cannot
    outlive it and contend with a successor's foreground traces for XLA's
    compiler locks — the PR-1 stabilization failure mode, previously
    handled only by the atexit hook (process exit), not node teardown."""
    return _join_prewarm_threads(grace)


class DagWindow:
    """Host-managed ring of the last W rounds as dense arrays, with the
    digest <-> (round, authority) maps the tensors can't hold. This is the
    'long context' of the system: rounds are the sequence axis, the committee
    the width (SURVEY §5.8).

    `pad_authorities_to` widens the committee axis of the tensors with
    always-absent slots (present=0, stake=0) so the axis divides evenly
    across a device mesh's 'auth' dimension; padding is invisible to the
    protocol — padded slots never hold certificates, relay reachability or
    carry stake."""

    def __init__(
        self,
        committee: Committee,
        window: int = 64,
        pad_authorities_to: int | None = None,
        device_resident: bool = False,
    ):
        self.committee = committee
        n = committee.size()
        self.N = max(n, pad_authorities_to or 0)
        self.W = window
        self.round_base: Round = 0
        self.present = np.zeros((self.W, self.N), np.uint8)
        self.parent = np.zeros((self.W, self.N, self.N), np.uint8)
        stakes = np.zeros((self.N,), np.int32)
        stakes[:n] = np.asarray(committee.stakes_array(), np.int32)
        self.stakes = stakes
        self.certs: dict[tuple[Round, int], Certificate] = {}
        self.digest_pos: dict[Digest, tuple[Round, int]] = {}
        # Device-resident mirror (device_resident=True): the tensors live on
        # device between dispatches; inserts buffer as pending coordinates
        # and apply as ONE batched on-device scatter at the next
        # device_view(), window slides as one on-device roll. The hot read
        # path therefore never re-uploads the [W, N, N] adjacency.
        self._dev_resident = device_resident
        self._dev: tuple | None = None
        self._dev_base: Round = 0
        self._dev_stale = True
        self._dev_pending: list[tuple[Round, int]] = []
        # Genesis certificates occupy round 0.
        for cert in Certificate.genesis(committee):
            self._place(cert)

    def _off(self, round: Round) -> int:
        return round - self.round_base

    def _place(self, cert: Certificate) -> None:
        idx = self.committee.index_of(cert.origin)
        off = self._off(cert.round)
        self.present[off, idx] = 1
        self.certs[(cert.round, idx)] = cert
        self.digest_pos[cert.digest] = (cert.round, idx)
        for pd in cert.header.parents:
            pos = self.digest_pos.get(pd)
            if pos is not None and pos[0] == cert.round - 1:
                self.parent[off, idx, pos[1]] = 1
        if self._dev_resident:
            self._dev_pending.append((cert.round, idx))

    def insert(self, cert: Certificate, keep_floor: Round) -> bool:
        """Add a certificate; slides the window forward (dropping only rounds
        below keep_floor, the GC bound) or grows it when commits lag behind
        round production. Returns False only for certificates below the
        already-GC'd base."""
        if cert.round < self.round_base:
            return False
        while cert.round - self.round_base >= self.W:
            target = cert.round - self.W + 1
            if target <= keep_floor:
                self.slide_to(target)
            elif keep_floor > self.round_base:
                self.slide_to(keep_floor)
                self._grow()
            else:
                self._grow()
        self._place(cert)
        return True

    def _grow(self) -> None:
        """Double W (recompiles the jitted kernels for the new static shape —
        rare, only when the uncommitted span outgrows the window)."""
        new_w = self.W * 2
        present = np.zeros((new_w, self.N), np.uint8)
        parent = np.zeros((new_w, self.N, self.N), np.uint8)
        present[: self.W] = self.present
        parent[: self.W] = self.parent
        self.present, self.parent, self.W = present, parent, new_w
        self._dev_stale = True  # shape change: next device_view re-uploads

    def slide_to(self, new_base: Round) -> None:
        shift = new_base - self.round_base
        if shift <= 0:
            return
        if shift >= self.W:
            self.present[:] = 0
            self.parent[:] = 0
        else:
            self.present[:-shift] = self.present[shift:]
            self.present[-shift:] = 0
            self.parent[:-shift] = self.parent[shift:]
            self.parent[-shift:] = 0
        dropped = [(r, i) for (r, i) in self.certs if r < new_base]
        for key in dropped:
            cert = self.certs.pop(key)
            self.digest_pos.pop(cert.digest, None)
        self.round_base = new_base

    def cert_at(self, round: Round, idx: int) -> Certificate | None:
        return self.certs.get((round, idx))

    # -- device residency --------------------------------------------------

    def device_view(self):
        """The (parent, present) tensors resident on device, synced to the
        host mirror. Steady state is incremental: pending placements apply
        as one power-of-two-padded `place_batch` scatter and a slid base as
        one `roll_window` shuffle — zero [W, N, N] host->device traffic on
        the hot path. A full upload happens only on first use and after
        `_grow` (shape change)."""
        import jax.numpy as jnp

        if self._dev is None or self._dev_stale:
            self._dev = (jnp.asarray(self.parent), jnp.asarray(self.present))
            self._dev_base = self.round_base
            self._dev_stale = False
            self._dev_pending.clear()
            return self._dev
        parent, present = self._dev
        if self.round_base != self._dev_base:
            parent, present = roll_window(
                parent, present, np.int32(self.round_base - self._dev_base)
            )
            self._dev_base = self.round_base
        if self._dev_pending:
            # Rows come from the host mirror at sync time, so a placement's
            # final parent links are always what lands on device; entries
            # GC'd below the base since they were buffered are dropped.
            pend = [
                (r - self.round_base, i)
                for (r, i) in self._dev_pending
                if r >= self.round_base
            ]
            self._dev_pending.clear()
            if pend:
                k = len(pend)
                kpad = 1 if k <= 1 else 1 << (k - 1).bit_length()
                offs = np.zeros((kpad,), np.int32)
                idxs = np.zeros((kpad,), np.int32)
                rows = np.zeros((kpad, self.N), np.uint8)
                valid = np.zeros((kpad,), np.uint8)
                for t, (off, idx) in enumerate(pend):
                    offs[t] = off
                    idxs[t] = idx
                    rows[t] = self.parent[off, idx]
                    valid[t] = 1
                parent, present = place_batch(
                    parent, present, offs, idxs, rows, valid
                )
        self._dev = (parent, present)
        return self._dev


class TpuBullshark:
    """Bullshark with the DAG walks on device. Drop-in for
    consensus.Bullshark (same process_certificate signature/semantics,
    equivalence-tested); the host retains only bookkeeping and the final
    index->certificate gather.

    With `mesh` set (a jax.sharding.Mesh containing an 'auth' axis) the
    production chain_commit dispatch shards the committee axis of the DAG
    tensors across devices — parent [W,N,N] over its link axis, present
    [W,N] and last_committed [N] over N — exactly the layout
    __graft_entry__.dryrun_multichip validates; XLA inserts the ICI
    collectives for the per-round frontier psum (SURVEY §5.8: the window as
    a first-class sharding axis). The committee axis is padded to a
    multiple of the 'auth' size with always-absent slots."""

    def __init__(
        self,
        committee: Committee,
        store: ConsensusStore | None,
        gc_depth: Round,
        leader_fn=None,
        window: int | None = None,
        mesh=None,
        prewarm: bool | None = None,
    ):
        self.committee = committee
        self.store = store
        self.gc_depth = gc_depth
        self._leader_fn = leader_fn
        self.mesh = mesh
        # Unmeshed engines keep the window resident on device (the meshed
        # dispatch places operands itself via in_shardings, so it keeps the
        # host mirror as its operand source).
        self.win = DagWindow(
            committee, window or (gc_depth + 14),
            pad_authorities_to=self._pad_for(committee),
            device_resident=(mesh is None),
        )
        self._chain_commit = self._build_dispatch()
        self._dispatch_W = self.win.W
        if prewarm is None:
            # Default only — an explicit prewarm=True/False always wins.
            # Background compiles contend with foreground jit traces for
            # XLA's compiler locks; on a single-core host that serializes
            # every later trace behind a minutes-long compile (and has
            # wedged concurrent traces outright), so test suites on such
            # hosts export NARWHAL_TPU_PREWARM=0.
            prewarm = os.environ.get("NARWHAL_TPU_PREWARM", "1") != "0"
        self._prewarm_enabled = prewarm
        self._prewarm_threads: list = []
        if prewarm:
            # Compile the NEXT window size ahead of need: _grow() doubles W
            # mid-stream precisely when the node is already behind on
            # commits, and an uncached XLA compile there stalls the commit
            # path for seconds-to-minutes. The background compile writes
            # the persistent compilation cache, so the post-growth dispatch
            # is a (fast) cache deserialization instead of a compile.
            self._prewarm(self.win.W * 2)

    @property
    def _warmed(self):
        return _PREWARMED_SHAPES

    def _prewarm(self, W: int) -> None:
        # Deduped process-wide: 20 in-process engines must not spawn 20
        # concurrent compiles of the identical shape.
        key = (W, self.win.N, self.mesh.shape["auth"] if self.mesh else 0)
        if key in _PREWARMED_SHAPES:
            return
        _PREWARMED_SHAPES.add(key)
        import threading

        def compile_ahead():
            try:
                N = self.win.N
                for kpad in (1, 2, 4):  # steady state + catch-up chain buckets
                    self._chain_commit.lower(
                        np.zeros((W, N, N), np.uint8),
                        np.zeros((W, N), np.uint8),
                        np.int32(0),
                        np.zeros((N,), np.int32),
                        np.int32(-1),
                        np.zeros((kpad,), np.int32),
                        np.zeros((kpad, N), np.uint8),
                    ).compile()
            except Exception:  # pragma: no cover - warmup is best-effort
                import logging

                # A failed prewarm must not permanently disable
                # prewarming this shape for the process.
                _PREWARMED_SHAPES.discard(key)
                logging.getLogger("narwhal.tpu").warning(
                    "window prewarm failed for %s", key, exc_info=True
                )

        global _PREWARM_ATEXIT
        if not _PREWARM_ATEXIT:
            import atexit

            atexit.register(_join_prewarm_threads)
            _PREWARM_ATEXIT = True
        _prune_prewarm_threads()
        self._prewarm_threads = [t for t in self._prewarm_threads if t.is_alive()]
        t = threading.Thread(target=compile_ahead, daemon=True)
        t.start()
        self._prewarm_threads.append(t)
        _PREWARM_THREADS.append(t)

    def _pad_for(self, committee: Committee) -> int | None:
        """Committee-axis width the mesh requires: the next multiple of the
        'auth' axis size (None when unmeshed)."""
        if self.mesh is None:
            return None
        auth = self.mesh.shape["auth"]
        return -(-committee.size() // auth) * auth

    def _build_dispatch(self):
        """The chain_commit entry point: the module-level tracked kernel on
        a single device, or the REGISTRY's mesh-sharded wrapper when a mesh
        is configured — one jit per (chain_commit, mesh shape) process-wide,
        so N co-hosted engines (and every window regrowth) share one
        compiled program per W instead of re-jitting. Scalars and the small
        per-leader operands are replicated (empty PartitionSpec) so no
        operand ever falls back to the default backend's device placement."""
        if self.mesh is None:
            return chain_commit
        from jax.sharding import PartitionSpec as P

        return kernel_registry.sharded(
            chain_commit,
            self.mesh,
            in_specs=(
                P(None, None, "auth"),  # parent [W, N, N]: link axis
                P(None, "auth"),  # present [W, N]
                None,  # gc_depth scalar
                P("auth"),  # lc_rel [N]
                None,  # lcr_rel scalar
                None,  # offs [K]
                P(None, None),  # onehots [K, N]
            ),
            out_specs=P(None, None, "auth"),
        )

    def recover(self, state: ConsensusState) -> None:
        """Rebuild the device window from a recovered host state (the
        consensus runner's ConsensusState.new_from_store) so a restarted node
        resumes committing from the on-disk DAG. Insertion is round-ascending
        because parent links resolve against already-placed digests."""
        keep_floor = max(0, state.last_committed_round - self.gc_depth)
        for round in sorted(state.dag):
            for _, cert in state.dag[round].values():
                self.win.insert(cert, keep_floor)

    # -- leader election --------------------------------------------------
    def _leader_index(self, round: Round, dag) -> int | None:
        if self._leader_fn is not None:
            entry = self._leader_fn(self.committee, round, dag)
            if entry is None:
                return None
            return self.committee.index_of(entry[1].origin)
        name = self.committee.leader(round)
        idx = self.committee.index_of(name)
        off = self.win._off(round)
        # DagWindow is mutated only by the Dag task's ingest/flush, never
        # mid-yield; consensus reads tolerate a one-flush-stale window
        # (absent leader just means "not present yet" — retried next round).
        if 0 <= off < self.win.W and self.win.present[off, idx]:  # lint: allow(multi-task-mutation)
            return idx
        return None

    # -- host bookkeeping -------------------------------------------------
    def _linked_np(self, round: Round, idx: int, prev_round: Round, prev_idx: int) -> bool:
        """Host-side chain linkage between consecutive even-round leaders
        (utils.rs:40-53 `linked`): a 2-round frontier propagation over the
        numpy parent mirror — O(N^2) bookkeeping, not the hot walk."""
        frontier = np.zeros((self.win.N,), bool)
        frontier[idx] = True
        for rr in range(round, prev_round, -1):
            off = self.win._off(rr)
            if not (0 <= off < self.win.W):
                return False
            # Same discipline as above: Dag-task-only writes, stale-tolerant
            # reads (missing links fail toward "not linked", retried later).
            links = self.win.parent[off]  # lint: allow(multi-task-mutation)
            frontier = (links[frontier].any(axis=0)) & self.win.present[
                self.win._off(rr - 1)
            ].astype(bool)
            if not frontier.any():
                return False
        return bool(frontier[prev_idx])

    def _lc_rel(self, state: ConsensusState) -> np.ndarray:
        lc = np.zeros((self.win.N,), np.int32)
        for pk, r in state.last_committed.items():
            lc[self.committee.index_of(pk)] = r
        return lc - np.int32(self.win.round_base)

    # -- protocol ---------------------------------------------------------
    def process_certificate(
        self,
        state: ConsensusState,
        consensus_index: SequenceNumber,
        certificate: Certificate,
    ) -> list[ConsensusOutput]:
        dispatch = self._ingest_and_dispatch(state, certificate)
        if dispatch is None:
            return []
        masks_dev, K = dispatch
        # Device->host readback of the commit masks (blocking here; the
        # async variant overlaps it with the node's event loop).
        masks = np.asarray(masks_dev)  # [Kpad, W, N] bool, post-GC commit sets
        return self._materialize(state, consensus_index, masks, K)

    async def process_certificate_async(
        self,
        state: ConsensusState,
        consensus_index: SequenceNumber,
        certificate: Certificate,
    ) -> list[ConsensusOutput]:
        """process_certificate with the device readback awaited off-thread so
        the node's event loop (workers, proposer, RPC) keeps running during
        the device->host round trip. Used by the Consensus runner; events
        stay serialized because the runner awaits each certificate in order."""
        import asyncio

        dispatch = self._ingest_and_dispatch(state, certificate)
        if dispatch is None:
            return []
        masks_dev, K = dispatch
        loop = asyncio.get_running_loop()
        masks = await loop.run_in_executor(None, np.asarray, masks_dev)
        return self._materialize(state, consensus_index, masks, K)

    def _commit_coords(self, round: Round) -> tuple[Round, Round] | None:
        """Bullshark rule (bullshark.rs:47-82): on a round-r+1 certificate
        the candidate leader sits at even round r, supported by round r+1.
        Returns (leader_round, support_round) or None when `round` cannot
        trigger a commit."""
        r = round - 1
        if r % 2 != 0 or r < 2:
            return None
        return r, round

    def _ingest(self, state: ConsensusState, certificate: Certificate) -> None:
        """Record one certificate in the host mirror + window (no dispatch)."""
        state.add(certificate)  # host mirror for recovery parity
        keep_floor = max(0, state.last_committed_round - self.gc_depth)
        if not self.win.insert(certificate, keep_floor):
            raise RuntimeError(
                f"round {certificate.round} outside DAG window "
                f"(base {self.win.round_base}, W {self.win.W})"
            )

    def _refresh_dispatch(self) -> None:
        if self.win.W != self._dispatch_W:
            # The window grew (or slid through a regrow): re-derive the
            # dispatch from the kernel registry instead of trusting the
            # wrapper captured at construction. Same mesh -> the registry
            # returns the same process-wide sharded program, so a meshed
            # engine keeps its 'auth'-partitioned layouts across growth
            # rather than silently re-tracing an unsharded (replicated)
            # kernel; tests/test_dag_kernels.py pins the invariant.
            self._chain_commit = self._build_dispatch()
            self._dispatch_W = self.win.W
        if self._prewarm_enabled:
            # Keep one doubling ahead of the current window size.
            self._prewarm(self.win.W * 2)

    def _eval_commit(self, state: ConsensusState, round: Round):
        """Evaluate the commit rule for a round-`round` certificate against
        SETTLED state and dispatch the fused chain walk when it commits.
        Returns (device masks, chain length) or None."""
        coords = self._commit_coords(round)
        if coords is None:
            return None
        leader_round, support_round = coords
        if leader_round <= state.last_committed_round:
            return None
        leader_idx = self._leader_index(leader_round, state.dag)
        if leader_idx is None:
            return None
        return self._dispatch_commit(state, leader_round, support_round, leader_idx)

    def _ingest_and_dispatch(self, state: ConsensusState, certificate: Certificate):
        """Shared pre-readback half of process_certificate: record the
        certificate, evaluate the commit rule on the host mirror, and — when
        this certificate commits a leader — dispatch the fused chain walk.
        Returns (device masks, chain length) or None."""
        self._ingest(state, certificate)
        self._refresh_dispatch()
        return self._eval_commit(state, certificate.round)

    def process_batch(
        self,
        state: ConsensusState,
        consensus_index: SequenceNumber,
        certificates: list[Certificate],
    ) -> list[ConsensusOutput]:
        """Batched process_certificate: all inserts land as ONE device
        scatter (the window syncs once, at the first commit dispatch), the
        commit rule is then evaluated per trigger in arrival order, and
        each commit event's mask readback is deferred one event so it
        overlaps the next event's host bookkeeping.

        The output sequence is IDENTICAL to per-certificate calls on the
        same (causally ordered) stream: Bullshark/Tusk re-evaluate the
        commit rule on every support-round certificate, a leader's reach
        mask covers only rounds at or below it, and chain linkage walks
        the LEADER's ancestry (present before the leader under causal
        delivery) — so batching arrivals can move where a commit is
        yielded, never its content or order. Each event still materializes
        before the next event's rule evaluation: last_committed gates both
        the rule and the GC filter."""
        for cert in certificates:
            self._ingest(state, cert)
        self._refresh_dispatch()
        outputs: list[ConsensusOutput] = []
        pending = None
        for cert in certificates:
            if self._commit_coords(cert.round) is None:
                continue
            if pending is not None:
                masks_dev, K = pending
                outs = self._materialize(
                    state, consensus_index, np.asarray(masks_dev), K
                )
                consensus_index += len(outs)
                outputs.extend(outs)
            pending = self._eval_commit(state, cert.round)
        if pending is not None:
            masks_dev, K = pending
            outputs.extend(
                self._materialize(state, consensus_index, np.asarray(masks_dev), K)
            )
        return outputs

    async def process_batch_async(
        self,
        state: ConsensusState,
        consensus_index: SequenceNumber,
        certificates: list[Certificate],
    ) -> list[ConsensusOutput]:
        """process_batch with each deferred readback awaited off-thread —
        the Consensus runner's greedy-drain path, so a certificate burst
        costs one batched insert and the loop keeps serving RPC during
        every device->host round trip."""
        import asyncio

        loop = asyncio.get_running_loop()
        for cert in certificates:
            self._ingest(state, cert)
        self._refresh_dispatch()
        outputs: list[ConsensusOutput] = []
        pending = None
        for cert in certificates:
            if self._commit_coords(cert.round) is None:
                continue
            if pending is not None:
                masks_dev, K = pending
                masks = await loop.run_in_executor(None, np.asarray, masks_dev)
                outs = self._materialize(state, consensus_index, masks, K)
                consensus_index += len(outs)
                outputs.extend(outs)
            pending = self._eval_commit(state, cert.round)
        if pending is not None:
            masks_dev, K = pending
            masks = await loop.run_in_executor(None, np.asarray, masks_dev)
            outputs.extend(self._materialize(state, consensus_index, masks, K))
        return outputs

    def _dispatch_commit(self, state, r, support_round, leader_idx):
        """Quorum pre-check + chain detection on the host mirror (cheap
        bookkeeping), then ONE fused device dispatch for every flatten walk
        of the commit event. `r` is the leader's round; support is counted
        among `support_round` certificates linking it. Returns (device
        masks, chain length) or None."""
        # Support quorum pre-check (one column read): a device readback costs
        # a full round trip, so dispatch only when this certificate commits.
        off_r = self.win._off(support_round)
        voters = self.win.parent[off_r, :, leader_idx].astype(bool) & self.win.present[
            off_r
        ].astype(bool)
        support = int(self.win.stakes[voters].sum())
        if support < self.committee.validity_threshold():
            return None

        # Chain of linked leaders (order_leaders): consecutive-leader linkage
        # spans only two rounds, so it is cheap host bookkeeping; the O(W*N^2)
        # flatten walks run on device in ONE fused dispatch.
        chain: list[tuple[Round, int]] = [(r, leader_idx)]
        cur_round, cur_idx = r, leader_idx
        for lr in range(r - 2, state.last_committed_round + 1, -2):
            prev_idx = self._leader_index(lr, state.dag)
            if prev_idx is None:
                continue
            if self._linked_np(cur_round, cur_idx, lr, prev_idx):
                chain.append((lr, prev_idx))
                cur_round, cur_idx = lr, prev_idx

        # Pad the chain to power-of-two bucket lengths so one compilation
        # serves steady state (K=1) and catch-up bursts alike.
        chain = list(reversed(chain))  # oldest first, scan order
        K = len(chain)
        Kpad = 1
        while Kpad < K:
            Kpad *= 2
        offs = np.zeros((Kpad,), np.int32)
        onehots = np.zeros((Kpad, self.win.N), np.uint8)
        for i, (lr, lidx) in enumerate(chain):
            offs[i] = self.win._off(lr)
            onehots[i, lidx] = 1

        # Meshed: numpy operands, placed per in_shardings. Unmeshed: the
        # device-resident window, so the commit walk uploads nothing but
        # the per-event scalars and the [Kpad, N] leader onehots.
        if self.mesh is None:
            parent_op, present_op = self.win.device_view()
        else:
            parent_op, present_op = self.win.parent, self.win.present
        masks_dev = self._chain_commit(
            parent_op,
            present_op,
            np.int32(self.gc_depth),
            self._lc_rel(state),
            np.int32(state.last_committed_round - self.win.round_base),
            offs,
            onehots,
        )
        # Start the device->host copy as soon as the walk finishes so the
        # materialization readback finds the masks already local.
        masks_dev.copy_to_host_async()
        return masks_dev, K

    def _materialize(
        self, state: ConsensusState, consensus_index: SequenceNumber, masks, K: int
    ) -> list[ConsensusOutput]:
        """Gather certificates from the per-leader commit masks, update the
        host recovery state and persist, in canonical (round, origin) order."""
        sequence: list[ConsensusOutput] = []
        for k in range(K):
            order = np.argwhere(masks[k])  # ascending (offset, authority)
            for off, aidx in order:
                cert = self.win.cert_at(self.win.round_base + int(off), int(aidx))
                if cert is None:
                    continue
                state.update(cert, self.gc_depth)
                sequence.append(
                    ConsensusOutput(certificate=cert, consensus_index=consensus_index)
                )
                consensus_index += 1
                if self.store is not None:
                    self.store.write_consensus_state(
                        state.last_committed, consensus_index - 1, cert.digest
                    )
        return sequence

    def update_committee(self, new_committee: Committee) -> None:
        self.committee = new_committee
        self.win = DagWindow(
            new_committee,
            self.win.W,
            pad_authorities_to=self._pad_for(new_committee),
            device_resident=(self.mesh is None),
        )


class TpuTusk(TpuBullshark):
    """Tusk with the DAG walks on device: identical machinery to
    TpuBullshark, the asynchronous commit rule (tusk.rs:47-82): a round-r
    certificate (r-1 even, r-1 >= 4) makes the leader at round r-3 a commit
    candidate, supported by its children at round r-2 carrying >= f+1
    stake. Drop-in for consensus.Tusk."""

    def _commit_coords(self, round: Round) -> tuple[Round, Round] | None:
        r = round - 1
        if r % 2 != 0 or r < 4:
            return None
        return r - 2, r - 1
