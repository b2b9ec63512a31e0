"""Causal commit tracing and the per-node flight recorder.

The reference debugs its pipeline with per-crate Prometheus metrics; those
aggregate. What the two diagnosis-starved problems in ROADMAP.md (the
`test_partial_committee_change` contention flake and the multi-chip
host-epilogue cap) both need is the *causal* record: where did one specific
certificate's time go, across roles and across the host/device boundary.

This module is that record, in two bounded pieces:

* **Spans** — the per-certificate waterfall. The trace context is the
  digest chain the protocol already carries on the wire (batch digest →
  header digest → certificate digest), so tracing adds ZERO wire bytes:
  `link` events recorded where the chain hops (batch digests folded into a
  proposed header, a header certified) let `waterfall()` stitch per-stage
  spans (seal / propose / certify / commit / execute, plus verify_stage
  from primary/verifier_stage.py) into one end-to-end timeline per
  certificate, joining across the dumps of every node that touched it.
  Span timestamps come from `clock.now()` — the running loop's time — so
  under simnet's virtual clock a seeded scenario produces a bit-identical
  traced event log on every run.

* **Flight recorder** — a bounded ring (`collections.deque`) of structured
  events per node: span closes, causal links, and `instant` events
  (channel-occupancy snapshots, backpressure/pacing state transitions)
  that record regardless of the trace switch because they are off the hot
  path and are exactly what a post-mortem needs. `dump()` is a
  self-contained JSON-able dict; `on_anomaly()` archives every live
  tracer's ring into a bounded module-level archive (and optionally to
  NARWHAL_FLIGHT_DIR) so commit-stall detectors, simnet oracles and the
  pytest failure hook can attach the evidence to the failure they report.

* **Process flight ring** — one more bounded ring, module-level, for
  records whose source is no node: the shared verify service (`flush`,
  `wake`), the verifier stage (`stage`), the commit walk (`walk`), the
  core (`certify`), the loop heartbeat (`lag`) and its account (`loop`,
  `owner`), the kernel registry (`compile`), the WAL (`wal_flush`) and
  the workers' first submission (`ingest_first`). It records always, like
  `instant`: every record is per flush, per protocol message, per walk,
  per stall or per kept stretch, never per signature or per frame.
  `flight(kind, ...)` appends one tuple, laid
  out as `FLIGHT_FIELDS` says (this module owns the layout; readers go by
  field name); `flight_dump()` copies it out with the (`time.monotonic()`,
  `time.time_ns()`) pair taken when the generation started, which lays
  the records on any wall-clock timeline (a profiler's). Record times are
  `clock.now()` on a loop and `time.monotonic()` on a thread: one clock
  outside simnet. `annotation(name, **meta)` is the same sites' mark on
  the profiler's own clock: a `jax.profiler.TraceAnnotation` when JAX is
  loaded, nothing otherwise. A mark around an `await` (the commit walk,
  the executor) is as wide as the coroutine's wall time, other tasks'
  turns on the loop included: what held the loop meanwhile is read from
  the `lag` records, not from the mark's width.

* **Loop account** — while a loop has a heartbeat, every callback it runs
  (`asyncio.events.Handle._run`: task steps, `call_soon`/`call_later`
  callbacks, the selector's readers and writers) is timed on the loop's
  own thread and charged to its owner: the task's name where a site set
  one, else the coroutine's code; a plain callback's code. A site
  re-labels the rest of the running callback with `charge(label)`, or
  takes a synchronous stretch out of it with `nested(label, t0)`. The
  account keeps a stretch of `ACCOUNT_KEEP_S` and rests `ACCOUNT_REST_S`
  (`Handle._run` is asyncio's own meanwhile, and `ACCOUNTING` is false: a
  site reads that flag before it takes a clock reading); at the end of a
  kept stretch the heartbeat writes what it held: one `loop` record and the
  owners' rows, each under its family (`OWNER_FAMILIES`).

Overhead discipline: span recording on the hot path is gated by
`Tracer.enabled` (NARWHAL_TRACE, default off) — when disabled the only cost
at an instrumented site is one attribute read and a falsy branch. When
enabled, `sampled(key)` decides deterministically from the digest bytes
(NARWHAL_TRACE_SAMPLE in (0,1]), so a sampled run traces the SAME
certificates on every node — partial waterfalls never happen — and a
seeded simnet replay samples identically.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import itertools
import json
import logging
import os
import sys
import threading
import time
import weakref

from .clock import now as _now
from .metrics import Counter, Histogram

# Ordered ring of recently archived dumps (nodes that shut down, anomaly
# snapshots): bounded so a long test session cannot grow without limit.
ARCHIVE: collections.deque = collections.deque(maxlen=64)

# Every constructed tracer, weakly — the dump surface for "all hosted
# nodes" consumers (conftest failure hook, anomaly triggers) without tying
# tracer lifetime to this module.
_LIVE: "weakref.WeakSet[Tracer]" = weakref.WeakSet()

# Cluster-incarnation generation: successive in-process clusters reuse
# node labels AND certificate digests (seeded fixtures), so a live-tracer
# dump that mixed incarnations would stitch spans from a PRIOR cluster
# into the current one (the diagnosed test_live_cluster_scrape flake).
# Each tracer records the generation current at its construction;
# `live_dumps`/`on_anomaly` only touch the current generation. Cluster
# boot bumps this via `new_generation()`.
_GENERATION: int = 0


# The process flight ring. Appends come from the loop thread(s) and from
# the verify service's collect thread: `deque.append` of one
# tuple is atomic, and readers copy on read (`flight_dump`), as
# `Tracer.dump` does. Sized to outlast a traced benchmark run with room to
# spare: four co-hosted validators write ~380 records a second under the
# busiest cell's load and ~520 when idle (rounds spin faster), and the
# harness keeps the committee alive for the minute or two its profiler
# takes to write a trace before any reader runs (PERF.md section 6, PR 26).
# Full, it holds about 50 MB.
FLIGHT_RING = 1 << 18
FLIGHT: collections.deque = collections.deque(maxlen=FLIGHT_RING)
# (time.monotonic(), time.time_ns()) at generation start: what lays the
# ring's monotonic stamps on a wall-clock timeline.
_FLIGHT_ANCHOR: tuple[float, int] = (time.monotonic(), time.time_ns())


def new_generation() -> int:
    """Start a new tracer incarnation; previously constructed tracers
    become invisible to `live_dumps`/`on_anomaly` (their rings stay
    reachable through direct references and the archive). The process
    flight ring starts empty: each in-process cluster's window is its own."""
    global _GENERATION, _FLIGHT_ANCHOR
    _GENERATION += 1
    FLIGHT.clear()
    _FLIGHT_ANCHOR = (time.monotonic(), time.time_ns())
    return _GENERATION


# The records' layout, owned here: kind -> the fields that follow the kind,
# in order. A record is a namedtuple whose first field is `kind` (a plain
# tuple to `deque.append` and to JSON), so the sites write positionally and
# the readers (chipbench/readers/, tools/perf/flight_profile.py) read by
# name; a site that passes the wrong number of fields raises where it stands.
# Times are seconds on `time.monotonic()`.
FLIGHT_FIELDS = {
    # tpu/verifier.py VerifyService, one per flush: lane singles|groups;
    # useful rows = signatures, or 2 per signer of a proof; padded = the
    # buckets its submit dispatched; t_oldest = the first entry's enqueue;
    # wait_sum = sum(seal - enqueue) over entries; t_dispatched = submit
    # returned; t_posted = collect returned and the verdicts went to the
    # waiters' loops; failure = None or what failed.
    "flush": "seq lane entries useful padded t_oldest wait_sum t_seal t_dispatched t_posted failure",
    # the same flush's waiters, once all resumed: posted -> resumed
    "wake": "seq entries lag_sum lag_max t_posted",
    # primary/verifier_stage.py, one per header|vote|certificate (msg);
    # key = the header digest the message is about
    "stage": "msg key node t_in t_verdict t_forwarded outcome",
    # primary/core.py at certify_timer.stop: the certify span of header `key`
    "certify": "key node t0 t1",
    # consensus/runner.py, one per call into the ordering engine
    "walk": "node certs outputs t_start t_done",
    # the loop heartbeat below: a late wake, or a second of quiet ones
    "lag": "due woke quiet quiet_sum",
    # the loop account below, one per stretch it keeps (ACCOUNT_KEEP_S in
    # every ACCOUNT_KEEP_S + ACCOUNT_REST_S) and loop with a heartbeat (loop
    # = the account's ordinal in this process; t0, t1 = the stretch kept):
    # callbacks run, their summed
    # wall time, the CPU time of the loop's thread over the same stretch
    # (busy_s - cpu_s = what the thread stood off a core inside callbacks,
    # less what the loop itself burnt between them), the longest single
    # stretch of one owner and who that was
    "loop": "loop t0 t1 handles busy_s cpu_s longest_s longest_owner",
    # the same stretch by owner: the OWNER_ROWS owners with the most seconds
    # and every label a site passed to `charge` or `nested`, then one row
    # `rest` per family for the others, so that a stretch's rows
    # sum to its busy_s; calls = callbacks (for a label: segments opened),
    # longest = the owner's longest single stretch
    "owner": "loop t1 owner family calls seconds longest",
    # tpu/kernel_registry.py, a first dispatch of (kernel, shapes)
    "compile": "kernel shapes t wall_s",
    # tpu/kernel_registry.py, a persisted kernel's first dispatch at a shape:
    # what the export on disk gave (hit | miss | stale | unreadable) and the
    # seconds spent loading it or, on anything but a hit, tracing and
    # exporting; the `compile` record that follows holds the whole wall
    "kernel_load": "kernel shapes outcome t seconds",
    # storage.py StorageStats.record_group, one per fused WAL flush
    "wal_flush": "ops flush_s t",
    # worker/worker.py, a worker's first non-empty submission
    "ingest_first": "node t",
}
FLIGHT_RECORD = {
    kind: collections.namedtuple(kind, "kind " + fields) for kind, fields in FLIGHT_FIELDS.items()
}


def flight(kind: str, *fields) -> None:
    """Append one record to the process flight ring. Always on: callers
    stay off the per-signature and per-frame path."""
    # One ring for the process by design: its writers are whatever has no
    # node of its own or spans several; `deque.append` of one tuple is atomic.
    FLIGHT.append(FLIGHT_RECORD[kind](kind, *fields))  # lint: allow(multi-task-mutation)


def flight_dump(max_events: int | None = None) -> dict:
    """Self-contained, JSON-able snapshot of the process flight ring; it
    outlives `Cluster.shutdown()`, so a reader can take it after the drain."""
    events = list(FLIGHT)
    if max_events is not None and max_events > 0:
        events = events[-max_events:]
    return {
        "node": "process",
        "generation": _GENERATION,
        "anchor": {"monotonic": _FLIGHT_ANCHOR[0], "time_ns": _FLIGHT_ANCHOR[1]},
        "ring_capacity": FLIGHT.maxlen,
        "events": events,
    }


_NO_ANNOTATION = contextlib.nullcontext()


def annotation(name: str, **meta):
    """A mark on the profiler's own clock around a host step
    (`narwhal/verify_submit`, `narwhal/verify_collect`,
    `narwhal/commit_walk`, `narwhal/execute`): a level-1 TraceMe, so a
    `jax.profiler` session with `host_tracer_level >= 1` holds it on the
    host plane beside the device's `XLA Ops`. Outside a session it costs
    one atomic read; in a process that never loaded JAX, nothing."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    if profiler is None:
        return _NO_ANNOTATION
    return profiler.TraceAnnotation(name, **meta)


# -- loop heartbeat and loop account -------------------------------------------

HEARTBEAT_PERIOD = 0.02
# One series each for the process, mounted in every node's registry
# (`Registry.mount`): a deployment runs one loop per process, and the
# co-hosted nodes of a `Cluster` share theirs.
LOOP_LAG = Histogram(
    "loop_lag_seconds",
    "How late the event loop ran a 20 ms heartbeat timer (scheduled wake "
    "vs actual): what every other callback on this loop waited as well",
    (),
    buckets=(0.001, 0.005, 0.01, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5),
)
LOOP_BUSY = Counter(
    "loop_busy_seconds_total",
    "Wall seconds the event loop spent inside the callbacks it ran, by the "
    "family of the task, handler or wire tag that owned them: the loop "
    "account's estimate, each stretch it kept standing for the time since "
    "the one before (what is left of a second the loop waited in its selector)",
    ("family",),
)
_HEARTBEATS: dict = {}  # running loop -> [task, holders]

# An owner's family: the first prefix that matches. A label (what a site
# passed to `charge`, or the name it gave a task) is matched as it stands,
# code by its file's path from the checkout's or the standard library's root
# and its qualified name: `narwhal_tpu/primary/core.py:Core.run`.
OWNER_FAMILIES = (
    ("rpc:", "network"),
    ("net:", "network"),
    ("core:", "primary"),
    ("stage:", "verify"),
    ("verify:", "verify"),
    ("consensus:", "execute"),
    ("execute:", "execute"),
    ("storage:", "storage"),
    ("chipbench/", "harness"),
    ("narwhal_tpu/network/", "network"),
    ("asyncio/selector_events.py", "network"),
    ("asyncio/streams.py", "network"),
    ("asyncio/transports.py", "network"),
    ("narwhal_tpu/primary/verifier_stage.py", "verify"),
    ("narwhal_tpu/tpu/verifier.py", "verify"),
    ("narwhal_tpu/primary/", "primary"),
    ("narwhal_tpu/worker/", "worker"),
    ("narwhal_tpu/consensus/", "execute"),
    ("narwhal_tpu/executor/", "execute"),
    ("narwhal_tpu/node.py:SimpleExecutionState", "execute"),
    ("narwhal_tpu/storage.py", "storage"),
    ("narwhal_tpu/stores.py", "storage"),
)
FAMILIES = ("network", "primary", "worker", "verify", "execute", "storage", "harness", "other")
# Owners that get a row of their own in a stretch's `owner` records, beside
# the sites' labels, which always do: they are few, and readers ask for them
# by name.
OWNER_ROWS = 24
# The account keeps a stretch of ACCOUNT_KEEP_S, then rests ACCOUNT_REST_S
# (`Handle._run` is asyncio's own meanwhile and the sites read ACCOUNTING
# false): one part in 25, in stretches shorter than a round and spread
# evenly, so that what they hold is the loop as it runs without the account.
# On the one cell that has been measured the loop is full and its rounds run
# on the fifth of it that the transactions leave: every second kept (15,000
# callbacks and 7,400 WAL writes a second at 0.6 and 0.5 us) lengthened the
# round by a fifth, and a whole second kept in eight, with the sites paying a
# clock read and a call while it rested, still cost `latency_p50_ms` 8 %
# (PERF.md section 6, PR 35). A reader scales what it finds by what the
# records cover; readings compare only at one pair of these constants.
ACCOUNT_KEEP_S = 0.1
ACCOUNT_REST_S = 2.4
# True while some loop's account keeps a stretch. A site that runs thousands
# of times a second reads it before it takes the clock reading `nested` wants
# (`t0 = tracing.ACCOUNTING and time.perf_counter()` ... `if t0: nested(..)`),
# so that a resting account costs the site one attribute read.
ACCOUNTING = False

_HANDLE_RUN = asyncio.events.Handle._run  # what a loop without an account runs
_TASKS = (asyncio.Task, asyncio.tasks._PyTask)
_ROOTS = tuple(
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(f))), "")
    for f in (__file__, asyncio.__file__)
)
_ACCOUNTS: dict = {}  # loop with a heartbeat -> its _LoopAccount
_BY_THREAD: dict = {}  # that loop's thread -> the same account: `charge` asks by thread
_ACCOUNT_LOCK = threading.RLock()  # opening, closing, resting: loops may live on several threads
_ORDINALS = itertools.count(1)  # an account's `loop` field in its records
_OWNERS: dict = {}  # code object or label -> (owner, family), worked out once each
_perf = time.perf_counter
_log = logging.getLogger("narwhal.tracing")


def _owner(key) -> tuple[str, str]:
    known = _OWNERS.get(key)
    if known is None:
        if isinstance(key, str):
            name = key
        else:
            path = key.co_filename
            for root in _ROOTS:
                if path.startswith(root):
                    path = path[len(root):]
                    break
            else:
                path = "/".join(path.split(os.sep)[-2:])
            name = f"{path}:{key.co_qualname}"
        family = next((f for prefix, f in OWNER_FAMILIES if name.startswith(prefix)), "other")
        # Keyed by code or by label: as many entries as the program has sites.
        known = _OWNERS[key] = (name, family)  # lint: allow(multi-task-mutation)
    return known


def _dotted_name(obj) -> str:
    """What stands for an owner that has no Python code: module and name."""
    module = getattr(obj, "__module__", None) or type(obj).__module__
    return f"{module}.{getattr(obj, '__qualname__', None) or type(obj).__qualname__}"


def _task_key(task):
    """A task's owner: the name a site gave it, else its coroutine's code
    (a generator's, an async generator's; the name of a compiled one:
    grpc's). asyncio's own `Task-<n>` names nobody."""
    name = task.get_name()
    if not name.startswith("Task-"):
        return name
    coro = task.get_coro()
    for attr in ("cr_code", "gi_code", "ag_code"):
        code = getattr(coro, attr, None)
        if code is not None:
            return code
    return _dotted_name(coro)


def _callback_key(cb):
    """For a callback that is no Python function or method: the code of
    what a `functools.partial` calls, else the builtin's own name."""
    cb = getattr(cb, "func", cb)
    return getattr(cb, "__code__", None) or _dotted_name(cb)


class _LoopAccount:
    """One loop's callbacks by owner in the stretch it keeps. Written by the
    loop's own thread only (`_run_charged`, `charge`, the heartbeat's
    flush), so nothing here is locked."""

    __slots__ = ("ordinal", "thread", "on", "tally", "opened", "label", "t_seg",
                 "unlabelled", "t_flushed", "t_before", "cpu_mark")

    def __init__(self, ordinal: int, t: float):
        self.ordinal = ordinal
        self.thread = threading.get_ident()
        self.on = True  # False while it rests between two kept stretches
        # a label, a task, or the id of a plain callback's code object (a
        # code object hashes its whole content on every lookup) -> [calls,
        # seconds, longest, the label, the task or the code object: held,
        # so that its id stays its own]
        self.tally: dict = {}
        self.opened = 0  # segments that sites opened: calls that are no callbacks
        # The running callback: the label a site gave what runs now (None:
        # the callback's own owner), when that segment began (0.0 before
        # the kept stretch's first callback), and what the owner ran before
        # and between labels.
        self.label = None
        self.t_seg = 0.0
        self.unlabelled = 0.0
        # When the running stretch began (while it rests: when the last one
        # ended), and when the one before it ended (None: this is the first).
        self.t_flushed, self.t_before, self.cpu_mark = t, None, time.thread_time()

    def switch(self, label: str):
        """Close the running stretch at one clock read and open the next
        under `label`; returns the label that held."""
        now = _perf()
        held = self.label
        self._add(held, now - self.t_seg)
        self.label, self.t_seg = label, now
        self.opened += 1
        self.row(label)[0] += 1
        return held

    def close(self, t1: float) -> float:
        """The callback that sites re-labelled, or nested stretches in,
        ended at `t1`: close its last stretch; what is left for its own
        owner."""
        self._add(self.label, t1 - self.t_seg)
        own, self.unlabelled, self.label = self.unlabelled, 0.0, None
        return own

    def row(self, label: str) -> list:
        row = self.tally.get(label)
        if row is None:
            row = self.tally[label] = [0, 0.0, 0.0, label]
        return row

    def _add(self, label, dt: float) -> None:
        if label is None:
            self.unlabelled += dt
            return
        row = self.row(label)
        row[1] += dt
        if dt > row[2]:
            row[2] = dt

    def tick(self, now: float) -> None:
        """On the loop's thread, from each wake of the heartbeat: a kept
        stretch that is over is written; one that is due begins."""
        if self.on:
            if now - self.t_flushed >= ACCOUNT_KEEP_S:
                self.flush(now)
                self.on = False
                _patch_handle_run()
        elif now - self.t_flushed >= ACCOUNT_REST_S:
            self.tally, self.opened = {}, 0  # the end of the callback that put it to rest
            self.t_seg = 0.0  # no callback of this stretch has been timed yet
            self.t_before, self.t_flushed, self.cpu_mark = self.t_flushed, now, time.thread_time()
            self.on = True
            _patch_handle_run()

    def flush(self, t1: float) -> None:
        """On the loop's thread, from the heartbeat: write what ran in the
        stretch that ends at `t1` as one `loop` record and its `owner` rows.
        The series takes the stretch for all the time since the one before."""
        tally, self.tally = self.tally, {}
        opened, self.opened = self.opened, 0
        cpu = time.thread_time()
        rows: dict = {}  # owner -> [family, calls, seconds, longest]: lambdas of one function share a name
        for calls, seconds, longest, who in tally.values():
            owner, family = _owner(_task_key(who) if isinstance(who, _TASKS) else who)
            row = rows.get(owner)
            if row is None:
                rows[owner] = [family, calls, seconds, longest]
            else:
                row[1] += calls
                row[2] += seconds
                row[3] = max(row[3], longest)
        ranked = sorted(rows.items(), key=lambda kv: kv[1][2], reverse=True)
        longest_owner, longest = max(((o, r[3]) for o, r in ranked), key=lambda x: x[1], default=(None, 0.0))
        flight("loop", self.ordinal, self.t_flushed, t1, sum(r[1] for _, r in ranked) - opened,
               sum(r[2] for _, r in ranked), cpu - self.cpu_mark, longest, longest_owner)
        labels = {key for key in tally if isinstance(key, str)}  # what sites passed to `charge` and `nested`
        named: list = []  # the owners with a row of their own
        rest: dict = {}  # family -> the others, folded into one row
        for i, (owner, row) in enumerate(ranked):
            if i < OWNER_ROWS or owner in labels:
                named.append((owner, row))
                continue
            family, calls, seconds, longest = row
            folded = rest.setdefault(family, [family, 0, 0.0, 0.0])
            folded[1] += calls
            folded[2] += seconds
            folded[3] = max(folded[3], longest)
        kept = t1 - self.t_flushed
        stands_for = (t1 - self.t_before) / kept if self.t_before is not None and kept > 0 else 1.0
        for owner, (family, calls, seconds, longest) in (*named, *(("rest", r) for r in rest.values())):
            flight("owner", self.ordinal, t1, owner, family, calls, seconds, longest)
            LOOP_BUSY.labels(family).inc(seconds * stands_for)
        self.t_flushed, self.cpu_mark = t1, cpu


def _run_charged(handle) -> None:
    """`asyncio.events.Handle._run` while some loop has an account: time
    the callback on this thread and add it to its owner's. A loop without
    an account, or whose account rests while another's keeps a stretch, pays
    the lookup. Kept to what a callback can afford (about
    half a microsecond): the callback is called as `Handle._run` calls it,
    without a frame between; a task's step is tallied under the task itself
    and named at the flush; nothing is written that the next statement does
    not need."""
    acct = _ACCOUNTS.get(handle._loop)
    if acct is None or not acct.on:
        return _HANDLE_RUN(handle)
    cb = handle._callback
    acct.t_seg = t0 = _perf()
    try:
        handle._context.run(cb, *handle._args)
    except (SystemExit, KeyboardInterrupt):
        raise
    except BaseException as exc:
        _report(handle, exc)
    t1 = _perf()
    try:
        who = getattr(cb, "__self__", None)
        if isinstance(who, _TASKS):
            key = who
        else:
            who = getattr(cb, "__code__", None) or _callback_key(cb)
            # Keys one stretch's tally and nothing that is ordered or sent;
            # the row holds the object, so its id stays its own meanwhile.
            key = id(who)  # lint: allow(id-keyed-ordering)
        # No site re-labelled any of it: the whole callback is its owner's.
        dt = t1 - t0 if acct.t_seg == t0 else acct.close(t1)
        tally = acct.tally  # read now: the heartbeat's own callback swaps it
        row = tally.get(key)
        if row is None:
            tally[key] = [1, dt, dt, who]
        else:
            row[0] += 1
            row[1] += dt
            if dt > row[2]:
                row[2] = dt
    except Exception:  # the account never takes the loop down with it
        _log.debug("loop account: a callback of %r went uncharged", cb, exc_info=True)


def _report(handle, exc: BaseException) -> None:
    """What `Handle._run` does with an exception its callback raised."""
    cb = asyncio.format_helpers._format_callback_source(handle._callback, handle._args)
    context = {"message": f"Exception in callback {cb}", "exception": exc, "handle": handle}
    if handle._source_traceback:
        context["source_traceback"] = handle._source_traceback
    handle._loop.call_exception_handler(context)


def _running_account():
    """The calling thread's account, once it has timed a callback."""
    acct = _BY_THREAD.get(threading.get_ident())
    return acct if acct is not None and acct.on and acct.t_seg else None


def charge(label: str):
    """Charge the rest of the callback the calling thread's loop is running
    to `label` (`core:vote`, `verify:deliver`): one clock read closes the
    running stretch. The label ends with the callback, so a coroutine that
    suspends is its task's again when it resumes. Returns the label that
    held (None: the callback's own owner). Nothing on a thread that runs no
    loop with a heartbeat, and nothing while the account rests."""
    if not ACCOUNTING:
        return None
    acct = _running_account()
    return None if acct is None else acct.switch(label)


def nested(label: str, t0: float) -> None:
    """A synchronous stretch of the running callback, from `t0` (a
    `time.perf_counter()` reading the site took when it began) to now, goes
    to `label` (`storage:wal`, `verify:seal`, `net:write`) and comes off what it
    interrupted, which resumes. One call and one clock read, for sites that
    run thousands of times a second; such a site reads `ACCOUNTING` first and
    takes `t0` only while it is true. Not across an `await`, and not around
    another `nested` stretch: the inner one's time would be counted under
    both labels, or the outer one dropped (no site nests today)."""
    acct = _running_account()
    if acct is None or t0 < acct.t_seg:
        return
    dt = _perf() - t0
    row = acct.row(label)
    row[0] += 1
    row[1] += dt
    if dt > row[2]:
        row[2] = dt
    acct.opened += 1
    acct.t_seg += dt  # the running stretch is that much shorter


def _patch_handle_run() -> None:
    """`_run_charged` stands in `Handle._run`'s place while some account
    keeps a stretch, and asyncio's own otherwise; `ACCOUNTING` says which."""
    global ACCOUNTING
    with _ACCOUNT_LOCK:
        ACCOUNTING = any(acct.on for acct in _ACCOUNTS.values())
        asyncio.events.Handle._run = _run_charged if ACCOUNTING else _HANDLE_RUN


def _open_account(loop) -> None:
    """On `loop`'s thread: it keeps a stretch at once."""
    with _ACCOUNT_LOCK:
        if loop not in _ACCOUNTS:
            acct = _LoopAccount(next(_ORDINALS), loop.time())
            _ACCOUNTS[loop] = _BY_THREAD[acct.thread] = acct
            _patch_handle_run()


def _close_account(loop, t: float | None) -> None:
    """Write the part of a kept stretch the account holds (`t`; None: the
    loop is gone) and drop it."""
    with _ACCOUNT_LOCK:
        acct = _ACCOUNTS.pop(loop, None)
        if acct is not None:
            if _BY_THREAD.get(acct.thread) is acct:
                del _BY_THREAD[acct.thread]
            if t is not None and acct.on:
                acct.flush(t)
        _patch_handle_run()


async def _heartbeat(period: float) -> None:
    """Every wake goes to the histogram. The ring takes a `lag` record
    (due, woke, quiet wakes since the last record, their summed lateness)
    for each wake later than one period, and one a second otherwise, so a
    reader has every late wake exactly and the count of the rest. Each wake
    lets the loop's account see whether a stretch it keeps ends or begins."""
    loop = asyncio.get_running_loop()
    account = _ACCOUNTS.get(loop)
    observe = LOOP_LAG.labels().observe
    quiet, quiet_sum = 0, 0.0
    every = max(1, round(1.0 / period))
    while True:
        due = loop.time() + period
        await asyncio.sleep(period)
        woke = loop.time()
        if account is not None:
            account.tick(woke)
        late = woke - due
        observe(late)
        if late <= period:
            quiet, quiet_sum = quiet + 1, quiet_sum + late
            if quiet < every:
                continue
        flight("lag", due, woke, quiet, quiet_sum)
        quiet, quiet_sum = 0, 0.0


def heartbeat_acquire() -> None:
    """Start the running loop's heartbeat and its account, or join the ones
    it has: one per loop however many nodes share it. Paired with
    `heartbeat_release`."""
    loop = asyncio.get_running_loop()
    for gone in [lp for lp in _HEARTBEATS if lp.is_closed()]:
        del _HEARTBEATS[gone]  # a loop that closed without its release
        _close_account(gone, None)
    slot = _HEARTBEATS.get(loop)
    if slot is None or slot[0].done():
        _open_account(loop)
        slot = _HEARTBEATS[loop] = [asyncio.ensure_future(_heartbeat(HEARTBEAT_PERIOD)), 0]
    slot[1] += 1


def heartbeat_release() -> None:
    loop = asyncio.get_running_loop()
    slot = _HEARTBEATS.get(loop)
    if slot is None:
        return
    slot[1] -= 1
    if slot[1] <= 0:
        slot[0].cancel()
        del _HEARTBEATS[loop]
        _close_account(loop, loop.time())


def _env_flag(name: str, default: str = "0") -> bool:
    return os.environ.get(name, default) not in ("", "0", "false", "no")


class Tracer:
    """One node's span recorder + flight ring.

    `enabled`/`sample`/`ring` default from the environment at construction
    (NARWHAL_TRACE, NARWHAL_TRACE_SAMPLE, NARWHAL_FLIGHT_RING) so a whole
    in-process committee flips together without plumbing flags through
    every constructor.

    Concurrency discipline: many tasks append to `events` (every stage
    timer close, every instant), and the live-dump RPC handler reads it
    concurrently — the ring is safe because appends are single-statement
    (atomic under cooperative scheduling: no await between deciding to
    record and recording) and every reader snapshots copy-on-read
    (`dump()` does `list(self.events)` and serializes BEFORE its caller's
    next yield point). Do not hold a live reference to `events` across an
    await. Span ordering sanity (one window per key per stage, so the
    waterfall's earliest-t0 pick cannot land on a late re-opened window
    after ring eviction) is the stage timers' job: see
    pacing.StageTimer's closed-key latch."""

    __slots__ = ("node", "enabled", "events", "anomalies", "_threshold",
                 "generation", "__weakref__")

    def __init__(
        self,
        node: str = "",
        enabled: bool | None = None,
        sample: float | None = None,
        ring: int | None = None,
    ):
        self.node = node
        self.enabled = (
            _env_flag("NARWHAL_TRACE") if enabled is None else enabled
        )
        if sample is None:
            sample = float(os.environ.get("NARWHAL_TRACE_SAMPLE", "1.0"))
        # Deterministic digest-based sampling: a key is traced iff its
        # first 4 bytes, read big-endian, fall under sample * 2^32. Every
        # node makes the same decision for the same digest.
        self._threshold = int(max(0.0, min(1.0, sample)) * 0x1_0000_0000)
        if ring is None:
            ring = int(os.environ.get("NARWHAL_FLIGHT_RING", "4096"))
        self.events: collections.deque = collections.deque(maxlen=max(16, ring))
        self.anomalies: list[str] = []
        self.generation = _GENERATION
        _LIVE.add(self)

    # -- hot path ----------------------------------------------------------

    def sampled(self, key: bytes) -> bool:
        """Deterministic per-digest sampling decision (callers gate on
        `enabled` first; this never reads the clock or the environment)."""
        if self._threshold >= 0x1_0000_0000:
            return True
        return int.from_bytes(key[:4], "big") < self._threshold

    def span(self, stage: str, key: bytes, t0: float, t1: float, attrs=None):
        """One closed span: stage `stage` of causal key `key` ran [t0, t1].
        Appended at CLOSE time only — an open span costs nothing but its
        caller-held t0."""
        self.events.append(("span", stage, key.hex(), t0, t1, attrs))

    def link(self, stage: str, parent: bytes, child: bytes) -> None:
        """The causal key hops: `parent`'s journey continues under `child`
        (batch digest -> header digest at propose, header digest ->
        certificate digest at certify)."""
        self.events.append(("link", stage, parent.hex(), child.hex()))

    # -- flight recorder (off the hot path; always records) ----------------

    def instant(self, kind: str, **attrs) -> None:
        """A point-in-time flight event: occupancy snapshot, backpressure
        level transition, pacing mode change, anomaly marker."""
        self.events.append(("instant", kind, _now(), attrs or None))

    def anomaly(self, reason: str, **attrs) -> None:
        """Record an anomaly marker and archive this tracer's ring."""
        self.anomalies.append(reason)
        self.instant("anomaly", reason=reason, **attrs)
        _archive(self.dump())

    # -- dump surface ------------------------------------------------------

    def dump(self, max_events: int | None = None) -> dict:
        """Self-contained, JSON-able snapshot of the ring."""
        events = list(self.events)
        if max_events is not None and max_events > 0:
            events = events[-max_events:]
        return {
            "node": self.node,
            "trace_enabled": self.enabled,
            "ring_capacity": self.events.maxlen,
            "anomalies": list(self.anomalies),
            "events": events,
        }

    def archive(self) -> None:
        """Push this tracer's dump into the module archive (node shutdown:
        the ring must outlive the node for post-teardown diagnosis)."""
        if self.events or self.anomalies:
            _archive(self.dump())


def _archive(dump: dict) -> None:
    ARCHIVE.append(dump)
    out_dir = os.environ.get("NARWHAL_FLIGHT_DIR", "")
    if out_dir:
        try:
            os.makedirs(out_dir, exist_ok=True)
            path = os.path.join(
                out_dir, f"flight-{dump.get('node') or 'node'}-{len(ARCHIVE)}.json"
            )
            with open(path, "w") as f:
                json.dump(dump, f, sort_keys=True)
        except OSError:
            pass  # diagnosis must never take the node down


def live_dumps(max_events: int | None = None) -> list[dict]:
    """Dump every live tracer of the CURRENT cluster incarnation (all
    hosted nodes of an in-process committee), stable-ordered by node
    label. Tracers from a prior incarnation are excluded even while still
    referenced — their spans describe a different cluster's history."""
    return sorted(
        (
            t.dump(max_events)
            for t in _LIVE
            if t.generation == _GENERATION
        ),
        key=lambda d: d["node"],
    )


def all_dumps(max_events: int | None = None) -> list[dict]:
    """Live rings plus the bounded archive of already-torn-down nodes,
    and the process flight ring."""
    return list(ARCHIVE) + live_dumps(max_events) + [flight_dump(max_events)]


def on_anomaly(reason: str) -> list[dict]:
    """Dump-on-anomaly trigger: snapshot every live ring into the archive,
    tagged with the reason, and return the dumps (what an oracle or a
    commit-stall detector attaches to its report)."""
    dumps = []
    for t in list(_LIVE):
        if t.generation != _GENERATION:
            continue
        t.anomalies.append(reason)
        dumps.append(t.dump())
    for d in dumps:
        d = dict(d)
        d["anomaly"] = reason
        _archive(d)
    return dumps


def clear_archive() -> None:
    ARCHIVE.clear()


# -- waterfall reconstruction ----------------------------------------------


def waterfall(dumps: list[dict]) -> dict[str, dict]:
    """Stitch span + link events from any number of node dumps into
    per-certificate waterfalls.

    Returns {certificate_digest_hex: {"stages": {stage: [t0, t1]}, ...}}
    where the stages of batches folded into the certificate's header (seal,
    propose, and the header's verify_stage hops) are re-keyed under the
    certificate via the recorded link chain. Each stage keeps the
    earliest-opening span observed for that key across all dumps."""
    spans: dict[str, dict[str, tuple[float, float]]] = {}
    parent_of: dict[str, list[str]] = {}  # child key -> parent keys
    for d in dumps:
        for ev in d.get("events", ()):
            # Dumps arrive over RPC from possibly-older nodes: skip any
            # event too short for its kind instead of raising mid-stitch.
            if ev[0] == "span" and len(ev) >= 5:
                _, stage, key, t0, t1 = ev[:5]
                best = spans.setdefault(key, {})
                if stage not in best or t0 < best[stage][0]:
                    best[stage] = (t0, t1)
            elif ev[0] == "link" and len(ev) >= 4:
                _, _stage, parent, child = ev[:4]
                if parent != child:  # a self-link stitches nothing
                    parent_of.setdefault(child, []).append(parent)

    def ancestors(key: str, seen: set[str]) -> list[str]:
        # Iterative DFS with a seen-set: a cyclic link chain (two nodes
        # disagreeing about direction) or an arbitrarily deep one (ring
        # overflow splitting chains) degrades to a partial lineage instead
        # of looping or blowing the stack.
        out: list[str] = []
        stack = list(parent_of.get(key, ()))
        while stack:
            p = stack.pop(0)
            if p in seen:
                continue
            seen.add(p)
            out.append(p)
            stack[:0] = parent_of.get(p, ())
        return out

    # Roots = keys that are nobody's parent (certificate digests) OR keys
    # with a terminal stage recorded. Commit/execute close on the
    # certificate digest, so any key carrying those stages is a root.
    children = {p for ps in parent_of.values() for p in ps}
    out: dict[str, dict] = {}
    for key, stages in spans.items():
        terminal = "commit" in stages or "execute" in stages
        if key in children and not terminal:
            continue
        merged = dict(stages)
        lineage = ancestors(key, {key})
        for a in lineage:
            for stage, window in spans.get(a, {}).items():
                if stage not in merged or window[0] < merged[stage][0]:
                    merged[stage] = window
        out[key] = {
            "stages": {s: [t0, t1] for s, (t0, t1) in sorted(merged.items())},
            "ancestors": lineage,
        }
    return out


def stage_percentiles(dumps: list[dict]) -> dict[str, dict]:
    """Per-stage duration p50/p95 over every span in the dumps — the
    `--trace-waterfall` artifact's summary table."""
    by_stage: dict[str, list[float]] = {}
    for d in dumps:
        for ev in d.get("events", ()):
            if ev[0] == "span":
                by_stage.setdefault(ev[1], []).append(ev[4] - ev[3])
    out = {}
    for stage, samples in sorted(by_stage.items()):
        samples.sort()
        n = len(samples)
        out[stage] = {
            "count": n,
            "p50_ms": round(samples[n // 2] * 1000, 3),
            "p95_ms": round(samples[min(n - 1, int(0.95 * n))] * 1000, 3),
            "max_ms": round(samples[-1] * 1000, 3),
        }
    return out
