"""The measured window, cut out of the program's process flight ring.

Not a metric's reader: the helper of the readers that take theirs from
`narwhal_tpu.tracing.flight_dump()` — the ring of `flush`, `wake`, `stage`,
`certify`, `walk`, `lag`, `loop`, `owner`, `compile`, `wal_flush` and
`ingest_first` records the program keeps always. The run takes the ring once,
when its drain ends (`obs["flight"]`, `chipbench/run.py` `take_flight`): the
committee writes on through the trace write and its shutdown, and a large one
would push the window's first records out of the ring before the readers came
to them. Every record a reader uses is written inside the window or soon
after its end (a stretch of the loop account that straddles it, within 0.1 s;
a verifier stage about a header certified in it, within its hop), and the
drain lasts at least 0.2 s past it. Without `obs["flight"]` (a hand-filled ring in a test)
the live ring is read. `obs` holds no absolute time, so the window is found
from the records themselves: the workers' first non-empty submission
(`ingest_first`; the generator's first burst is due 50 ms after it starts)
plus the mix's ramp opens it, and it is `obs["seconds"]` long. A program
without the ring, or records without that one, give None, and so does every
reader that asks.

The program owns the records' layout (`narwhal_tpu.tracing.FLIGHT_FIELDS`):
a record is a namedtuple whose first field is its kind, and the readers go by
field name. Times are seconds on `time.monotonic()`.
"""

from __future__ import annotations

import collections

# t0, t1: the window; by: the ring's records by kind; period: the loop
# heartbeat's (a wake later than one period has a `lag` record of its own).
Window = collections.namedtuple("Window", "t0 t1 by period")
# The field of each kind that says when a record was written, or within a
# few milliseconds of it: what `coverage` tells a record's age by.
STAMPS = {
    "flush": "t_posted", "wake": "t_posted", "stage": "t_forwarded", "certify": "t1", "walk": "t_done",
    "lag": "woke", "loop": "t1", "owner": "t1", "compile": "t", "kernel_load": "t", "wal_flush": "t",
    "ingest_first": "t",
}


def window(obs) -> Window | None:
    """The window's bounds and the records by kind, or None."""
    try:
        from narwhal_tpu import tracing
    except ImportError:
        return None
    flight = obs.get("flight")
    if flight is None:
        dump = getattr(tracing, "flight_dump", None)
        if dump is None:
            return None
        flight = dump()
    by: dict[str, list] = collections.defaultdict(list)
    for record in flight["events"]:
        by[record.kind].append(record)
    if not by["ingest_first"]:
        return None
    t0 = min(r.t for r in by["ingest_first"]) + float(obs["mix"].get("warm_s", 0.0))
    return Window(t0, t0 + float(obs["seconds"]), by, tracing.HEARTBEAT_PERIOD)


def coverage(obs) -> dict | None:
    """What the run's snapshot of the ring kept, for its record: the records
    and the ring's capacity; the seconds from the oldest record kept to the
    snapshot, and to the window's opening (the ring's headroom; None where
    the window was lost); and the records written a second inside the window
    and in the drain after it. None where the run took no snapshot."""
    flight = obs.get("flight")
    if flight is None:
        return None
    stamps = [getattr(r, STAMPS[r.kind]) for r in flight["events"] if r.kind in STAMPS]
    taken = flight["t_taken"]
    oldest = min(stamps, default=taken)
    out = {"records": len(flight["events"]), "ring_capacity": flight["ring_capacity"],
           "seconds_kept": taken - oldest, "headroom_s": None, "per_s_window": None, "per_s_drain": None}
    win = window(obs)
    if win is not None:
        out["headroom_s"] = win.t0 - oldest
        out["per_s_window"] = sum(win.t0 <= t <= win.t1 for t in stamps) / (win.t1 - win.t0)
        if taken > win.t1:
            out["per_s_drain"] = sum(t > win.t1 for t in stamps) / (taken - win.t1)
    return out


def within(win: Window | None, kind: str, at: str) -> list:
    """The records of `kind` whose stamp in field `at` lies in the window;
    none where there is no window."""
    return [r for r in win.by[kind] if win.t0 <= getattr(r, at) <= win.t1] if win else []


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Sorted, disjoint union of the intervals, each clipped to [lo, hi]."""
    out: list[tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(disjoint) -> float:
    return sum(b - a for a, b in disjoint)


def verify_shares(win: Window | None) -> tuple[float, float, float] | None:
    """(starved, held, in flight) in per cent of the window, summing to 100.
    In flight: some flush is between `t_dispatched` and `t_posted`. Held:
    none is, but an entry is queued or being packed (`t_oldest` to
    `t_dispatched` of its flush). Starved: neither, both lanes empty."""
    flushes = win.by["flush"] if win else []
    if not flushes:
        return None
    span = win.t1 - win.t0
    flying = union(((f.t_dispatched, f.t_posted) for f in flushes), win.t0, win.t1)
    waiting = union(((f.t_oldest, f.t_dispatched) for f in flushes), win.t0, win.t1)
    either = union(flying + waiting, win.t0, win.t1)
    in_flight = length(flying)
    held = length(either) - in_flight
    return (100.0 * (span - length(either)) / span, 100.0 * held / span, 100.0 * in_flight / span)
