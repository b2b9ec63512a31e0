"""The measured window, cut out of the program's process flight ring.

Not a metric's reader: the helper of the readers that take theirs from
`narwhal_tpu.tracing.flight_dump()` — the ring of `flush`, `wake`, `stage`,
`certify`, `walk`, `lag`, `compile`, `wal_flush` and `ingest_first` records
the program keeps always, which outlives the committee's shutdown. `obs`
holds no absolute time, so the window is found from the ring itself: the
workers' first non-empty submission (`ingest_first`; the generator's first
burst is due 50 ms after it starts) plus the mix's ramp opens it, and it is
`obs["seconds"]` long. A program without the ring, or a ring without that
record, gives None, and so does every reader that asks.

The program owns the records' layout (`narwhal_tpu.tracing.FLIGHT_FIELDS`):
a record is a namedtuple whose first field is its kind, and the readers go by
field name. Times are seconds on `time.monotonic()`.
"""

from __future__ import annotations

import collections

# t0, t1: the window; by: the ring's records by kind; period: the loop
# heartbeat's (a wake later than one period has a `lag` record of its own).
Window = collections.namedtuple("Window", "t0 t1 by period")


def window(obs) -> Window | None:
    """The window's bounds and the ring's records by kind, or None."""
    try:
        from narwhal_tpu import tracing
    except ImportError:
        return None
    dump = getattr(tracing, "flight_dump", None)
    if dump is None:
        return None
    by: dict[str, list] = collections.defaultdict(list)
    for record in dump()["events"]:
        by[record.kind].append(record)
    if not by["ingest_first"]:
        return None
    t0 = min(r.t for r in by["ingest_first"]) + float(obs["mix"].get("warm_s", 0.0))
    return Window(t0, t0 + float(obs["seconds"]), by, tracing.HEARTBEAT_PERIOD)


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
