"""The loop account inside the measured window.

Not a metric's reader: the helper of the eleven `loop.*` readers that take
theirs from the `loop` and `owner` records of the program's process flight
ring (`narwhal_tpu.tracing`, the loop account: every callback an event loop
with a heartbeat runs, timed on the loop's own thread and charged to the
task, handler or wire tag that ran it), and of `tools.perf.flight_profile
--owners`. The account keeps short stretches spread evenly over the window
(`tracing.ACCOUNT_KEEP_S` in every `ACCOUNT_KEEP_S + ACCOUNT_REST_S`), one
`loop` record and its `owner` rows each: shares are of the seconds the
records cover, and seconds a round are scaled from them to the window. So
every `loop.*` reading is an estimate from a sample, and two readings
compare only where the program kept the same stretches: a PR that turns
those constants moves all eleven with no change to the loop. A stretch that
straddles an edge of the window counts by the share of it that lies inside.
Where several loops keep an account, the one that carries the committee is
the one that was busiest in the window. A program without the records gives
None, and so does every reader that asks.
"""

from __future__ import annotations

import collections

from chipbench.readers import flight_window as fw

# loop: the account's ordinal; covered: the seconds of the window that its
# records cover; busy: of those, seconds the loop spent inside callbacks;
# offcpu: of those, seconds its thread stood off a core (busy_s - cpu_s);
# handles: callbacks run; families: busy by the owners' family, summing to
# busy; owners: (owner, family) -> [calls, seconds, longest single stretch];
# scale: the window's seconds over covered.
Account = collections.namedtuple("Account", "loop covered busy offcpu handles families owners scale")


def over(by, t0: float, t1: float) -> Account | None:
    """The busiest loop's account of [t0, t1], from the ring's records by kind."""
    inside: dict = {}  # (loop, t1) -> the share of that stretch inside the window
    sums: dict = collections.defaultdict(lambda: [0.0, 0.0, 0.0, 0.0])  # loop -> busy, off a core, handles, covered
    for r in by["loop"]:
        overlap = min(r.t1, t1) - max(r.t0, t0)
        if overlap <= 0:
            continue
        share = inside[(r.loop, r.t1)] = overlap / (r.t1 - r.t0)
        for i, v in enumerate((r.busy_s, r.busy_s - r.cpu_s, r.handles, r.t1 - r.t0)):
            sums[r.loop][i] += share * v
    if not sums:
        return None
    loop, (busy, offcpu, handles, covered) = max(sums.items(), key=lambda kv: kv[1][0])
    families: dict = collections.defaultdict(float)
    owners: dict = collections.defaultdict(lambda: [0.0, 0.0, 0.0])
    for r in by["owner"]:
        share = inside.get((r.loop, r.t1), 0.0) if r.loop == loop else 0.0
        if share:
            families[r.family] += share * r.seconds
            row = owners[(r.owner, r.family)]
            row[0] += share * r.calls
            row[1] += share * r.seconds
            row[2] = max(row[2], r.longest)
    return Account(loop, covered, busy, offcpu, handles, dict(families), dict(owners), (t1 - t0) / covered)


def account(obs) -> Account | None:
    win = fw.window(obs)
    return None if win is None else over(win.by, win.t0, win.t1)


def ms_per_round(obs, acct: Account, seconds: float) -> float | None:
    """`seconds` of the account's covered seconds, as milliseconds a round
    of the whole window."""
    rounds = obs["window"]["rounds"]
    return 1000.0 * acct.scale * seconds / rounds if rounds else None


def family_ms_per_round(obs, family: str) -> float | None:
    """Milliseconds a round the loop spent in callbacks of `family`'s owners
    (0 where the family ran nothing)."""
    acct = account(obs)
    return None if acct is None else ms_per_round(obs, acct, acct.families.get(family, 0.0))
