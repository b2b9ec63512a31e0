"""The eleven readers of the loop account (`loop` and `owner` records of the
program's process flight ring): each on a hand-built ring against a value
worked by hand — two loops' records in one window, a second that straddles
each edge of it — and each returning nothing on a ring without the records."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run as runner  # noqa: E402
from narwhal_tpu import tracing  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# The window: first submission at 100.0, a 3 s ramp, 10 s long: [103, 113]; 50 rounds in it.
OBS = {"mix": {"warm_s": 3.0}, "seconds": 10.0, "window": {"rounds": 50.0}}

# loop, t0, t1, handles, busy_s, cpu_s, longest_s, longest_owner
LOOPS = [
    (1, 101.5, 102.5, 900, 0.9, 0.9, 0.01, "x"),  # before the window
    (1, 102.5, 103.5, 1000, 0.8, 0.7, 0.01, "x"),  # half of it inside: 0.4 busy, 0.35 on a core
    *((1, 103.5 + i, 104.5 + i, 2000, 0.6, 0.5, 0.02, "x") for i in range(9)),  # whole: 5.4 and 4.5
    (1, 112.5, 113.75, 1000, 1.0, 1.0, 0.01, "x"),  # 0.5 s of its 1.25 inside: 0.4 and 0.4
    (2, 103.0, 104.0, 50, 0.2, 0.2, 0.01, "y"),  # another loop (a tool's, a test's): less busy
    (2, 104.0, 105.0, 50, 0.1, 0.1, 0.01, "y"),
]
# loop, t1, owner, family, calls, seconds, longest: the seconds of a (loop, t1) sum to its busy_s
OWNERS = [
    (1, 102.5, "rpc:HeaderMsg", "network", 10, 0.9, 0.01),
    (1, 103.5, "rpc:HeaderMsg", "network", 10, 0.5, 0.01), (1, 103.5, "core:vote", "primary", 10, 0.3, 0.01),
    *(row for i in range(9) for row in (
        (1, 104.5 + i, "rpc:HeaderMsg", "network", 20, 0.25, 0.01),
        (1, 104.5 + i, "core:vote", "primary", 20, 0.15, 0.01),
        (1, 104.5 + i, "verify:seal", "verify", 12, 0.05, 0.002),
        (1, 104.5 + i, "storage:wal", "storage", 99, 0.04, 0.001),
        (1, 104.5 + i, "execute:certificate", "execute", 3, 0.03, 0.02),
        (1, 104.5 + i, "chipbench/run.py:serve", "harness", 20, 0.02, 0.001),
        (1, 104.5 + i, "narwhal_tpu/worker/batch_maker.py:BatchMaker.run", "worker", 20, 0.05, 0.001),
        (1, 104.5 + i, "rest", "other", 500, 0.01, 0.001),
    )),
    (1, 113.75, "rpc:HeaderMsg", "network", 10, 0.75, 0.01), (1, 113.75, "rest", "other", 10, 0.25, 0.01),
    (2, 104.0, "tool.py:main", "other", 50, 0.2, 0.01), (2, 105.0, "tool.py:main", "other", 50, 0.1, 0.01),
]
# Seconds inside the window, loop 1: the half second, nine whole ones, 0.4 of the last.
FAMILY_S = {
    "network": 0.5 * 0.5 + 9 * 0.25 + 0.4 * 0.75, "primary": 0.5 * 0.3 + 9 * 0.15, "verify": 9 * 0.05,
    "storage": 9 * 0.04, "execute": 9 * 0.03, "harness": 9 * 0.02, "worker": 9 * 0.05,
    "other": 9 * 0.01 + 0.4 * 0.25,
}
BUSY_S = 0.4 + 5.4 + 0.4
BY_HAND = {
    "loop.busy_share": 100 * BUSY_S / 10,
    "loop.work_ms_per_round": 1000 * BUSY_S / 50,
    "loop.offcpu_share": 100 * (0.05 + 0.9 + 0.0) / BUSY_S,
    **{f"loop.{family}_ms_per_round": 1000 * s / 50 for family, s in FAMILY_S.items()},
}
LOOP_METRICS = ["loop.busy_share", "loop.work_ms_per_round", "loop.offcpu_share",
                *(f"loop.{family}_ms_per_round" for family in tracing.FAMILIES)]


def fill(loops=LOOPS, owners=OWNERS, firsts=(100.004, 100.0)) -> None:
    tracing.new_generation()
    for i, t in enumerate(firsts):
        tracing.flight("ingest_first", f"worker-{i}", t)
    for row in loops:
        tracing.flight("loop", *row)
    for row in owners:
        tracing.flight("owner", *row)


@pytest.fixture(autouse=True)
def _empty_ring_afterwards():
    yield
    tracing.new_generation()


def test_the_eleven_are_entries_a_reader_each_by_the_accounts_families_and_the_helper_is_no_metric():
    """Each is a `per_layer` entry of `BENCHMARK.json`, found by name and in
    the order it was appended (PR 36): entries appended after them, and a
    `workloads` list that keeps one out of a later cell, leave this as it is."""
    assert set(BY_HAND) == set(LOOP_METRICS) and len(LOOP_METRICS) == 11
    entries = [m for m in BENCH["per_layer"] if m["name"] in LOOP_METRICS]
    assert [m["name"] for m in entries] == LOOP_METRICS
    assert all(m["source"] == "program_span" and m["better"] == "lower" and m["moves"] == "latency_p50_ms"
               for m in entries)
    assert {m["name"]: m["unit"] for m in entries} == {
        name: "%" if name.endswith("_share") else "ms" for name in LOOP_METRICS}
    families = {n.split(".")[1].removesuffix("_ms_per_round") for n in LOOP_METRICS if n.endswith("_ms_per_round")}
    assert families == set(tracing.FAMILIES) | {"work"}
    readers = os.path.join(ROOT, "chipbench", "readers")
    assert all(os.path.isfile(os.path.join(readers, f"{name}.py")) for name in LOOP_METRICS)
    assert all(callable(runner.load_reader(name)) for name in LOOP_METRICS)
    from chipbench.readers import loop_account

    assert not hasattr(loop_account, "read")  # the helper's file is no metric's


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_reader_on_a_hand_built_ring(metric):
    fill()
    assert runner.load_reader(metric)(OBS) == pytest.approx(BY_HAND[metric], rel=1e-9)


def test_the_families_sum_to_the_work_and_the_share_is_the_same_seconds():
    fill()
    read = {m: runner.load_reader(m)(OBS) for m in LOOP_METRICS}
    families = sum(v for m, v in read.items() if m.endswith("_ms_per_round") and m != "loop.work_ms_per_round")
    assert families == pytest.approx(read["loop.work_ms_per_round"], rel=1e-9)
    assert read["loop.busy_share"] / 100 * 10.0 == pytest.approx(read["loop.work_ms_per_round"] / 1000 * 50, rel=1e-9)


@pytest.mark.parametrize("metric", LOOP_METRICS)
def test_reader_returns_nothing_on_a_ring_without_the_records(metric, monkeypatch):
    tracing.new_generation()
    assert runner.load_reader(metric)(OBS) is None
    fill(loops=(), owners=())  # the parent's ring: a window, and no account in it
    assert runner.load_reader(metric)(OBS) is None
    fill(firsts=())  # records, but no first submission to cut the window from
    assert runner.load_reader(metric)(OBS) is None
    fill(loops=LOOPS[:1], owners=OWNERS[:1])  # an account that ended before the window opened
    assert runner.load_reader(metric)(OBS) is None
    fill()
    monkeypatch.delattr(tracing, "flight_dump")  # a program without the ring
    assert runner.load_reader(metric)(OBS) is None


@pytest.mark.parametrize("metric", [m for m in LOOP_METRICS if m.endswith("_per_round")])
def test_a_window_without_a_round_reads_nothing_per_round(metric):
    fill()
    assert runner.load_reader(metric)(dict(OBS, window={"rounds": 0})) is None


def test_a_family_that_ran_nothing_reads_zero_and_the_busiest_loop_is_the_committees():
    fill(owners=[row for row in OWNERS if row[3] != "worker"])
    assert runner.load_reader("loop.worker_ms_per_round")(OBS) == 0.0
    # The other loop made the busier: its seconds are read, loop 1's are not.
    fill(loops=[*LOOPS, *((2, 105.0 + i, 106.0 + i, 10, 0.9, 0.9, 0.01, "y") for i in range(8))],
         owners=[*OWNERS, *((2, 106.0 + i, "tool.py:main", "other", 10, 0.9, 0.01) for i in range(8))])
    assert runner.load_reader("loop.busy_share")(OBS) == pytest.approx(100 * (0.3 + 7.2) / 10)
    assert runner.load_reader("loop.other_ms_per_round")(OBS) == pytest.approx(1000 * 7.5 / 50)
    assert runner.load_reader("loop.network_ms_per_round")(OBS) == 0.0


def test_an_account_that_rests_between_stretches_stands_for_the_window_by_what_it_covers():
    """The records cover some of the window only (`tracing.ACCOUNT_KEEP_S`
    in every `ACCOUNT_KEEP_S + ACCOUNT_REST_S`). Shares are of what they
    cover; seconds a round are scaled from that to the window."""
    kept = [row for row in LOOPS if row[0] == 1 and row[1] in (103.5, 106.5, 109.5)]  # three stretches, 3 s of ten
    fill(loops=kept, owners=[row for row in OWNERS if row[1] in (104.5, 107.5, 110.5)])
    assert runner.load_reader("loop.busy_share")(OBS) == pytest.approx(100 * 0.6)
    assert runner.load_reader("loop.offcpu_share")(OBS) == pytest.approx(100 * 0.1 / 0.6)
    assert runner.load_reader("loop.work_ms_per_round")(OBS) == pytest.approx(1000 * (3 * 0.6) * (10 / 3) / 50)
    assert runner.load_reader("loop.verify_ms_per_round")(OBS) == pytest.approx(1000 * (3 * 0.05) * (10 / 3) / 50)
