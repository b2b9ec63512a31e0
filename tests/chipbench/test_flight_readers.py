"""The readers that take their metric from the program's process flight ring
(`narwhal_tpu.tracing.flight_dump`): each on a hand-built ring against a value
worked by hand, the window cut from `ingest_first` plus the mix's ramp; the
run's snapshot of the ring, taken when the drain ends, against the live ring;
and one CPU rehearsal whose line carries the three that are counts."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run as runner  # noqa: E402
from chipbench.readers import flight_window as fw  # noqa: E402
from narwhal_tpu import tracing  # noqa: E402
from tests.chipbench.test_loop_readers import BY_HAND as LOOP_BY_HAND  # noqa: E402
from tests.chipbench.test_loop_readers import LOOP_METRICS, LOOPS, OWNERS  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
# The window: first submission at 100.0, a 3 s ramp, 10 s long: [103, 113].
OBS = {"mix": {"warm_s": 3.0}, "seconds": 10.0, "executed_in_window": 10}
B = 2048

FLUSHES = [
    # seq lane entries useful padded t_oldest wait_sum t_seal t_dispatched t_posted failure
    (0, "singles", 3, 3, B, 102.990, 0.009, 102.995, 102.998, 103.010, None),  # sealed before
    (1, "singles", 4, 4, B, 103.997, 0.010, 104.000, 104.002, 104.020, None),
    (2, "groups", 2, 12, B, 104.010, 0.008, 104.015, 104.016, 104.041, None),  # overlaps 1
    (3, "singles", 2, 2, B, 108.000, 0.006, 108.003, 108.005, 108.025, None),
    (4, "singles", 1, 1, B, 112.990, 0.005, 112.995, 112.998, 113.020, None),  # straddles the end
]
WAKES = [  # seq, entries resumed, summed lag, longest lag, t_posted
    (0, 5, 1.0, 0.5, 102.0),
    (1, 4, 0.008, 0.003, 104.020),
    (3, 2, 0.010, 0.006, 108.025),
]
CERTIFIED = [("aa", "primary-0", 105.000, 105.200), ("bb", "primary-1", 106.000, 106.100),
             ("cc", "primary-2", 113.400, 113.500)]
STAGES = [  # kind, key, node, t_in, t_verdict, t_forwarded, outcome
    ("header", "aa", "primary-1", 105.010, 105.029, 105.030, "verified"),
    ("header", "aa", "primary-2", 105.020, 105.049, 105.050, "verified"),  # overlaps: union 0.040
    ("vote", "aa", "primary-0", 105.100, 105.119, 105.120, "verified"),
    ("vote", "aa", "primary-0", 105.110, 105.129, 105.130, "verified"),  # union 0.030
    ("certificate", "aa", "primary-1", 105.190, 105.229, 105.230, "verified"),  # clipped to 0.010
    ("header", "bb", "primary-0", 106.020, 106.039, 106.040, "verified"),  # 0.020
    ("header", "zz", "primary-0", 106.020, 106.039, 106.090, "verified"),  # another header's
]
WALKS = [  # node, certificates in, outputs, t_start, t_done
    ("primary-0", 1, 0, 102.000, 102.500),
    ("primary-0", 1, 0, 104.000, 104.001),
    ("primary-1", 3, 5, 104.500, 104.503),
    ("primary-2", 1, 9, 109.000, 109.026),
]
LAGS_QUIET = [  # due, woke, quiet wakes, their summed lateness
    (104.0, 104.001, 50, 0.050), (105.0, 105.150, 20, 0.020), (106.0, 106.080, 10, 0.010),
    (107.0, 107.300, 10, 0.010), (108.0, 108.0005, 8, 0.008), (101.0, 102.0, 0, 0.0),
]
LAGS_LATE = [(105.0, 105.150, 5, 0.005), (106.0, 106.080, 5, 0.005), (107.0, 107.300, 9, 0.009)]
COMPILES = [  # kernel, shapes, t, wall_s
    ("chain_commit", "s", 104.5, 0.3), ("place_batch", "s", 103.1, 0.5), ("roll_window", "s", 102.9, 0.2),
    ("reach_mask", "s", 113.2, 0.1), ("leader_support", "s", 113.2, 0.5),
]
WAL = [(3, 0.001, t) for t in (102.0, 103.5, 104.5, 105.5, 106.5, 112.9, 113.5)]


def fill(lags=LAGS_QUIET, firsts=(100.004, 100.0, 100.02)) -> None:
    tracing.new_generation()
    for i, t in enumerate(firsts):
        tracing.flight("ingest_first", f"worker-{i}", t)
    for kind, rows in (("flush", FLUSHES), ("wake", WAKES), ("stage", STAGES), ("walk", WALKS),
                       ("lag", lags), ("compile", COMPILES), ("wal_flush", WAL)):
        for row in rows:
            tracing.flight(kind, *row)
    for key, node, t0, t1 in CERTIFIED:
        tracing.flight("certify", key, node, t0, t1)


@pytest.fixture(autouse=True)
def _empty_ring_afterwards():
    yield
    tracing.new_generation()


FLIGHT_METRICS = [
    "verify.queue_wait_ms", "verify.turnaround_ms", "verify.wake_lag_ms", "verify.row_fill_share",
    "verify.starved_share", "verify.held_share", "certify.verify_share", "commit.walk_ms",
    "loop.lag_p95_ms", "kernels.first_dispatches_in_window", "wal.flushes_per_tx",
]
BY_HAND = {
    # wait sums of flushes 1-4 over their 9 entries
    "verify.queue_wait_ms": 1000 * (0.010 + 0.008 + 0.006 + 0.005) / 9,
    # t_posted - t_seal: 0.020, 0.026, 0.022, 0.025
    "verify.turnaround_ms": 1000 * 0.093 / 4,
    "verify.wake_lag_ms": 1000 * 0.018 / 6,
    "verify.row_fill_share": 100 * 19 / (4 * B),
    # in flight 0.010 + 0.039 + 0.020 + 0.002 = 0.071; queued or in flight 0.010 + 0.044 + 0.025 + 0.010 = 0.089
    "verify.starved_share": 100 * (10 - 0.089) / 10,
    "verify.held_share": 100 * 0.018 / 10,
    # aa: 0.040 + 0.030 + 0.010 of 0.200; bb: 0.020 of 0.100
    "certify.verify_share": 100 * 0.100 / 0.300,
    "commit.walk_ms": (1 + 3 + 26) / 3,
    # 98 quiet wakes and 3 late ones: the 95th percentile falls among the quiet, at their mean
    "loop.lag_p95_ms": 1000 * 0.098 / 98,
    # [104.2, 104.5], [102.6, 103.1] and [112.7, 113.2] meet the window
    "kernels.first_dispatches_in_window": 3,
    "wal.flushes_per_tx": 5 / 10,
}


def test_the_eleven_are_entries_each_with_a_reader_of_its_own():
    """Found by name, in the order PR 26 entered them: entries appended
    after them (a new cell's, a new layer's) leave this test as it is."""
    names = [m["name"] for m in BENCH["per_layer"]]
    eleven = [m for m in BENCH["per_layer"] if m["name"] in FLIGHT_METRICS]
    assert [m["name"] for m in eleven] == FLIGHT_METRICS and set(BY_HAND) == set(FLIGHT_METRICS)
    readers = os.path.join(ROOT, "chipbench", "readers")
    assert all(os.path.isfile(os.path.join(readers, f"{name}.py")) for name in FLIGHT_METRICS)
    counts = {m["name"] for m in eleven if m["source"] == "program_counter"}
    assert counts == {"verify.row_fill_share", "kernels.first_dispatches_in_window", "wal.flushes_per_tx"}
    assert all(m["source"] == "program_span" for m in eleven if m["name"] not in counts)
    assert "flight_window" not in names  # the helper's file is no metric's


@pytest.mark.parametrize("metric", FLIGHT_METRICS)
def test_reader_on_a_hand_built_ring(metric):
    fill()
    assert runner.load_reader(metric)(OBS) == pytest.approx(BY_HAND[metric], rel=1e-6)


@pytest.mark.parametrize("metric", FLIGHT_METRICS)
def test_reader_returns_nothing_on_an_empty_ring_or_a_program_without_one(metric, monkeypatch):
    tracing.new_generation()
    assert runner.load_reader(metric)(OBS) is None
    fill(firsts=())  # records, but no first submission to cut the window from
    assert runner.load_reader(metric)(OBS) is None
    fill()
    monkeypatch.delattr(tracing, "flight_dump")  # the parent of the PR that added the ring
    assert runner.load_reader(metric)(OBS) is None


def test_the_window_is_cut_from_the_first_submission_and_the_ramp():
    fill()
    win = fw.window(OBS)
    assert (win.t0, win.t1) == (103.0, 113.0)
    assert [f.seq for f in fw.within(win, "flush", "t_seal")] == [1, 2, 3, 4]
    assert win.period == tracing.HEARTBEAT_PERIOD  # the lag floor is the program's
    assert fw.window({"mix": {}, "seconds": 2.0}).t0 == 100.0  # a mix without a ramp


def test_starved_held_and_in_flight_make_the_whole_window():
    fill()
    starved, held, in_flight = fw.verify_shares(fw.window(OBS))
    assert in_flight == pytest.approx(0.71) and held == pytest.approx(0.18)
    assert starved + held + in_flight == pytest.approx(100.0, abs=1e-9)
    assert fw.union([(1, 3), (2, 5), (7, 8), (8, 9), (20, 30)], 0, 10) == [(1, 5), (7, 9)]


def test_the_lag_percentile_among_the_late_wakes():
    fill(lags=LAGS_LATE)  # 19 quiet wakes, late ones of 80, 150 and 300 ms: rank 20 of 22
    assert runner.load_reader("loop.lag_p95_ms")(OBS) == pytest.approx(150.0)


def test_readers_with_no_record_of_their_kind_in_the_window():
    tracing.new_generation()
    tracing.flight("ingest_first", "worker-0", 100.0)
    for metric in FLIGHT_METRICS:
        value = runner.load_reader(metric)(OBS)
        assert value == (0.0 if metric in ("kernels.first_dispatches_in_window", "wal.flushes_per_tx") else None)
    assert runner.load_reader("wal.flushes_per_tx")(dict(OBS, executed_in_window=0)) is None


# The 22 readers of the ring (the eleven above and the loop account's eleven) on
# one hand-built ring of both kinds of record, 50 rounds in the window.
RING_METRICS = FLIGHT_METRICS + LOOP_METRICS
RING_OBS = dict(OBS, window={"rounds": 50.0})
RING_BY_HAND = {**BY_HAND, **LOOP_BY_HAND}


def fill_both() -> None:
    fill()
    for row in LOOPS:
        tracing.flight("loop", *row)
    for row in OWNERS:
        tracing.flight("owner", *row)


def test_the_readers_keep_reading_the_snapshot_after_the_ring_is_overwritten():
    """What the run keeps when its drain ends is what the readers read, however
    much the committee writes after it (the trace write, the shutdown)."""
    fill_both()
    obs = dict(RING_OBS, flight=runner.take_flight(traced=True, acked=1))
    for _ in range(tracing.FLIGHT_RING):
        tracing.flight("kernel_load", "msm_accumulate_kernel", "uint8[2048,112]", "hit", 500.0, 0.01)
    assert not any(r.kind == "ingest_first" for r in tracing.flight_dump()["events"])
    for metric in RING_METRICS:
        assert runner.load_reader(metric)(obs) == pytest.approx(RING_BY_HAND[metric], rel=1e-6), metric
        assert runner.load_reader(metric)(RING_OBS) is None, metric  # the live ring lost the window


@pytest.mark.parametrize("metric", RING_METRICS)
def test_the_snapshot_and_the_live_ring_read_alike(metric):
    fill_both()
    obs = dict(RING_OBS, flight=runner.take_flight(traced=True, acked=1))
    assert runner.load_reader(metric)(obs) == runner.load_reader(metric)(RING_OBS)


def test_a_traced_run_whose_snapshot_lost_the_window_is_a_harness_fault():
    fill(firsts=())  # the ring pushed the first submissions out
    with pytest.raises(runner.HarnessFault, match=f"records kept by a ring of {tracing.FLIGHT_RING}, which cover"):
        runner.take_flight(traced=True, acked=3)
    # Untraced, or no client answered: no reader needs the window.
    assert runner.take_flight(traced=False, acked=3)["ring_capacity"] == tracing.FLIGHT_RING
    assert runner.take_flight(traced=True, acked=0)["events"]


def test_the_snapshots_record_gives_its_size_and_the_rings_headroom():
    fill()
    flight = runner.take_flight(traced=True, acked=1)
    flight["t_taken"] = 114.0  # a drain of 1 s past the window [103, 113]
    kept = fw.coverage(dict(OBS, flight=flight))
    # 5 flushes, 3 wakes, 7 stages, 4 walks, 6 lags, 5 compiles, 7 WAL flushes, 3 firsts, 3 certified.
    # Written in the window: 4 + 2 + 7 + 3 + 5 + 2 + 5 + 0 + 2; after it: 1 + 0 + 0 + 0 + 0 + 2 + 1 + 0 + 1.
    assert kept == {"records": 43, "ring_capacity": tracing.FLIGHT_RING, "seconds_kept": 14.0,
                    "headroom_s": 3.0, "per_s_window": 3.0, "per_s_drain": 5.0}
    assert fw.coverage(OBS) is None  # no snapshot taken
    fill(firsts=())
    flight = runner.take_flight(traced=False, acked=1)
    flight["t_taken"] = 114.0
    assert fw.coverage(dict(OBS, flight=flight))["headroom_s"] is None  # the window is lost


def test_rehearsal_traced_line_holds_the_three_counts():
    """The real entry on the CPU: the ring is filled by the program, survives
    the committee's shutdown, and the three count metrics reach the line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", NARWHAL_TPU_PREWARM="0",
               JAX_COMPILATION_CACHE_DIR=os.path.join(ROOT, "chipbench", "out", "jax_cache.cpu"),
               CHIPBENCH_REHEARSAL=json.dumps({"verify_bucket": 16, "validators": 4, "rate": 200,
                                               "parameters": {"commit_latency_target": 60}}))
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", "local-4x1.cruise", "--seed", str(2**31 + 26),
         "--seconds", "3", "--trace", "1"], cwd=ROOT, env=env, text=True, capture_output=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    metrics = result["metrics"]
    assert result["correct"] is True
    # 16-row buckets on the CPU: a few signatures, or the doubled rows of a proof, in each.
    assert 5 < metrics["verify.row_fill_share"]["value"] <= 100
    # The program's counter against the harness's own (compile_walls() diffed by key).
    out = json.load(open(os.path.join(ROOT, "chipbench", "out", f"local-4x1.cruise.{2**31 + 26}.json")))
    assert metrics["kernels.first_dispatches_in_window"]["value"] == len(
        out["observed"]["first_dispatches_in_window"])
    # Per executed transaction: on a crowded CPU the 3 s window may execute none.
    if out["observed"]["executed_in_window"]:
        assert metrics["wal.flushes_per_tx"]["value"] > 0
    else:
        assert "wal.flushes_per_tx" not in metrics
    # The snapshot of the ring is in the run's record by its size, not its records.
    kept = out["observed"]["flight"]
    assert kept["ring_capacity"] == 2**18 and "events" not in kept and 0 < kept["records"] < 2**18
    assert kept["headroom_s"] >= 3.0 and kept["per_s_window"] > 0  # the ring holds the ramp and the boot
    # A CPU run prints counts only: none of the eight spans.
    assert not set(metrics) & (set(FLIGHT_METRICS) - {"verify.row_fill_share", "wal.flushes_per_tx",
                                                      "kernels.first_dispatches_in_window"})
