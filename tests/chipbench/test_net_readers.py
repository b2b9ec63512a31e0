"""The seven readers of the network family by part: three of `WireStats`'
counts in the window (frames a drain, sends a frame, drainer starts a drain)
and four of the loop account's network owners (`net:write`, `net:aead`,
`net:codec` and the drainer's own), each on synthetic counts or a hand-built
ring against a value worked by hand, and each returning nothing on a program
without its counter or label."""

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
WIRE_METRICS = ["wire.frames_per_drain", "wire.sends_per_frame", "wire.drainer_starts_per_drain"]
NET_METRICS = ["loop.net_write_ms_per_round", "loop.net_aead_ms_per_round", "loop.net_codec_ms_per_round",
               "loop.net_drainer_ms_per_round"]
DRAINER = "narwhal_tpu/network/rpc.py:FrameSender._drain_loop"

# The window's counts: 2,790 frames in 2,000 drains, 5,400 sends, 1,700 drainer starts.
WIRE = {"frames_sent": 2790, "bytes_sent": 11_000_000, "frames_received": 2790, "bytes_received": 11_000_000,
        "drains": 2000, "sends": 5400, "drainer_starts": 1700}
# The window: first submission at 100.0, a 3 s ramp, 10 s long: [103, 113]; 50 rounds in it.
OBS = {"mix": {"warm_s": 3.0}, "seconds": 10.0, "window": {"rounds": 50.0, "wire": WIRE}}

# loop, t0, t1, handles, busy_s, cpu_s, longest_s, longest_owner
LOOPS = [
    (1, 101.5, 102.5, 900, 0.6, 0.6, 0.01, "x"),  # before the window
    *((1, 103.5 + i, 104.5 + i, 2000, 0.6, 0.6, 0.02, "x") for i in range(9)),  # whole
    (1, 112.5, 113.75, 1000, 1.0, 1.0, 0.01, "x"),  # 0.5 s of its 1.25 inside: 0.4 of it
]
# What one whole second kept holds, by owner; its seconds sum to the record's busy_s.
SECOND = [
    ("net:write", "network", 300, 0.08), ("net:aead", "network", 600, 0.03), ("net:codec", "network", 500, 0.02),
    (DRAINER, "network", 250, 0.10), ("rpc:HeaderMsg", "network", 40, 0.05),
    ("narwhal_tpu/network/rpc.py:PeerLink.run", "network", 300, 0.04), ("rest", "network", 20, 0.01),
    ("core:vote", "primary", 20, 0.27),
]
# loop, t1, owner, family, calls, seconds, longest
OWNERS = [
    (1, 102.5, "net:write", "network", 300, 0.6, 0.01),
    *((1, 104.5 + i, owner, family, calls, seconds, 0.001) for i in range(9) for owner, family, calls, seconds in SECOND),
    (1, 113.75, "net:write", "network", 10, 0.5, 0.01), (1, 113.75, DRAINER, "network", 10, 0.25, 0.01),
    (1, 113.75, "rest", "other", 10, 0.25, 0.01),
]
OTHER_NETWORK_S = 9 * (0.05 + 0.04 + 0.01)  # the dispatch task, the link's reads and `rest`
SCALE = 10 / 9.5  # the records cover 9.5 s of the window's 10: seconds a round are scaled to the window
BY_HAND = {
    "wire.frames_per_drain": 2790 / 2000,
    "wire.sends_per_frame": 5400 / 2790,
    "wire.drainer_starts_per_drain": 1700 / 2000,
    "loop.net_write_ms_per_round": SCALE * 1000 * (9 * 0.08 + 0.4 * 0.5) / 50,
    "loop.net_aead_ms_per_round": SCALE * 1000 * 9 * 0.03 / 50,
    "loop.net_codec_ms_per_round": SCALE * 1000 * 9 * 0.02 / 50,
    "loop.net_drainer_ms_per_round": SCALE * 1000 * (9 * 0.10 + 0.4 * 0.25) / 50,
}


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


def test_the_seven_are_appended_entries_of_the_transport_layer_each_with_a_reader():
    entries = [m for m in BENCH["per_layer"] if m["name"] in BY_HAND]
    assert [m["name"] for m in entries] == WIRE_METRICS + NET_METRICS
    assert [m["name"] for m in BENCH["per_layer"][-7:]] == WIRE_METRICS + NET_METRICS
    assert {m["layer"] for m in entries} == {"transport / WAL (network/, storage.py)"}
    assert all(m["moves"] == "latency_p50_ms" and "workloads" not in m for m in entries)
    assert {m["name"] for m in entries if m["better"] == "higher"} == {"wire.frames_per_drain"}
    assert {m["name"]: m["source"] for m in entries} == {
        **{n: "program_counter" for n in WIRE_METRICS}, **{n: "program_span" for n in NET_METRICS}}
    assert all(m["unit"] == "ms" for m in entries if m["name"] in NET_METRICS)
    assert all(callable(runner.load_reader(m["name"])) for m in entries)


@pytest.mark.parametrize("metric", WIRE_METRICS)
def test_wire_reader_on_the_windows_counts(metric):
    assert runner.load_reader(metric)(OBS) == pytest.approx(BY_HAND[metric], rel=1e-12)


@pytest.mark.parametrize("metric", NET_METRICS)
def test_net_reader_on_a_hand_built_ring(metric):
    fill()
    assert runner.load_reader(metric)(OBS) == pytest.approx(BY_HAND[metric], rel=1e-9)


def test_the_four_parts_and_the_familys_other_owners_sum_to_the_network_family():
    fill()
    parts = sum(runner.load_reader(m)(OBS) for m in NET_METRICS)
    network = runner.load_reader("loop.network_ms_per_round")(OBS)
    assert parts + SCALE * 1000 * OTHER_NETWORK_S / 50 == pytest.approx(network, rel=1e-9)


def test_a_program_without_the_new_counters_reads_frames_a_drain_and_nothing_else():
    """The parent's `WireStats` counts frames and drains, not sends or
    drainer starts; and a window without a drain or a frame divides by
    nothing."""
    parent = dict(OBS, window=dict(OBS["window"], wire={k: v for k, v in WIRE.items()
                                                         if k not in ("sends", "drainer_starts")}))
    assert runner.load_reader("wire.frames_per_drain")(parent) == pytest.approx(2790 / 2000)
    assert runner.load_reader("wire.sends_per_frame")(parent) is None
    assert runner.load_reader("wire.drainer_starts_per_drain")(parent) is None
    idle = dict(OBS, window=dict(OBS["window"], wire=dict(WIRE, frames_sent=0, drains=0)))
    assert all(runner.load_reader(m)(idle) is None for m in WIRE_METRICS)


@pytest.mark.parametrize("metric", NET_METRICS)
def test_net_reader_returns_nothing_on_a_program_without_the_labels(metric, monkeypatch):
    # The parent's ring: the drainer owns its seals and writes, no `net:` label is written.
    fill(owners=[row for row in OWNERS if not row[2].startswith("net:")])
    assert runner.load_reader(metric)(OBS) is None
    tracing.new_generation()
    assert runner.load_reader(metric)(OBS) is None
    fill(loops=(), owners=())  # a window, and no account in it
    assert runner.load_reader(metric)(OBS) is None
    fill(loops=LOOPS[:1], owners=OWNERS[:1])  # an account that ended before the window opened
    assert runner.load_reader(metric)(OBS) is None
    fill()
    assert runner.load_reader(metric)(dict(OBS, window=dict(OBS["window"], rounds=0))) is None
    monkeypatch.delattr(tracing, "flight_dump")  # a program without the ring
    assert runner.load_reader(metric)(OBS) is None
