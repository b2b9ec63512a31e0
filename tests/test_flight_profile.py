"""tools/perf/flight_profile.py: the process flight ring laid on a profiler
trace, on a hand-built ring and hand-built planes against values worked by
hand. No device and no profiler: the planes are stand-ins with the fields
`jax.profiler.ProfileData` gives."""

from __future__ import annotations

import json
from types import SimpleNamespace as NS

import pytest

from narwhal_tpu import tracing
from tools.perf import flight_profile as fp

# The profiler's clock: nanoseconds from its session's start, which was at
# 99.5 s on the ring's clock. So 100.000 s on the ring is 500 ms on the trace.
SESSION_T0 = 99.5


def ns(t: float) -> int:
    return round((t - SESSION_T0) * 1e9)


def event(name, t0, t1, **stats):
    return NS(name=name, start_ns=ns(t0), duration_ns=ns(t1) - ns(t0), stats=list(stats.items()))


def planes():
    host = NS(name="/host:CPU", lines=[
        NS(name="verify-collect", events=[event("narwhal/verify_collect", 100.010, 100.030, seq=1)]),
        NS(name="MainThread", events=[  # the loop: it seals and dispatches a flush itself
            event("narwhal/verify_submit", 100.002, 100.010, seq=1, lane="singles"),
            event("narwhal/verify_submit", 100.101, 100.105, seq=2, lane="groups"),
            event("some/other_trace_me", 100.0, 100.5),
            event("narwhal/execute", 100.035, 100.095, index=7),
            event("narwhal/commit_walk", 100.220, 100.230, seq=3, certs=4),
        ]),
    ])
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            event("jit_msm_accumulate_kernel(123)", 100.009, 100.016),
            event("jit_msm_accumulate_kernel(123)", 100.1045, 100.1115),
            event("jit_chain_commit(9)", 100.225, 100.226),
        ]),
        NS(name="XLA Ops", events=[
            event("%while.38", 100.009, 100.012), event("%while.39", 100.012, 100.016),
            event("%while.38", 100.1045, 100.1115), event("%fusion.2", 100.225, 100.226),
        ]),
        NS(name="Steps", events=[event("ignored", 100.0, 101.0)]),
    ])
    return [host, device]


def ring() -> dict:
    tracing.new_generation()
    # seq lane entries useful padded t_oldest wait_sum t_seal t_dispatched t_posted failure
    tracing.flight("flush", 1, "singles", 3, 3, 2048, 99.999, 0.004, 100.002, 100.010, 100.030, None)
    tracing.flight("flush", 2, "groups", 1, 6, 2048, 100.096, 0.005, 100.101, 100.105, 100.125, None)
    tracing.flight("flush", 3, "singles", 1, 1, 0, 100.300, 0.0, 100.300, 100.301, 100.301, "submit: boom")
    tracing.flight("wake", 1, 3, 0.009, 0.004, 100.030)
    tracing.flight("wake", 2, 1, 0.012, 0.012, 100.125)
    tracing.flight("lag", 100.040, 100.094, 3, 0.001)  # 54 ms late, woke inside the first gap
    tracing.flight("lag", 100.500, 100.501, 50, 0.02)  # a quiet second
    tracing.flight("stage", "header", "aa", "primary-1", 100.000, 100.040, 100.041, "verified")
    tracing.flight("stage", "header", "bb", "primary-2", 100.000, 100.020, 100.023, "verified")
    tracing.flight("stage", "vote", "aa", "primary-0", 100.100, 100.100, 100.100, "malformed")
    dump = tracing.flight_dump()
    tracing.new_generation()
    return json.loads(json.dumps(dump))  # as an operator gets it: plain rows


def test_planes_give_marks_programs_and_the_busy_union():
    profile = fp.read_planes(planes())
    assert {k: len(v) for k, v in profile.marks.items()} == {
        "narwhal/verify_submit": 2, "narwhal/verify_collect": 1, "narwhal/execute": 1, "narwhal/commit_walk": 1}
    assert profile.marks["narwhal/verify_submit"][0] == (ns(100.002), ns(100.010), {"seq": 1, "lane": "singles"})
    assert [name for _, _, name in profile.programs] == ["msm_accumulate_kernel"] * 2 + ["chain_commit"]
    assert profile.busy == [(ns(100.009), ns(100.016)), (ns(100.1045), ns(100.1115)), (ns(100.225), ns(100.226))]


def test_the_clock_is_laid_through_the_submit_marks_not_the_anchor():
    by = fp.typed(ring()["events"])
    assert by["flush"][0].t_dispatched == 100.010 and by["stage"][2].outcome == "malformed"
    offset, matched, residual = fp.clock_offset_ns(by["flush"], fp.read_planes(planes()).marks["narwhal/verify_submit"])
    assert matched == 2 and offset == pytest.approx(-SESSION_T0 * 1e9, abs=1.0) and residual < 1_000
    assert fp.clock_offset_ns(by["flush"], []) is None


def test_the_report_by_hand():
    out = fp.report(ring(), fp.read_planes(planes()))
    assert out["records"] == {"flush": 3, "wake": 2, "lag": 2, "stage": 3}
    assert out["clock"]["flushes_matched"] == 2 and out["clock"]["from"] == "verify_submit marks"
    # The profiler counts from its session's start, not from the epoch.
    assert abs(out["clock"]["profiler_minus_time_ns_s"]) > 1e6
    leads = out["kernel_start_after_dispatched_ms"]
    assert [(x["seq"], x["rows"]) for x in leads] == [(1, [3, 2048]), (2, [6, 2048])]
    # submit returns after the dispatch: the program starts inside the mark.
    assert leads[0]["kernel_start_after_dispatched_ms"] == pytest.approx(-1.0, abs=1e-3)
    assert leads[1]["kernel_start_after_dispatched_ms"] == pytest.approx(-0.5, abs=1e-3)
    assert leads[0]["dispatched_to_posted_ms"] == pytest.approx(20.0) and leads[0]["kernel_ms"] == pytest.approx(7.0)
    assert out["device_busy_ms"] == pytest.approx(15.0) and out["slice_ms"] == pytest.approx(217.0)
    long, short = out["gaps"]
    # 100.1115 -> 100.225: flush 2 in flight until 100.125, then nothing queued.
    assert long["gap_ms"] == pytest.approx(113.5)
    assert long["in_flight"] == pytest.approx(100 * 13.5 / 113.5) and long["held"] == pytest.approx(0.0)
    assert long["starved"] == pytest.approx(100 * 100.0 / 113.5)
    assert long["marks_ms"] == {"commit_walk": pytest.approx(5.0)} and long["late_heartbeats_ms"] == []
    # 100.016 -> 100.1045: flush 1 in flight to 100.030; flush 2 queued from 100.096 and packed to
    # 100.105; the executor's mark lies over 60 ms of it and the heartbeat woke 54 ms late in it.
    assert short["gap_ms"] == pytest.approx(88.5)
    assert short["in_flight"] == pytest.approx(100 * 14.0 / 88.5) and short["held"] == pytest.approx(100 * 8.5 / 88.5)
    assert short["starved"] + short["held"] + short["in_flight"] == pytest.approx(100.0)
    assert short["marks_ms"] == {"verify_collect": pytest.approx(14.0), "execute": pytest.approx(60.0),
                                 "verify_submit": pytest.approx(3.5)}
    assert short["late_heartbeats_ms"] == [pytest.approx(54.0)]
    hops = out["hops"]
    assert hops["longest_wake_ms"] == pytest.approx(12.0)
    assert hops["stage"]["header"] == {"messages": 2, "outcomes": {"verified": 2},
                                       "in_to_verdict_ms": pytest.approx(30.0),
                                       "verdict_to_forwarded_ms": pytest.approx(2.0)}
    assert hops["stage"]["vote"]["outcomes"] == {"malformed": 1}


def test_without_a_matching_mark_the_anchor_lays_the_clock():
    dump = ring()
    out = fp.report(dump, fp.Profile({}, [], []))
    assert out["clock"]["flushes_matched"] == 0 and out["clock"]["profiler_minus_time_ns_s"] == 0.0
    assert out["gaps"] == [] and out["kernel_start_after_dispatched_ms"] == []


def test_the_micro_timing_leaves_the_ring_as_it_was():
    tracing.new_generation()
    tracing.flight("ingest_first", "worker-0", 1.0)
    out = fp.micro(n=200)
    assert set(out) == {"flight_flush_us", "flight_stage_us", "flight_lag_us", "histogram_observe_us",
                        "mark_outside_session_us"} and all(v > 0 for v in out.values())
    assert list(tracing.FLIGHT) == [("ingest_first", "worker-0", 1.0)]
    tracing.new_generation()


def test_the_site_timing_keeps_a_stretch_and_leaves_the_ring_and_the_loop_as_they_were():
    tracing.new_generation()
    tracing.flight("ingest_first", "worker-0", 1.0)
    out = fp.sites(n=200, calls=64)
    assert set(out) == {"nested_site_resting_us", "nested_site_kept_us", "is_closing_us", *LOOPBACK}
    assert all(v > 0 for k, v in out.items() if k != "per_byte_ns")
    assert list(tracing.FLIGHT) == [("ingest_first", "worker-0", 1.0)]
    assert tracing.ACCOUNTING is False and not tracing._ACCOUNTS
    tracing.new_generation()


LOOPBACK = {"send_21B_us", "send_3200B_us", "two_sends_frame_us", "sendmsg_frame_us",
            "transport_two_writes_frame_us", "transport_joined_write_frame_us",
            "writelines_drain_1.1_frames_us", "loop_reader_two_writes_frame_us",
            "loop_reader_writelines_drain_1.1_frames_us", "per_byte_ns"}


def test_the_loopback_calls_time_each_way_a_frame_reaches_the_socket():
    """Each timing is of calls that all reached the peer: a run whose reads
    came up short would hang or raise, not print."""
    out = fp.loopback_calls(n=64, batch=16)
    assert set(out) == LOOPBACK
    assert all(v > 0 for k, v in out.items() if k != "per_byte_ns")
    assert out["per_byte_ns"] == 1e3 * (out["send_3200B_us"] - out["send_21B_us"]) / (3200 - 21)


def test_the_split_of_a_submit_by_hand():
    rows = [
        {"lane": "singles", "total": 0.004, "precheck": 0.001, "fold": 0.0002, "jit_call": 0.002, "readback_start": 0.0003},
        {"lane": "singles", "total": 0.006, "precheck": 0.001, "fold": 0.0002, "jit_call": 0.003, "readback_start": 0.0003},
        {"lane": "singles", "total": 30.0, "precheck": 0.2, "jit_call": 29.0},  # the set-up's bucket: far from the median
        {"lane": "groups", "total": 0.005, "precheck": 0.002, "jit_call": 0.002},  # no fold on this lane
    ]
    got = fp.split_report(rows)
    assert got["singles"] == pytest.approx(
        {"flushes": 3, "total": 6.0, "precheck": 1.0, "fold": 0.2, "jit_call": 3.0, "readback_start": 0.3, "rest": 1.5})
    assert got["groups"] == pytest.approx(
        {"flushes": 1, "total": 5.0, "precheck": 2.0, "fold": 0.0, "jit_call": 2.0, "readback_start": 0.0, "rest": 1.0})


def test_the_split_times_every_part_of_a_flush_on_either_lane(monkeypatch):
    """On the plain kernels (tests/plain_kernels.py): the wrapped names are
    the verifier's own, so a renamed method fails here and not on the chip."""
    import jax.numpy as jnp

    from narwhal_tpu.tpu.verifier import TpuVerifier
    from tests import test_verify_rows as rows_test

    if rows_test.verifier_mod._scalar_lib() is None:
        pytest.skip("native toolchain unavailable")
    for owner, name in ((TpuVerifier, "_precheck_native"), (TpuVerifier, "_fold_native"), (TpuVerifier, "submit"),
                        (TpuVerifier, "submit_groups"), (type(jnp.zeros(())), "copy_to_host_async")):
        monkeypatch.setattr(owner, name, getattr(owner, name))  # put back when the test ends
    rows = fp.install_split()
    v = rows_test.plain_verifier()
    assert v(rows_test.signatures(3)) == [True] * 3
    assert v.collect_groups(v.submit_groups(rows_test.certificate_groups(1))) == [True]
    assert [r["lane"] for r in rows] == ["singles", "groups"]
    assert set(rows[0]) == {"lane", "total", "precheck", "fold", "jit_call"}  # a host array starts no readback
    assert set(rows[1]) == {"lane", "total", "precheck", "jit_call"}
    for r in rows:
        assert 0 < r["precheck"] + r["jit_call"] <= r["total"]
    assert set(fp.split_report(rows)) == {"singles", "groups"}


# ---------------------------------------------------------------------------
# --owners: the loop account of a window
# ---------------------------------------------------------------------------


def account_ring() -> dict:
    tracing.new_generation()
    # loop, t0, t1, handles, busy_s, cpu_s, longest_s, longest_owner
    tracing.flight("loop", 1, 99.5, 100.5, 4000, 0.8, 0.7, 0.02, "rpc:HeaderMsg")  # half of it in [100, 102]
    tracing.flight("loop", 1, 100.5, 101.5, 5000, 0.9, 0.8, 0.03, "core:vote")
    tracing.flight("loop", 2, 100.0, 101.0, 100, 0.1, 0.1, 0.01, "tool.py:main")  # a loop less busy: not read
    # loop, t1, owner, family, calls, seconds, longest
    tracing.flight("owner", 1, 100.5, "rpc:HeaderMsg", "network", 40, 0.6, 0.02)
    tracing.flight("owner", 1, 100.5, "rest", "other", 100, 0.2, 0.001)
    tracing.flight("owner", 1, 101.5, "rpc:HeaderMsg", "network", 30, 0.5, 0.01)
    tracing.flight("owner", 1, 101.5, "core:vote", "primary", 20, 0.3, 0.03)
    tracing.flight("owner", 1, 101.5, "rest", "other", 90, 0.1, 0.002)
    tracing.flight("owner", 2, 101.0, "tool.py:main", "other", 100, 0.1, 0.01)
    return tracing.flight_dump()


def test_the_owner_table_of_a_window_per_round_and_per_second(tmp_path, capsys):
    dump = json.loads(json.dumps(account_ring()))
    table = fp.owners(fp.typed(dump["events"]), 100.0, 101.5, rounds=10)
    assert (table["loop"], table["per"], table["covered_s"]) == (1, "round", 1.5)
    assert table["busy_share_pct"] == pytest.approx(100 * (0.4 + 0.9) / 1.5)
    assert table["offcpu_share_pct"] == pytest.approx(100 * (0.05 + 0.1) / 1.3)
    assert table["handles_per_s"] == pytest.approx((2000 + 5000) / 1.5)
    assert table["work_ms"] == pytest.approx(130.0)
    assert table["families_ms"] == pytest.approx({"network": 80.0, "primary": 30.0, "other": 20.0})
    assert list(table["families_ms"]) == ["network", "primary", "other"]
    assert [(o["owner"], o["family"]) for o in table["owners"]] == [
        ("rpc:HeaderMsg", "network"), ("core:vote", "primary"), ("rest", "other")]
    head = table["owners"][0]
    assert (head["calls"], head["ms"], head["longest_ms"]) == pytest.approx((5.0, 80.0, 20.0))
    assert sum(o["ms"] for o in table["owners"]) == pytest.approx(table["work_ms"])
    # An account that kept 1.5 s of a 2 s window (it rests between stretches): those stand for the whole.
    part = fp.owners(fp.typed(dump["events"]), 100.0, 102.0, rounds=10)
    assert part["covered_s"] == 1.5 and part["work_ms"] == pytest.approx(130.0 * 2.0 / 1.5)
    assert part["busy_share_pct"] == pytest.approx(table["busy_share_pct"])
    assert fp.owners(fp.typed(dump["events"]), 200.0, 202.0) is None  # no second of the account in there
    # From the command line: the dump's whole span, per second where no rounds are given.
    path = tmp_path / "flight.json"
    path.write_text(json.dumps(dump))
    assert fp.main(["--owners", "--flight", str(path)]) == 0
    printed = json.loads(capsys.readouterr().out)["loop_account"]
    assert printed["per"] == "second" and (printed["window_s"], printed["covered_s"]) == pytest.approx((2.0, 2.0))
    assert printed["work_ms"] == pytest.approx(1000 * 1.7 / 2.0)
    assert len(printed["owners"]) == 3 <= fp.OWNERS_LISTED == 20


def test_the_owner_table_lists_the_network_familys_parts_past_the_twenty_largest():
    tracing.new_generation()
    tracing.flight("loop", 1, 100.0, 101.0, 4000, 0.6, 0.6, 0.02, "o0")
    for i in range(25):
        tracing.flight("owner", 1, 101.0, f"o{i}", "other", 10, 0.02, 0.001)
    for label, seconds in (("net:write", 0.03), ("net:aead", 0.001), ("net:codec", 0.002)):
        tracing.flight("owner", 1, 101.0, label, "network", 10, seconds, 0.001)
    table = fp.owners(fp.typed(tracing.flight_dump()["events"]), 100.0, 101.0, rounds=10)
    listed = [o["owner"] for o in table["owners"]]
    assert listed[0] == "net:write" and listed[-2:] == ["net:codec", "net:aead"]
    assert len(listed) == fp.OWNERS_LISTED + 2 and "o24" not in listed
