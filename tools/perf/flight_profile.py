"""The process flight ring laid on a profiler trace taken by hand.

    python3 -m tools.perf.flight_profile --xplane <dir or .xplane.pb> --flight <dump.json>
    python3 -m tools.perf.flight_profile --drive 8        # on the chip, through chipbench's set-up
    python3 -m tools.perf.flight_profile --drive 8 --split  # and what a flush's `seal -> dispatched` is made of
    python3 -m tools.perf.flight_profile --micro          # what one record, add, mark, nested site or socket call costs here
    python3 -m tools.perf.flight_profile --owners --drive 51          # the loop's time by owner, a whole window of the cell
    python3 -m tools.perf.flight_profile --owners --flight <dump.json>  # of a dump's whole span, per second

What reads the program's four profiler marks (`narwhal/verify_submit`,
`narwhal/verify_collect`, `narwhal/commit_walk`, `narwhal/execute`:
tracing.annotation) and the fields of the ring no benchmark metric reads
(`stage.msg`, `t_verdict`, `outcome`; `wake.lag_max`). Given a
`jax.profiler` trace with `host_tracer_level >= 1` and the ring's dump
(`tracing.flight_dump()`, or the `"process"` entry of
`Telemetry.DumpFlightRecorder`) of the same seconds, it reports:

* the clock: the offset that lays the ring's `time.monotonic()` stamps on the
  profiler's clock, taken where both stamped one instant (a flush's
  `t_dispatched` is the end of its `narwhal/verify_submit` mark, matched by
  `seq`), and how far that is from the ring's (`monotonic`, `time_ns`) anchor;
* for each flush in the trace, where its device program starts against
  `t_dispatched`;
* the longest idle gaps of the device, each put down to `starved` (both
  verify lanes empty, nothing in flight), `held` (an entry queued or being
  packed) or `in flight`, with the marks and the late heartbeats over it;
* the verifier stage's hops by message kind and outcome;
* with `--split`, what `TpuVerifier.submit` / `submit_groups` spend where, per
  lane, on the host's clock: the native precheck (SHA-512, canonicality), the
  native fold, the jit call (operands handed over and the program enqueued),
  the readback starts, and the rest (Python and numpy: staging the rows). The
  parts are timed by wrapping names a tree before and after ISSUE 30 both has,
  so `PYTHONPATH=<other checkout> python3 <this file> --drive 8 --split`
  splits that checkout's flush with this file.

`--owners` reads the loop account (`loop` and `owner` records: every callback
the event loop ran while the account kept a stretch, charged to the task,
handler or wire tag that ran it) through the benchmark's own arithmetic
(`chipbench.readers.loop_account`) and prints the window's busiest loop: how
busy it was, its callbacks a second, its families and its twenty largest
owners by milliseconds a round (and the network family's `net:` parts
whatever their rank), with calls a round and each owner's longest stretch.
With `--drive` the window and its rounds are the cell's own, nothing is
profiled, and the fifteen `loop.*` readers' values and the three `wire.*`
counts of the drainers are printed beside the table; a dump alone is read
over its whole span, per second.

A mark around an `await` (`commit_walk`, `execute`) is as wide as its
coroutine's wall time, other tasks' turns included; what held the loop is the
`lag` records' to say. `--drive` needs a device and `--owners` the
benchmark's readers: the only parts that import `chipbench`; the rest is
arithmetic on what it is given.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import statistics
import sys
import time

_T_PROC = time.monotonic()

from narwhal_tpu import tracing  # noqa: E402

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KERNEL = "msm_accumulate_kernel"
MARK_PREFIX = "narwhal/"

# marks: name -> [(start_ns, end_ns, {stat: value})]; programs: [(start_ns,
# end_ns, kernel)] sorted; busy: disjoint device-busy intervals, sorted.
Profile = collections.namedtuple("Profile", "marks programs busy")


def typed(events) -> dict[str, list]:
    """The dump's events by kind, each a `tracing.FLIGHT_RECORD` (a dump
    that went through JSON holds plain rows)."""
    by: dict[str, list] = collections.defaultdict(list)
    for row in events:
        by[row[0]].append(tracing.FLIGHT_RECORD[row[0]](*row))
    return by


def union(intervals) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def overlap(disjoint, a: float, b: float) -> float:
    return sum(max(0.0, min(y, b) - max(x, a)) for x, y in disjoint)


def read_planes(planes, platform: str = "TPU") -> Profile:
    """`planes` as `jax.profiler.ProfileData.planes` gives them."""
    marks: dict[str, list] = collections.defaultdict(list)
    programs, ops = [], []
    for plane in planes:
        device = plane.name.startswith(f"/device:{platform}:")
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                start, end = int(ev.start_ns), int(ev.start_ns + ev.duration_ns)
                if not device:
                    if ev.name.startswith(MARK_PREFIX):
                        marks[ev.name].append((start, end, dict(ev.stats)))
                elif line.name == MODULES_LINE:
                    name = ev.name.split("(")[0]
                    programs.append((start, end, name[4:] if name.startswith("jit_") else name))
                else:
                    ops.append((start, end))
    programs.sort()
    return Profile(dict(marks), programs, union(ops or [(s, e) for s, e, _ in programs]))


def read_xplane(path: str, platform: str = "TPU") -> Profile:
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = sorted(
            os.path.join(root, f) for root, _, files in os.walk(path) for f in files if f.endswith(".xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    return read_planes(ProfileData.from_file(path).planes, platform)


def clock_offset_ns(flushes, submit_marks) -> tuple[float, int, float] | None:
    """(offset, flushes matched, widest residual): profiler ns = ring
    seconds x 1e9 + offset. A flush's `t_dispatched` and the end of the
    submit mark with its `seq` are one instant on two clocks."""
    ends = {int(stats["seq"]): end for _, end, stats in submit_marks if "seq" in stats}
    deltas = [ends[f.seq] - f.t_dispatched * 1e9 for f in flushes if f.seq in ends and f.failure is None]
    if not deltas:
        return None
    offset = statistics.median(deltas)
    return offset, len(deltas), max(abs(d - offset) for d in deltas)


def kernel_leads(flushes, programs, to_ns, kernel: str = KERNEL) -> list[dict]:
    """For each flush dispatched inside the trace, its program (the nearest
    start among the next three of `kernel`, in order) against `t_dispatched`."""
    starts = [(s, e) for s, e, name in programs if name == kernel]
    if not starts:
        return []
    lo, hi = starts[0][0], starts[-1][1]
    out, nxt = [], 0
    for f in sorted(flushes, key=lambda f: f.t_dispatched):
        td = to_ns(f.t_dispatched)
        if not lo - 20e6 <= td <= hi or nxt >= len(starts) or f.failure is not None:
            continue
        j = min(range(nxt, min(nxt + 3, len(starts))), key=lambda k: abs(starts[k][0] - td))
        nxt = j + 1
        out.append({
            "seq": f.seq, "lane": f.lane, "rows": [f.useful, f.padded],
            "kernel_start_after_dispatched_ms": (starts[j][0] - td) / 1e6,
            "seal_to_dispatched_ms": 1e3 * (f.t_dispatched - f.t_seal),
            "dispatched_to_posted_ms": 1e3 * (f.t_posted - f.t_dispatched),
            "kernel_ms": (starts[j][1] - starts[j][0]) / 1e6,
        })
    return out


def attribute_gaps(profile: Profile, flushes, lags, to_ns, top: int = 10) -> list[dict]:
    """The `top` longest idle gaps of the device, each split into starved,
    held and in flight (per cent of the gap; chipbench/readers/flight_window
    `verify_shares` makes the same split of a whole window), with the
    milliseconds of each mark over it and the late heartbeats that woke in
    it or within 50 ms after."""
    busy = profile.busy
    gaps = sorted(((b - a, a, b) for (_, a), (b, _) in zip(busy, busy[1:])), reverse=True)[:top]
    flying = union((to_ns(f.t_dispatched), to_ns(f.t_posted)) for f in flushes)
    either = union(flying + [(to_ns(f.t_oldest), to_ns(f.t_dispatched)) for f in flushes])
    late = [(to_ns(r.woke), 1e3 * (r.woke - r.due)) for r in lags if r.woke - r.due > tracing.HEARTBEAT_PERIOD]
    out = []
    for dur, a, b in gaps:
        fly, queued_or_fly = overlap(flying, a, b), overlap(either, a, b)
        over = {name[len(MARK_PREFIX):]: overlap(union((s, e) for s, e, _ in evs), a, b) / 1e6
                for name, evs in profile.marks.items()}
        out.append({
            "gap_ms": dur / 1e6,
            "starved": 100.0 * (dur - queued_or_fly) / dur,
            "held": 100.0 * (queued_or_fly - fly) / dur,
            "in_flight": 100.0 * fly / dur,
            "marks_ms": {k: v for k, v in over.items() if v > 0},
            "late_heartbeats_ms": [ms for at, ms in late if a <= at <= b + 50e6],
        })
    return out


def hops(stages, wakes) -> dict:
    """The verifier stage by message kind: messages, outcomes, mean in ->
    verdict and verdict -> forwarded (ms); and the longest posted -> resumed
    of any flush (ms)."""
    table = {}
    for msg in sorted({s.msg for s in stages}):
        rows = [s for s in stages if s.msg == msg]
        table[msg] = {
            "messages": len(rows),
            "outcomes": dict(collections.Counter(s.outcome for s in rows)),
            "in_to_verdict_ms": 1e3 * statistics.fmean(s.t_verdict - s.t_in for s in rows),
            "verdict_to_forwarded_ms": 1e3 * statistics.fmean(s.t_forwarded - s.t_verdict for s in rows),
        }
    return {"stage": table, "longest_wake_ms": 1e3 * max((w.lag_max for w in wakes), default=0.0)}


def report(dump: dict, profile: Profile) -> dict:
    by = typed(dump["events"])
    anchor = dump["anchor"]
    anchored = anchor["time_ns"] - anchor["monotonic"] * 1e9  # the offset if the profiler stamped time_ns
    found = clock_offset_ns(by["flush"], profile.marks.get(MARK_PREFIX + "verify_submit", ()))
    offset = found[0] if found else anchored

    def to_ns(t: float) -> float:
        return t * 1e9 + offset

    leads = kernel_leads(by["flush"], profile.programs, to_ns)
    return {
        "records": {k: len(v) for k, v in by.items()},
        "marks": {k: len(v) for k, v in profile.marks.items()},
        "programs": dict(collections.Counter(name for _, _, name in profile.programs)),
        "clock": {
            "offset_ns": offset,
            "from": "verify_submit marks" if found else "the ring's anchor (no mark matched a flush)",
            "flushes_matched": found[1] if found else 0,
            "widest_residual_us": found[2] / 1e3 if found else None,
            "profiler_minus_time_ns_s": (offset - anchored) / 1e9,
        },
        "kernel_start_after_dispatched_ms": leads,
        "device_busy_ms": sum(b - a for a, b in profile.busy) / 1e6,
        "slice_ms": (profile.busy[-1][1] - profile.busy[0][0]) / 1e6 if profile.busy else 0.0,
        "gaps": attribute_gaps(profile, by["flush"], by["lag"], to_ns),
        "hops": hops(by["stage"], by["wake"]),
    }


SPLIT_PARTS = ("precheck", "fold", "jit_call", "readback_start")


OWNERS_LISTED = 20  # and every `net:` label past them: the network family's parts


def owners(by, t0: float, t1: float, rounds: float | None = None) -> dict | None:
    """The loop account of [t0, t1] as a table: the busiest loop's seconds by
    family and by owner, per round where `rounds` says how many the window
    committed, else per second of the window."""
    from chipbench.readers import loop_account

    acct = loop_account.over(by, t0, t1)
    if acct is None:
        return None
    unit = (rounds or (t1 - t0)) / acct.scale  # rounds, or seconds, that the covered seconds stand for
    ranked = sorted(acct.owners.items(), key=lambda kv: kv[1][1], reverse=True)
    return {
        "window_s": t1 - t0, "covered_s": acct.covered, "rounds": rounds, "per": "round" if rounds else "second",
        "loop": acct.loop, "busy_share_pct": 100.0 * acct.busy / acct.covered,
        "offcpu_share_pct": 100.0 * acct.offcpu / acct.busy if acct.busy else None,
        "handles_per_s": acct.handles / acct.covered, "work_ms": 1000.0 * acct.busy / unit,
        "families_ms": {f: 1000.0 * s / unit for f, s in sorted(acct.families.items(), key=lambda kv: -kv[1])},
        "owners": [
            {"owner": owner, "family": family, "calls": calls / unit, "ms": 1000.0 * seconds / unit,
             "longest_ms": 1000.0 * longest}
            for i, ((owner, family), (calls, seconds, longest)) in enumerate(ranked)
            if i < OWNERS_LISTED or owner.startswith("net:")
        ],
    }


def install_split() -> list[dict]:
    """Time the parts of every `TpuVerifier.submit` / `submit_groups` from
    now on; returns the list that gets one row per call: lane, `total` and
    each of SPLIT_PARTS in seconds (`perf_counter`). A wrapper costs two
    clock reads; five to seven a flush."""
    import jax.numpy as jnp

    from narwhal_tpu.tpu.verifier import TpuVerifier

    rows: list[dict] = []
    parts: collections.Counter = collections.Counter()

    def timed(part, fn):
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                parts[part] += time.perf_counter() - t0
        return wrapper

    def whole(lane, fn):
        def wrapper(self, work):
            if not getattr(self._msm_kernel, "split_timed", False):
                self._msm_kernel = timed("jit_call", self._msm_kernel)
                self._msm_kernel.split_timed = True
            parts.clear()
            t0 = time.perf_counter()
            try:
                return fn(self, work)
            finally:
                rows.append({"lane": lane, "total": time.perf_counter() - t0, **parts})
        return wrapper

    TpuVerifier._precheck_native = timed("precheck", TpuVerifier._precheck_native)
    TpuVerifier._fold_native = timed("fold", TpuVerifier._fold_native)
    array = type(jnp.zeros(()))
    array.copy_to_host_async = timed("readback_start", array.copy_to_host_async)
    TpuVerifier.submit = whole("singles", TpuVerifier.submit)
    TpuVerifier.submit_groups = whole("groups", TpuVerifier.submit_groups)
    return rows


def split_report(rows: list[dict]) -> dict:
    """Medians in ms per lane; `rest` is what no wrapped part covers. The
    set-up's one full bucket is among the rows and far from any median."""
    out = {}
    for lane in sorted({r["lane"] for r in rows}):
        mine = [r for r in rows if r["lane"] == lane]
        med = {part: 1e3 * statistics.median(r.get(part, 0.0) for r in mine) for part in ("total", *SPLIT_PARTS)}
        med["rest"] = 1e3 * statistics.median(r["total"] - sum(r.get(p, 0.0) for p in SPLIT_PARTS) for r in mine)
        out[lane] = {"flushes": len(mine), **med}
    return out


def drive(seconds: float, slice_s: float) -> tuple[dict, dict]:
    """A few seconds of `local-4x1.cruise` through chipbench's own set-up,
    with a profiler slice of `slice_s` that is kept (none at 0): (the ring's
    dump, what the run observed; its `trace_dir` is the caller's)."""
    from chipbench import __main__ as entry
    from chipbench import run as runner

    args = entry.parse(["--workload", "local-4x1.cruise", "--seed", str(2**31 + 2601),
                        "--seconds", str(seconds), "--trace", "1" if slice_s else "0"])
    runner.TRACE_SLICE_S = slice_s
    ctx = runner.prepare(args, _T_PROC)
    rec = runner.measure(ctx, args, runner.cell_rate(ctx, args))
    print(json.dumps({"drive": {"correct": rec["correct"], "failed": rec["failed"],
                                "attempted": rec["attempted"], "device": ctx.device}}), flush=True)
    return tracing.flight_dump(), dict(rec["obs"], mix=ctx.mix)


def micro(n: int = 200_000) -> dict:
    """Microseconds per record, histogram add and mark (outside a profiler
    session) on this host: what the always-on recorder costs a site."""
    import timeit

    observe = tracing.LOOP_LAG.labels().observe
    keep = list(tracing.FLIGHT)
    timed = {
        "flight_flush_us": lambda: tracing.flight("flush", 1, "singles", 4, 4, 2048, 1.0, 0.01, 1.0, 1.0, 1.0, None),
        "flight_stage_us": lambda: tracing.flight("stage", "vote", "aa" * 32, "primary-x", 1.0, 1.0, 1.0, "verified"),
        "flight_lag_us": lambda: tracing.flight("lag", 1.0, 1.0, 50, 0.01),
        "histogram_observe_us": lambda: observe(0.0004),
        "mark_outside_session_us": lambda: tracing.annotation("narwhal/verify_submit", seq=1, lane="singles").__enter__(),
    }
    out = {name: 1e6 * timeit.timeit(fn, number=n) / n for name, fn in timed.items()}
    tracing.FLIGHT.clear()
    tracing.FLIGHT.extend(keep)
    return out


def sites(n: int = 200_000, calls: int = 20_000) -> dict:
    """Microseconds a `nested` site costs on this host: a clock reading and
    the call while the account keeps a stretch, the flag's read while it
    rests; the `is_closing()` read `_write_parts` makes before each
    `writelines`; and what a socket system call costs on loopback
    (`loopback_calls`)."""
    import asyncio
    import timeit

    keep = list(tracing.FLIGHT)

    def site() -> None:
        t0 = tracing.ACCOUNTING and time.perf_counter()
        if t0:
            tracing.nested("net:write", t0)

    async def body() -> dict:
        out = {"nested_site_resting_us": 1e6 * timeit.timeit(site, number=n) / n}
        tracing.heartbeat_acquire()
        try:
            await asyncio.sleep(0)  # the account times callbacks from the next one on
            assert tracing.ACCOUNTING
            out["nested_site_kept_us"] = 1e6 * timeit.timeit(site, number=n) / n
        finally:
            tracing.heartbeat_release()
        server = await asyncio.start_server(lambda r, w: None, "127.0.0.1", 0)
        _, writer = await asyncio.open_connection("127.0.0.1", server.sockets[0].getsockname()[1])
        out["is_closing_us"] = 1e6 * timeit.timeit(writer.transport.is_closing, number=n) / n
        writer.close()
        server.close()
        return out

    out = asyncio.run(body())
    out.update(loopback_calls(calls))
    tracing.FLIGHT.clear()
    tracing.FLIGHT.extend(keep)
    return out


HEADER_B, BODY_B = 21, 3200  # a frame header; a body of the cell's typical frame


def loopback_calls(n: int = 20_000, batch: int = 16) -> dict:
    """Microseconds a socket call costs on a loopback TCP connection on this
    host, so a `send` splits into a part per call and a part per byte: a
    21 B `send`, a 3,200 B `send`, a frame (header, body) as two `send`s and
    as one `sendmsg`; and through asyncio's socket transport, a frame as two
    `write`s (as the drainer wrote before it took `writelines`), as one
    `write` of the joined bytes, and a drain of 1.1 frames as one
    `writelines` (every tenth drain two frames), as `FrameSender` writes it;
    the last two again with the peer read by the same event loop. Only the
    calls are timed: after every `batch` of them the peer reads
    what arrived, untimed, so no call finds the socket full."""
    import asyncio
    import socket

    listener = socket.create_server(("127.0.0.1", 0))
    tx = socket.create_connection(listener.getsockname())
    rx, _ = listener.accept()
    listener.close()
    tx.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)  # as asyncio's TCP transports set it
    # `tx` stays blocking with no timeout: a socket with one polls before
    # every `send`, a second system call. A batch fits its buffers.
    rx.settimeout(10.0)
    header, body = bytes(HEADER_B), bytes(BODY_B)
    rounds = max(1, n // batch)

    def read_back(nbytes: int) -> None:
        while nbytes:
            nbytes -= len(rx.recv(min(nbytes, 1 << 20)))

    def timed(call, nbytes: int) -> float:
        spent = 0.0
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(batch):
                call()
            spent += time.perf_counter() - t0
            read_back(batch * nbytes)
        return 1e6 * spent / (rounds * batch)

    def two_sends() -> None:
        tx.send(header)
        tx.send(body)

    frame = HEADER_B + BODY_B
    out = {
        "send_21B_us": timed(lambda: tx.send(header), HEADER_B),
        "send_3200B_us": timed(lambda: tx.send(body), BODY_B),
        "two_sends_frame_us": timed(two_sends, frame),
        "sendmsg_frame_us": timed(lambda: tx.sendmsg([header, body]), frame),
    }

    async def transport(writer, frames_per_call, call) -> float:
        """Microseconds a `call(writer, frames)` takes through asyncio's
        socket transport; `frames_per_call(i)` frames on the i-th call."""
        spent, done = 0.0, 0
        for _ in range(rounds):
            counts = [frames_per_call(done + i) for i in range(batch)]
            t0 = time.perf_counter()
            for k in counts:
                call(writer, k)
            spent += time.perf_counter() - t0
            done += batch
            left = sum(counts) * frame
            while left:  # what the transport held back goes out as the loop turns
                try:
                    left -= len(rx.recv(1 << 20))
                except BlockingIOError:
                    await asyncio.sleep(0)
        return 1e6 * spent / done

    def two_writes(writer, k: int) -> None:
        writer.write(header)
        writer.write(body)

    def joined_write(writer, k: int) -> None:
        writer.write(header + body)

    def writelines(writer, k: int) -> None:
        writer.writelines([header, body] * k)

    async def to_loop_reader(frames_per_call, call) -> float:
        """As `transport`, with the peer a stream the same loop reads, as
        in a co-hosted committee: each send also makes a socket the loop
        polls readable."""
        got = [0]

        async def on_conn(reader, w) -> None:
            while chunk := await reader.read(1 << 20):
                got[0] += len(chunk)
            w.close()

        server = await asyncio.start_server(on_conn, "127.0.0.1", 0)
        _, writer = await asyncio.open_connection("127.0.0.1", server.sockets[0].getsockname()[1])
        spent, done, want = 0.0, 0, 0
        for _ in range(rounds):
            counts = [frames_per_call(done + i) for i in range(batch)]
            t0 = time.perf_counter()
            for k in counts:
                call(writer, k)
            spent += time.perf_counter() - t0
            done += batch
            want += sum(counts) * frame
            while got[0] < want:
                await asyncio.sleep(0)
        writer.close()
        await writer.wait_closed()
        server.close()
        await server.wait_closed()
        return 1e6 * spent / done

    async def both() -> None:
        rx.setblocking(False)
        _, writer = await asyncio.open_connection(sock=tx)
        out["transport_two_writes_frame_us"] = await transport(writer, lambda i: 1, two_writes)
        out["transport_joined_write_frame_us"] = await transport(writer, lambda i: 1, joined_write)
        out["writelines_drain_1.1_frames_us"] = await transport(
            writer, lambda i: 2 if i % 10 == 9 else 1, writelines)
        writer.close()
        await writer.wait_closed()
        out["loop_reader_two_writes_frame_us"] = await to_loop_reader(lambda i: 1, two_writes)
        out["loop_reader_writelines_drain_1.1_frames_us"] = await to_loop_reader(
            lambda i: 2 if i % 10 == 9 else 1, writelines)

    asyncio.run(both())
    rx.close()
    out["per_byte_ns"] = 1e3 * (out["send_3200B_us"] - out["send_21B_us"]) / (BODY_B - HEADER_B)
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m tools.perf.flight_profile")
    ap.add_argument("--xplane", help="a trace directory or an .xplane.pb")
    ap.add_argument("--flight", help="tracing.flight_dump() of the same seconds, as JSON")
    ap.add_argument("--drive", type=float, metavar="SECONDS", help="take both on this machine's device")
    ap.add_argument("--slice", type=float, default=1.0, help="seconds of profile kept by --drive")
    ap.add_argument("--platform", default="TPU")
    ap.add_argument("--micro", action="store_true", help="time the recorder's own calls on this host")
    ap.add_argument("--split", action="store_true", help="with --drive: split submit's host time into its parts")
    ap.add_argument("--owners", action="store_true", help="the loop's time by owner (no profile is taken or read)")
    args = ap.parse_args(argv)
    if args.micro:
        print(json.dumps({"micro": micro(), "sites": sites()}), flush=True)
        if not (args.drive or args.xplane):
            return 0
    if args.owners:
        out: dict = {}
        if args.drive:
            from chipbench import run as runner
            from chipbench.readers import flight_window

            dump, obs = drive(args.drive, 0.0)
            win = flight_window.window(obs)
            t0, t1, rounds = win.t0, win.t1, obs["window"]["rounds"]
            out["metrics"] = {
                name: runner.load_reader(name)(obs)
                for name in ("loop.busy_share", "loop.work_ms_per_round", "loop.offcpu_share",
                             *(f"loop.{family}_ms_per_round" for family in tracing.FAMILIES),
                             *(f"loop.net_{part}_ms_per_round" for part in ("write", "aead", "codec", "drainer")),
                             "wire.frames_per_drain", "wire.sends_per_frame", "wire.drainer_starts_per_drain")
            }
        elif args.flight:
            with open(args.flight) as f:
                dump = json.load(f)
            spans = [(r[2], r[3]) for r in dump["events"] if r[0] == "loop"]  # each kept stretch
            t0, t1, rounds = min((a for a, _ in spans), default=0.0), max((b for _, b in spans), default=0.0), None
        else:
            ap.error("--owners reads --flight, or takes --drive")
        out["loop_account"] = owners(typed(dump["events"]), t0, t1, rounds)
        print(json.dumps(out, indent=1))
        return 0
    if args.drive:
        split = install_split() if args.split else None
        dump, obs = drive(args.drive, args.slice)
        xplane = obs["trace_dir"]
        if split is not None:
            print(json.dumps({"submit_split_ms": split_report(split)}), flush=True)
    elif args.xplane and args.flight:
        with open(args.flight) as f:
            dump, xplane = json.load(f), args.xplane
    else:
        ap.error("give --xplane and --flight, or --drive")
    print(json.dumps(report(dump, read_xplane(xplane, args.platform)), indent=1))
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)  # --drive leaves the committee's threads behind
