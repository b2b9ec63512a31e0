"""chipbench on the CPU: rehearsals of the driver's command, the plain
reference against the program, the data-driven harness, the control.

None of this is a measurement. The rehearsals run the real entry (Cluster,
both device backends, the msm verify kernel) at the smallest bucket and
committee that exercise the path, in processes of their own that share the
checkout's compile cache; every one of them pays the msm kernel's jit trace
(~30 s on XLA:CPU), so there are few.
"""

from __future__ import annotations

import hashlib
import json
import os
import random
import re
import shutil
import struct
import subprocess
import sys
import zlib
from collections import namedtuple

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import judge, run as runner, trace_reduce, traffic, work  # noqa: E402
from chipbench.reference import ed25519, formats  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
REHEARSAL = {"verify_bucket": 16, "validators": 4, "rate": 200,
             "parameters": {"commit_latency_target": 60}}
# XLA:CPU takes ~70 ms a flush, which puts the commit latency near the 4 s
# admission target; the quiet rehearsals lift that one target, and the
# overloaded one drops it under any commit's latency, so the workers shed.
OVERLOAD = {"verify_bucket": 16, "validators": 4, "rate": 2000,
            "parameters": {"commit_latency_target": 0.05}}


def chipbench(args: list[str], rehearsal: dict | None, cwd: str = ROOT, module: str = "chipbench",
              timeout: float = 900.0) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", NARWHAL_TPU_PREWARM="0")
    env.pop("CHIPBENCH_REHEARSAL", None)
    if rehearsal is not None:
        env["CHIPBENCH_REHEARSAL"] = json.dumps(rehearsal)
    # One compile cache for every rehearsal, a copy of the tree's too, and not
    # the directory a chip run of this checkout would use.
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, "chipbench", "out", "jax_cache.cpu")
    return subprocess.run(
        [sys.executable, "-m", module, *args], cwd=cwd, env=env, text=True,
        capture_output=True, timeout=timeout,
    )


def driver_args(cell: str, seed: int, trace: int, seconds: int = 3) -> list[str]:
    return ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]


def last_json(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

ENTRIES = (
    [("config", c) for c in BENCH["configs"]] + [("workload", w) for w in BENCH["workloads"]]
    + [("end_to_end", m) for m in BENCH["end_to_end"]] + [("per_layer", m) for m in BENCH["per_layer"]]
)


@pytest.mark.parametrize("kind,entry", ENTRIES, ids=[f"{k}:{e['name']}" for k, e in ENTRIES])
def test_entry_names_units_and_files(kind, entry):
    assert NAME.match(entry["name"])
    if kind == "config":
        assert entry["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        cfg = json.load(open(os.path.join(ROOT, entry["file"])))
        assert cfg["name"] == entry["name"] and set(entry["reduced"]) == set(cfg["reduced"])
        assert all(NAME.match(k) for k in entry["reduced"]) and cfg["guarantees"] and cfg["storage_engine"]
        assert judge.ordering_reference(cfg["consensus_protocol"]).commit_sequence  # its engine's plain rule
    elif kind == "workload":
        assert NAME.match(entry["config"]) and NAME.match(entry["traffic"]) and entry["chips"] in (1, 4)
        assert len(entry["why"]) <= 200
        assert traffic.load_mix(entry["traffic"])["rate_share_of_knee"] > 0
    else:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert set(entry.get("workloads", CELLS)) <= set(CELLS)
        if entry["name"] != "setup_s":
            assert callable(runner.load_reader(entry["name"]))
        if kind == "per_layer":
            assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
            if entry["name"].endswith("_roofline"):
                assert entry["unit"] == "%"


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in BENCH["end_to_end"] if cell in m.get("workloads", CELLS)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(cell in m.get("workloads", CELLS) for m in BENCH["per_layer"])
    assert BENCH["command"] == ["python3", "-m", "chipbench"] and 1 <= BENCH["run_seconds"] <= 51


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

MIX = {"rate_share_of_knee": 1.0, "tick_ms": 50, "drain_s": 5}


def test_same_seed_same_bytes_other_seed_same_sizes_and_instants():
    a, txs_a = traffic.schedule(MIX, 1230, 4, 2.0, 7, 512)
    b, txs_b = traffic.schedule(MIX, 1230, 4, 2.0, 7, 512)
    c, txs_c = traffic.schedule(MIX, 1230, 4, 2.0, 2**31 + 11, 512)
    assert [x.raw for x in a] == [x.raw for x in b] and txs_a == txs_b
    assert txs_a[1:] != txs_c[1:] and len(set(txs_a[1:])) == len(txs_a) - 1
    assert sorted(x.count for x in a) == sorted(x.count for x in c)
    assert sorted(round(x.due, 6) for x in a) == sorted(round(x.due, 6) for x in c)
    assert [x.lane for x in a] != [x.lane for x in c]  # another order
    assert all(len(t) == 512 for t in txs_a[1:])


@pytest.mark.parametrize("rate,lanes", [(1560, 4), (3900, 4), (333, 10), (50, 40)])
def test_offered_rate_is_met_exactly_and_bursts_parse(rate, lanes):
    bursts, txs = traffic.schedule(MIX, rate, lanes, 4.0, 3, 512)
    assert abs(len(txs) - 1 - rate * 4.0) <= lanes
    for b in bursts[:50]:
        assert formats.batch_transactions(b.raw) == txs[b.first_id : b.first_id + b.count]


def test_load_is_spread_evenly_over_the_lanes_and_the_ticks():
    bursts, _ = traffic.schedule(MIX, 1560, 4, 4.0, 1, 512)
    per_lane = [sum(b.count for b in bursts if b.lane == lane) for lane in range(4)]
    assert max(per_lane) - min(per_lane) <= 1
    per_tick = [sum(b.count for b in bursts if int(b.due / 0.05 + 1e-9) == k) for k in range(80)]
    assert max(per_tick) - min(per_tick) <= 4 and sum(per_tick) == 6240  # a lane owes 19.5 a tick


def test_the_compile_cache_is_the_environments_or_the_benchmarks_own(monkeypatch, tmp_path):
    from chipbench.__main__ import place_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    place_compile_cache(rehearsal=False)
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)
    own = os.path.join(ROOT, "chipbench", "out", "jax_cache.")
    for rehearsal, platform in ((True, "cpu"), (False, "device")):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        place_compile_cache(rehearsal)
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == own + platform and os.path.isdir(own + platform)


# ---------------------------------------------------------------------------
# the plain reference, against the RFC and against the program
# ---------------------------------------------------------------------------

RFC8032 = [  # (secret seed, public key, message, signature): RFC 8032 7.1, tests 1 and 2
    ("9d61b19deffd5a60ba844af492ec2cc44449c5697b326919703bac031cae7f60",
     "d75a980182b10ab7d54bfed3c964073a0ee172f3daa62325af021a68f707511a", "",
     "e5564300c360ac729086e2cc806e828a84877f1eb8e5d974d873e065224901555fb8821590a33bacc61e39701cf9b46bd25bf5f0595bbe24655141438e7a100b"),
    ("4ccd089b28ff96da9db6c346ec114e0f5b8a319f35aba624da8cf6ed4fb8a6fb",
     "3d4017c3e843895a92b70aa74d1b7ebc9c982ccf2ec4968cc0cd55f12af4660c", "72",
     "92a009a9f0d4cab8720e820b5f642540a2b27b5416503f8fb3762223ebdb69da085ac1e43e15996e458f3613d0f11d8c387b2eaeb4302aeeb00d291612bb0c00"),
]


@pytest.mark.parametrize("vector", RFC8032, ids=["rfc8032-1", "rfc8032-2"])
def test_plain_ed25519_on_rfc_vectors(vector):
    _, pk, msg, sig = (bytes.fromhex(x) for x in vector)
    assert ed25519.verify(pk, msg, sig)
    assert not ed25519.verify(pk, msg + b"x", sig)
    assert not ed25519.verify(pk, msg, sig[:-1] + bytes([sig[-1] ^ 0x10]))
    s_plus_l = (int.from_bytes(sig[32:], "little") + ed25519.L).to_bytes(32, "little")
    assert not ed25519.verify(pk, msg, sig[:32] + s_plus_l)  # non-canonical s


@pytest.fixture(scope="module")
def committee7():
    from narwhal_tpu.fixtures import CommitteeFixture

    return CommitteeFixture(size=7, seed=5)


def compact_certificate(fx, author: int, rnd: int, signers: list[int]):
    from narwhal_tpu.types import Certificate, Vote

    header = fx.header(author=author, round=rnd)
    sigs = tuple(
        Vote.for_header(header, fx.authorities[s].public, fx.authorities[s].keypair).signature
        for s in signers
    )
    idx = tuple(fx.committee.index_of(fx.authorities[s].public) for s in signers)
    order = sorted(range(len(idx)), key=lambda i: idx[i])
    return Certificate.compact_from_votes(
        header, tuple(idx[i] for i in order), tuple(sigs[i] for i in order)
    )


def reference_accepts(cert_bytes: bytes, authors: list[bytes]) -> bool:
    c = formats.decode_certificate(cert_bytes)
    voters = [authors[i] for i in c.signers]
    items = [(pk, formats.vote_digest(c, pk), r) for pk, r in zip(voters, c.rs)]
    return ed25519.verify(c.author, c.header_digest, c.signature) and ed25519.verify_half_aggregate(
        items, formats.aggregate_weights(c), c.agg_s
    )


def test_reference_reads_the_programs_certificates_and_proofs(committee7):
    fx = committee7
    authors = sorted(a.public for a in fx.authorities)
    cert = compact_certificate(fx, author=2, rnd=3, signers=[0, 1, 3, 4, 6])
    raw = cert.to_bytes()
    c = formats.decode_certificate(raw)
    assert c.digest == cert.digest and c.header_digest == cert.header.digest
    assert c.author == cert.origin and c.round == 3 and set(c.parents) == set(cert.header.parents)
    assert reference_accepts(raw, authors)
    # A proof altered where it is produced, and a signature moved to another header.
    forged = type(cert)(cert.header, cert.signers, cert.signatures,
                        bytes([cert.agg_s[0] ^ 1]) + cert.agg_s[1:])
    assert not reference_accepts(forged.to_bytes(), authors)
    other = compact_certificate(fx, author=2, rnd=4, signers=[0, 1, 3, 4, 6])
    swapped = type(cert)(other.header, cert.signers, cert.signatures, cert.agg_s)
    assert not reference_accepts(swapped.to_bytes(), authors)


@pytest.mark.parametrize("n,loss,seed", [(4, 0.0, 1), (4, 0.3, 2), (7, 0.25, 3), (10, 0.2, 4)])
def test_plain_bullshark_commits_what_the_programs_host_engine_commits(n, loss, seed):
    """The plain rule as the judge finds it, by the engine's name."""
    from narwhal_tpu.consensus import Bullshark, ConsensusState
    from narwhal_tpu.fixtures import CommitteeFixture, make_certificates
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.types import Certificate

    fx = CommitteeFixture(size=n, seed=seed)
    genesis = Certificate.genesis(fx.committee)
    gc_depth = 12  # shallow, so the garbage-collection rule is walked too
    certs, _ = make_certificates(
        fx.committee, 1, 40, {c.digest for c in genesis},
        failure_probability=loss, rng=random.Random(seed),
    )
    host, state, want = Bullshark(fx.committee, NodeStorage(None).consensus_store, gc_depth), ConsensusState(genesis), []
    for c in certs:
        want.extend(o.certificate.digest for o in host.process_certificate(state, len(want), c))
    Plain = namedtuple("Plain", "author round epoch parents digest")
    plain = [Plain(c.origin, c.round, c.epoch, tuple(sorted(c.header.parents)), c.digest) for c in certs]
    random.Random(seed).shuffle(plain)  # the union of stores comes in no order
    bullshark = judge.ordering_reference("bullshark")
    assert len(want) > n and bullshark.commit_sequence(plain, gc_depth) == want
    assert bullshark.leader_of(6, 0, sorted(fx.committee.authority_keys())) == fx.committee.leader(6)


def test_an_engine_without_a_plain_rule_is_named_by_its_missing_file():
    with pytest.raises(LookupError, match=re.escape("chipbench/reference/no-such-engine.py is missing")):
        judge.ordering_reference("no-such-engine")


def wal_record(ops) -> bytes:
    body = struct.pack("<I", len(ops))
    for name, key, value in ops:
        body += struct.pack("<BH", 0, len(name)) + name.encode() + struct.pack("<I", len(key)) + key
        body += struct.pack("<I", len(value)) + value
    return struct.pack("<II", len(body), zlib.crc32(body)) + body


def test_wal_reader_reads_the_programs_log_and_tells_a_damaged_record_from_a_torn_tail(tmp_path):
    from narwhal_tpu.storage import StorageEngine

    eng = StorageEngine(str(tmp_path / "s"), use_native=False)
    cf, other = eng.column_family("sequence"), eng.column_family("votes")
    for i in range(6):
        eng.write_batch([(cf, struct.pack(">Q", i), bytes([i]) * 32), (other, b"k%d" % i, b"v")])
    eng.close()
    info: dict = {}
    got = formats.read_wal(str(tmp_path / "s"), {"sequence"}, info=info)
    assert [(int.from_bytes(k, "big"), v) for k, v in got["sequence"]] == [(i, bytes([i]) * 32) for i in range(6)]
    assert info == {"records": 6, "unread_bytes": 0, "records_beyond_break": 0}

    recs = [wal_record([("sequence", bytes([i]) * 8, b"d" * 32)]) for i in range(5)]
    damaged = bytearray(recs[2])
    damaged[12] ^= 1
    for name, blob, beyond in (
        ("mid", recs[0] + recs[1] + bytes(damaged) + recs[3] + recs[4], 2),
        ("tail", recs[0] + recs[1] + recs[2][:-5], 0),
    ):
        d = tmp_path / name
        d.mkdir()
        (d / "wal.log").write_bytes(blob)
        info = {}
        assert len(formats.read_wal(str(d), {"sequence"}, info=info)["sequence"]) == 2
        assert info["unread_bytes"] > 0 and info["records_beyond_break"] == beyond


# ---------------------------------------------------------------------------
# the yardstick: bytes, peaks, the trace reduction, the readers
# ---------------------------------------------------------------------------


def test_verify_bucket_bytes_by_hand():
    # A (32) + R (32) + s (32) + k (32) + z (16) in, one verdict byte out.
    assert work.ROW_IN_BYTES == 144 and work.verify_bucket_bytes(2048) == 2048 * 145 == 296_960
    peaks = json.load(open(os.path.join(ROOT, "chipbench", "peaks.json")))
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9 and "source" in peaks["TPU v5 lite"]
    assert work.least_seconds(2048, peaks["TPU v5 lite"]) == pytest.approx(296_960 / 819e9)


def test_trace_reduction_on_a_recorded_v5e_trace():
    """Two `msm_accumulate_kernel` programs recorded on a TPU v5 lite (PR 25),
    cut to the first 120 operations of each: 141 kB."""
    path = os.path.join(os.path.dirname(__file__), "v5e_two_programs.xplane.pb")
    r = trace_reduce.reduce_file(path)
    assert r["devices"] == 1 and r["lines"] == {
        "/device:TPU:0|XLA Modules": 2, "/device:TPU:0|XLA Ops": 240}
    k = r["kernels"]["msm_accumulate_kernel"]
    assert k["events"] == 2 and k["seconds"] == pytest.approx(0.013233041, rel=1e-6)
    # Busy time by brute force over the same events.
    from jax.profiler import ProfileData

    plane = ProfileData.from_file(path).find_plane_with_name("/device:TPU:0")
    ops = [e for line in plane.lines if line.name == "XLA Ops" for e in line.events]
    marks = sorted({int(e.start_ns) for e in ops} | {int(e.start_ns + e.duration_ns) for e in ops})
    busy = sum(
        b - a for a, b in zip(marks, marks[1:])
        if any(e.start_ns <= a and b <= e.start_ns + e.duration_ns for e in ops)
    )
    assert r["busy_s"] == pytest.approx(busy / 1e9, rel=1e-9) and 0 < r["busy_s"] < k["seconds"]
    assert r["idle_gaps"][0][0] == "after:msm_accumulate_kernel" and r["idle_gaps"][0][1] > 0.03
    assert len(r["device_ops"]) == 10 and all(len(name) <= 96 for name, _ in r["device_ops"])
    assert trace_reduce.reduce_file(path, platform="GPU") is None


def test_names_of_programs_and_operations():
    assert trace_reduce.module_name("jit_msm_accumulate_kernel(16393633039096773586)") == "msm_accumulate_kernel"
    assert trace_reduce.op_name("%while.38 = (s32[]{:T(128)}, s32[20,4096]{1,0}) while((s32[]) %t), body=%b") == "%while.38 while"
    assert trace_reduce.op_name("fusion.3") == "fusion.3"


def test_readers_return_nothing_where_there_is_nothing_to_read():
    obs = {"trace": None, "trace_window_s": 0.0, "verify_bucket": 2048,
           "window": {"rounds": 0, "seconds": 3.0, "flushes": {}, "wire": {"frames_sent": 0, "bytes_sent": 0},
                      "stages": {s: (0.0, 0) for s in ("seal", "certify", "commit", "execute")}},
           "executed_in_window": 0, "latency": {}, "late": {}, "attempted": 0}
    for name in ("msm_accumulate_kernel_roofline", "verify_kernel.device_ms", "device.idle_share",
                 "round.mean_ms", "seal.mean_ms", "verify.flushes_per_round", "wire.bytes_per_tx",
                 "latency_p50_ms", "cruise.latency_p95_ms", "client.late_p95_ms", "ingest.shed_share"):
        assert runner.load_reader(name)(obs) is None, name
    traced = dict(obs, trace={"busy_s": 0.1, "kernels": {"msm_accumulate_kernel": {"seconds": 0.066, "events": 10}}},
                  trace_window_s=0.5, peaks={"hbm_bytes_per_s": 819e9})
    assert runner.load_reader("device.idle_share")(traced) == pytest.approx(80.0)
    assert runner.load_reader("verify_kernel.device_ms")(traced) == pytest.approx(6.6)
    share = runner.load_reader("msm_accumulate_kernel_roofline")(traced)
    assert share == pytest.approx(100 * (296_960 / 819e9) / 0.0066) and 0 < share < 1


@pytest.mark.parametrize("number", list(judge.LIMITS))
def test_every_limit_is_a_number_compared_and_all_have_to_be_there(number):
    sound = {k: 0 for k in judge.LIMITS}
    assert judge.verdict(sound) == (True, {k: [0, 0] for k in judge.LIMITS})
    assert judge.verdict(dict(sound, **{number: 1}))[0] is False
    missing = dict(sound)
    del missing[number]
    assert judge.verdict(missing)[0] is False


# ---------------------------------------------------------------------------
# how a run ends
# ---------------------------------------------------------------------------


def test_no_chip_and_no_rehearsal_exits_2_with_no_result():
    proc = chipbench(driver_args(CELLS[0], 11, 0), rehearsal=None, timeout=300)
    assert proc.returncode == 2
    last = proc.stdout.strip().splitlines()[-1]
    assert last.startswith("chipbench: harness fault, no result") and "no TPU" in last
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "NoChip" in open(os.path.join(ROOT, "chipbench", "out", "last_failure.txt")).read()


def test_unknown_cell_is_a_harness_fault_with_its_reason_on_stdout():
    proc = chipbench(driver_args("no-such.cell", 12, 0), rehearsal=REHEARSAL, timeout=300)
    assert proc.returncode == 1 and "no workload 'no-such.cell'" in proc.stdout.strip().splitlines()[-1]
    assert "Traceback" in proc.stderr


def test_a_directory_with_only_the_benchmark_exits_nonzero_with_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p, ignore=shutil.ignore_patterns("out", "__pycache__"))
    proc = chipbench(driver_args(CELLS[0], 13, 0), rehearsal=REHEARSAL, cwd=str(tmp_path), timeout=300)
    assert proc.returncode != 0 and not any(line.startswith("{") for line in proc.stdout.splitlines())


# ---------------------------------------------------------------------------
# rehearsals of the driver's command (each its own process, ~1 min)
# ---------------------------------------------------------------------------


def test_rehearsal_cruise_untraced_ends_in_its_one_line():
    proc = chipbench(driver_args("local-4x1.cruise", 2**31 + 77, 0), rehearsal=REHEARSAL)
    result = last_json(proc)
    assert set(result) == RESULT_KEYS | {"checks"} and list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] == 600
    assert set(result["checks"]) == set(judge.LIMITS)
    assert set(result["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert result["device"]["platform"] == "cpu"
    # A CPU run prints counts only: every end-to-end metric is a time or a rate.
    assert result["metrics"] == {}
    tail = proc.stderr.strip().splitlines()
    assert tail[-1] == "chipbench correct: True" and tail[-2].startswith("chipbench check ")
    out = json.load(open(os.path.join(ROOT, "chipbench", "out", f"local-4x1.cruise.{2**31 + 77}.json")))
    assert out["rehearsal"] is True and out["notes"]["certificates_sampled"] >= 8
    assert out["notes"]["validator_commits"] and min(out["notes"]["validator_commits"]) > 0
    assert {"imports_native_s", "verify_warm_s", "boot_s", "schedule_s", "teardown_s"} <= set(out["setup"])


def test_rehearsal_traced_and_overloaded_still_exits_0_with_failed_counts():
    proc = chipbench(driver_args("local-4x1.cruise", 78, 1), rehearsal=OVERLOAD)
    result = last_json(proc)
    assert set(result) == RESULT_KEYS | {"checks"} and result["correct"] is True
    assert result["attempted"] == 6000 and 0 < result["failed"] <= result["attempted"]
    assert {"busy_s", "window_s"} <= set(result["device"]) and result["device"]["window_s"] > 0
    counts = {m["name"] for m in BENCH["per_layer"] if m["source"] == "program_counter"
              and "local-4x1.cruise" in m.get("workloads", CELLS)}
    assert set(result["metrics"]) <= counts and "ingest.shed_share" in result["metrics"]
    assert result["metrics"]["ingest.shed_share"]["value"] > 0
    assert result["metrics"]["verify.detours"]["value"] == 0


# What a PR that only appends has to leave passing: every entry, every cell,
# and the pins of the per-layer entries, which go by name.
STRUCTURAL = [
    "tests/chipbench/test_chipbench.py::test_entry_names_units_and_files",
    "tests/chipbench/test_chipbench.py::test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric",
    "tests/chipbench/test_flight_readers.py::test_the_eleven_are_entries_each_with_a_reader_of_its_own",
    "tests/chipbench/test_loop_readers.py::"
    "test_the_eleven_are_entries_a_reader_each_by_the_accounts_families_and_the_helper_is_no_metric",
]


def test_a_copy_without_git_cache_and_libraries_runs_a_cell_that_only_new_files_add(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(ROOT, copy, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "*.so", "out", "chiprun_out", "__pycache__", ".pytest_cache", ".archive_check"))
    before = {
        os.path.relpath(os.path.join(d, f), copy): hashlib.sha256(open(os.path.join(d, f), "rb").read()).hexdigest()
        for d, _, files in os.walk(copy / "chipbench") for f in files
    }
    # A configuration, a traffic mix and a per-layer metric: new files only.
    cfg = json.load(open(copy / "chipbench/configs/local-4x1.json"))
    cfg.update(name="local-5x1", source="a test's committee", committee=dict(cfg["committee"], validators=5))
    json.dump(cfg, open(copy / "chipbench/configs/local-5x1.json", "w"))
    json.dump({"rate_share_of_knee": 0.1, "tick_ms": 100, "drain_s": 20},
              open(copy / "chipbench/traffic/trickle.json", "w"))
    (copy / "chipbench/readers/verify.dispatches.py").write_text(
        "def read(obs):\n    return float(sum(obs['window']['verifier'].values()))\n")
    bench = json.load(open(copy / "BENCHMARK.json"))
    bench["configs"].append({"name": "local-5x1", "source": "a test's committee",
                             "file": "chipbench/configs/local-5x1.json", "reduced": cfg["reduced"], "why": "test"})
    bench["workloads"].append({"name": "local-5x1.trickle", "config": "local-5x1", "traffic": "trickle",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "verify.dispatches", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "verify stage",
                               "moves": "executed_tx_per_s"})
    json.dump(bench, open(copy / "BENCHMARK.json", "w"))
    # The appends alone leave the structural tests passing, run inside the copy.
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")}
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider", "-p", "no:randomly", *STRUCTURAL],
        cwd=copy, env=dict(env, JAX_PLATFORMS="cpu"), text=True, capture_output=True, timeout=300,
    )
    entries = sum(len(bench[k]) for k in ("configs", "workloads", "end_to_end", "per_layer"))
    assert proc.returncode == 0 and f"{entries + 3} passed" in proc.stdout, proc.stdout[-3000:] + proc.stderr[-2000:]
    rehearsal = {"verify_bucket": 16, "parameters": {"commit_latency_target": 60}}  # five validators, the file's rate
    proc = chipbench(driver_args("local-5x1.trickle", 79, 1), rehearsal=rehearsal, cwd=str(copy))
    result = last_json(proc)
    assert result["correct"] is True and abs(result["attempted"] - 0.1 * cfg["knee_tx_per_s"] * 3) <= 5
    assert result["metrics"]["verify.dispatches"]["value"] > 0
    after = {
        rel: hashlib.sha256(open(copy / rel, "rb").read()).hexdigest() for rel in before
    }
    assert after == before
    assert list((copy / "native").glob("*.so")), "the run builds its own libraries"
    out = json.load(open(copy / "chipbench/out/local-5x1.trickle.79.json"))
    assert len(out["notes"]["validator_commits"]) == 5
    # An engine with no plain rule of its own faults before the device is
    # looked for, let alone a committee booted, and names the missing file.
    json.dump(dict(cfg, consensus_protocol="no-such-engine"), open(copy / "chipbench/configs/local-5x1.json", "w"))
    proc = chipbench(driver_args("local-5x1.trickle", 80, 0), rehearsal=rehearsal, cwd=str(copy), timeout=300)
    last = proc.stdout.strip().splitlines()[-1]
    assert proc.returncode == 1 and "HarnessFault" in last and "chipbench/reference/no-such-engine.py" in last
    assert "load_cell" in proc.stderr and "device {" not in proc.stdout


def run_faults(names: str, seconds: str) -> dict[str, dict]:
    proc = chipbench(["--workload", "local-4x1.cruise", "--seeds", "1", "--seconds", seconds,
                      "--faults", names], rehearsal=REHEARSAL, module="chipbench.faults", timeout=1500)
    lines = [json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{")]
    assert proc.returncode == 0 and lines[-1]["ok"] is True, proc.stdout[-3000:] + proc.stderr[-2000:]
    return {line["fault"]: line for line in lines[:-1]}


def test_the_control_and_an_altered_answer_come_out_not_correct():
    """The rest of a run, the look for a chip aside, with the timed path
    broken underneath: the sound run is correct, the control (one order on
    all validators, broken) and a result altered where it is produced are not."""
    sound, control, altered = run_faults("order_swapped,answer_altered", "2").values()
    assert sound["fault"] == "sound" and sound["correct"] is True
    assert control["correct"] is False and control["checks"]["order_diverged"][0] >= 1
    assert control["checks"]["exec_vs_store"][0] >= 1
    assert altered["correct"] is False and altered["checks"]["executed_unknown"][0] >= 1


def test_a_forgery_let_through_and_a_commit_mask_altered_come_out_not_correct():
    """The same, broken where the device's answers come back: a verify stage
    that takes every answer as valid while one validator forges, and a commit
    walk whose masks lose a certificate on one validator."""
    runs = run_faults("forgery_accepted,commit_left_out", "2")
    sound, forged, walk = runs["sound"], runs["forgery_accepted"], runs["commit_left_out"]
    assert sound["correct"] is True and list(runs)[-1] == "forgery_accepted"  # it stays planted
    assert forged["correct"] is False and forged["checks"]["bad_certificates"][0] >= 1
    assert walk["correct"] is False and walk["checks"]["walk_mismatch"][0] >= 1


@pytest.mark.slow
def test_every_planted_fault_comes_out_not_correct():
    runs = run_faults("order_swapped,answer_altered,half_left_out,replayed,forgery_accepted,commit_left_out", "3")
    assert [r["correct"] for r in runs.values()] == [True] + [False] * 6
    assert runs["replayed"]["checks"]["executed_again"][0] >= 1
