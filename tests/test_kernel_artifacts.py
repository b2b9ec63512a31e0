"""A persisted kernel's export beside the compile cache (ISSUE 32): the
first dispatch at a shape loads it, and traces only where no file answers
to the key. Every case runs its dispatches in fresh processes
(tests/kernel_artifact_child.py) against a compile cache directory of its
own: a load has to work in a process that never traced. All but the last
group use a kernel that traces in a blink (tests/artifact_kernels.py); the
last pays `msm_accumulate_kernel`'s own trace and compile once, at bucket 16."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHILD = os.path.join(HERE, "kernel_artifact_child.py")
OUTCOMES = ("hit", "miss", "stale", "unreadable")


def start(cache, **opts) -> subprocess.Popen:
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache), JAX_PLATFORMS="cpu")
    return subprocess.Popen(
        [sys.executable, CHILD, json.dumps(opts)], env=env, cwd=os.path.dirname(HERE),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )


def finish(proc: subprocess.Popen, timeout: float = 600) -> dict:
    out, err = proc.communicate(timeout=timeout)
    assert proc.returncode == 0, err[-4000:]
    return json.loads(out.strip().splitlines()[-1])


def child(cache, **opts) -> dict:
    return finish(start(cache, **opts))


def outcome(rep: dict) -> list[str]:
    """The outcomes of a child's loads, which its flight records and its
    scrape series have to tell alike."""
    seen = [r["outcome"] for r in rep["loads"]]
    assert rep["counter"] == {o: float(seen.count(o)) for o in OUTCOMES}
    for r in rep["loads"]:
        assert r["seconds"] >= 0 and r["t"] > 0 and r["kernel"] and r["shapes"]
    return seen


def the_file(cache) -> str:
    (name,) = os.listdir(os.path.join(cache, "kernel_artifacts"))
    return os.path.join(cache, "kernel_artifacts", name)


def head_of(path: str) -> dict:
    with open(path, "rb") as f:
        return json.loads(f.readline())


EXPECTED = [[30, 110, 190, 270]] * 2  # the rows of arange(16) in fours, summed, times scale 5; twice


@pytest.fixture
def cache(tmp_path):
    return str(tmp_path / "cache")


@pytest.fixture
def written(cache):
    """A cache directory in which one process has traced and exported."""
    first = child(cache, kernel="tiny")
    assert outcome(first) == ["miss"] and first["traces"] == [[4, 4]]
    return cache


def test_miss_writes_and_a_fresh_process_hits(cache):
    first = child(cache, kernel="tiny")
    assert outcome(first) == ["miss"] and first["traces"] == [[4, 4]]
    assert first["files"] == ["tiny_persisted_kernel.uint8_4_4_.scale_5.cpu.cpu.export"]
    key = head_of(the_file(cache))["key"]
    assert key["kernel"] == "tiny_persisted_kernel" and key["shapes"] == "uint8[4,4]"
    assert key["statics"] == {"scale": "5"} and key["platform"] == "cpu"
    assert set(key) >= {"device_kind", "jax", "jaxlib", "source", "format"}
    second = child(cache, kernel="tiny")
    assert outcome(second) == ["hit"]
    assert second["traces"] == []  # the Python body was never entered
    assert first["results"] == second["results"] == EXPECTED
    # one first dispatch a shape on either path, its wall recorded under the kernel's name
    for rep in (first, second):
        assert len(rep["compiles"]) == 1 and rep["compiles"][0]["shapes"] == "uint8[4,4];scale=5"
        assert [w["mesh"] for w in rep["walls"]] == ["1"]


@pytest.mark.parametrize("what", ["source", "jax_version"])
def test_another_key_is_stale_and_rewrites(written, tmp_path, what):
    path = the_file(written)
    before = head_of(path)
    edited = str(tmp_path / "artifact_kernels_edited.py")
    shutil.copy(os.path.join(HERE, "artifact_kernels.py"), edited)
    with open(edited, "a") as f:
        f.write("# one more line: other source bytes, the same kernel\n")
    other = {"source": {"module": edited}, "jax_version": {"jax_version": "0.0.1"}}[what]
    rep = child(written, kernel="tiny", **other)
    assert outcome(rep) == ["stale"]
    assert rep["traces"] == [[4, 4]] and rep["results"] == EXPECTED  # traced, never guessed
    after = head_of(path)
    field = "source" if what == "source" else "jax"
    assert after["key"][field] != before["key"][field]
    assert {k: v for k, v in after["key"].items() if k != field} == {
        k: v for k, v in before["key"].items() if k != field}
    again = child(written, kernel="tiny", **other)  # the rewritten file is that key's
    assert outcome(again) == ["hit"] and again["traces"] == []
    back = child(written, kernel="tiny")  # and stale again for the checkout as it is
    assert outcome(back) == ["stale"] and back["traces"] == [[4, 4]]


def test_another_shape_is_a_miss_with_a_file_of_its_own(written):
    rep = child(written, kernel="tiny", shapes=[[4, 4], [8, 4]])
    assert outcome(rep) == ["hit", "miss"] and rep["traces"] == [[8, 4]]
    assert rep["files"] == [
        "tiny_persisted_kernel.uint8_4_4_.scale_5.cpu.cpu.export",
        "tiny_persisted_kernel.uint8_8_4_.scale_5.cpu.cpu.export",
    ]
    assert len(rep["compiles"]) == 2
    both = child(written, kernel="tiny", shapes=[[8, 4], [4, 4]])
    assert outcome(both) == ["hit", "hit"] and both["traces"] == []


def damage(path: str, how: str) -> None:
    with open(path, "rb") as f:
        whole = f.read()
    with open(path, "wb") as f:
        if how == "truncated":
            f.write(whole[: len(whole) // 2])
        elif how == "garbage":
            f.write(os.urandom(4096))
        elif how == "blob_flipped":  # a whole head over a blob that is not its own
            f.write(whole[:-64] + bytes(b ^ 0x5A for b in whole[-64:]))
        elif how == "empty":
            pass


@pytest.mark.parametrize("how", ["truncated", "garbage", "blob_flipped", "empty"])
def test_a_damaged_file_is_unreadable_traced_and_rewritten(written, how):
    path = the_file(written)
    with open(path, "rb") as f:
        whole = f.read()
    damage(path, how)
    rep = child(written, kernel="tiny")
    assert outcome(rep) == ["unreadable"]
    assert rep["traces"] == [[4, 4]] and rep["results"] == EXPECTED  # fell back to the trace
    with open(path, "rb") as f:
        assert f.read() == whole  # the same key and source export to the same bytes
    nxt = child(written, kernel="tiny")
    assert outcome(nxt) == ["hit"] and nxt["traces"] == []


def test_two_processes_racing_to_write_leave_one_whole_file(cache, tmp_path):
    go = str(tmp_path / "go")
    racers = [start(cache, kernel="tiny", wait_for=go) for _ in range(2)]
    time.sleep(6)  # both have imported jax and wait at the line before their first dispatch
    open(go, "w").close()
    reps = [finish(p) for p in racers]
    for rep in reps:
        assert outcome(rep)[0] in ("miss", "hit", "unreadable") and rep["results"] == EXPECTED
    assert "miss" in [outcome(r)[0] for r in reps]
    assert os.listdir(os.path.join(cache, "kernel_artifacts")) == [
        "tiny_persisted_kernel.uint8_4_4_.scale_5.cpu.cpu.export"]  # and no temporary name left
    after = child(cache, kernel="tiny")
    assert outcome(after) == ["hit"] and after["traces"] == []


def test_writers_of_one_path_never_leave_a_torn_file(tmp_path):
    """The same, where the race is certain: eight threads rewrite one path
    with blobs of different sizes while a reader keeps loading it."""
    import threading

    from narwhal_tpu.tpu import kernel_registry as kr

    path, key = str(tmp_path / "a" / "k.export"), {"kernel": "k"}
    blobs = [bytes([i]) * (50_000 + 7_000 * i) for i in range(8)]
    kr._write_artifact(path, key, blobs[0])
    seen, stop = [], threading.Event()

    def read():
        while not stop.is_set():
            with open(path, "rb") as f:
                head, blob = json.loads(f.readline()), f.read()
            seen.append(len(blob) == head["size"] and blob in blobs)

    reader = threading.Thread(target=read)
    reader.start()
    writers = [threading.Thread(target=lambda b=b: [kr._write_artifact(path, key, b) for _ in range(40)])
               for b in blobs]
    for w in writers:
        w.start()
    for w in writers:
        w.join()
    stop.set()
    reader.join()
    assert seen and all(seen)
    assert os.listdir(os.path.dirname(path)) == ["k.export"]


def test_the_mesh_path_writes_and_reads_nothing(written):
    path = the_file(written)
    before = (os.stat(path).st_mtime_ns, open(path, "rb").read())
    rep = child(written, kernel="tiny_mesh")
    assert rep["persisted"] is False
    assert outcome(rep) == [] and "kernel_load" not in rep["kinds"]
    assert rep["traces"] == [[4, 4]]  # the file that would have answered was not asked
    assert rep["results"] == [EXPECTED[0]] and [w["mesh"] for w in rep["walls"]] == ["2:data"]
    assert rep["files"] == [os.path.basename(path)]  # nor was another written, or this one again
    assert (os.stat(path).st_mtime_ns, open(path, "rb").read()) == before


def test_the_verifiers_mesh_wrappers_are_not_persisted():
    """The seam in `TpuVerifier.__init__`: one device dispatches the
    persisted module-level kernel; every wrapper the mesh path builds traces."""
    import jax

    from narwhal_tpu.tpu import ed25519 as kernel
    from narwhal_tpu.tpu import kernel_registry as kr
    from narwhal_tpu.tpu.verifier import TpuVerifier, _sharded_kernels, data_mesh

    assert kernel.msm_accumulate_kernel._persist == ("chunk",)
    assert [n for n in kr.kernel_names()
            if kr.get_kernel(n)._persist is not None and not n.startswith("tiny_")] == ["msm_accumulate_kernel"]
    _sharded_kernels(kernel, data_mesh(4, devices=jax.devices("cpu")[:4]), "data")
    assert kr.sharded_entries() > 0
    assert all(w._persist is None for w in kr._SHARDED.values())
    assert TpuVerifier(max_bucket=16, msm_min_bucket=16)._msm_kernel is kernel.msm_accumulate_kernel


def test_the_series_is_mounted_in_a_nodes_registry():
    from narwhal_tpu.fixtures import CommitteeFixture
    from narwhal_tpu.node import PrimaryNode
    from narwhal_tpu.stores import NodeStorage
    from narwhal_tpu.tpu import kernel_registry as kr

    f = CommitteeFixture(size=4)
    a = f.authority(0)
    node = PrimaryNode(a.keypair, f.committee, f.worker_cache, f.parameters, NodeStorage(None),
                       network_keypair=a.network_keypair)
    assert node.registry.get("kernel_artifact_total") is kr.KERNEL_ARTIFACTS
    assert kr.KERNEL_ARTIFACTS.label_names == ("kernel", "outcome")


# ---- msm_accumulate_kernel itself, bucket 16: one traced start, one loaded


@pytest.fixture(scope="module")
def msm_starts(tmp_path_factory):
    cache = str(tmp_path_factory.mktemp("msm-cache"))
    first = child(cache, kernel="msm")
    second = child(cache, kernel="msm")
    return cache, first, second


def test_msm_first_start_traces_and_writes(msm_starts):
    cache, first, _ = msm_starts
    assert outcome(first) == ["miss"] and first["body_entered"] == 1
    assert first["files"] == ["msm_accumulate_kernel.uint8_16_112_.chunk_128.cpu.cpu.export"]
    key = head_of(the_file(cache))["key"]
    assert key["statics"] == {"chunk": "128"} and key["shapes"] == "uint8[16,112]"


def test_msm_second_start_loads_and_never_enters_the_body(msm_starts):
    _, first, second = msm_starts
    assert outcome(second) == ["hit"] and second["body_entered"] == 0
    # the trace is what the load saves: the first start spent it before it compiled
    assert second["loads"][0]["seconds"] < first["loads"][0]["seconds"]


@pytest.mark.parametrize("start_", ["traced", "loaded"])
def test_msm_one_compile_a_shape_under_the_kernels_name(msm_starts, start_):
    rep = msm_starts[1 if start_ == "traced" else 2]
    # five dispatches at one shape: one program compiled, and it is the
    # kernel's by name, in jax's log lines and in a device trace. The start
    # that traces lowers twice: the body for the export, then the export.
    assert rep["compiled"] == ["Finished XLA compilation of jit(msm_accumulate_kernel)"]
    assert rep["lowered"] == ["Compiling jit(msm_accumulate_kernel)"] * (2 if start_ == "traced" else 1)
    assert rep["program"] == "@jit_msm_accumulate_kernel"
    assert len(rep["compiles"]) == 1 and rep["compiles"][0]["wall_s"] > 0
    assert [(w["kernel"], w["mesh"], w["shapes"]) for w in rep["walls"]] == [
        ("msm_accumulate_kernel", "1", "uint8[16,112]")]


def test_msm_loaded_results_equal_the_traced_ones_and_the_plain_integers(msm_starts):
    _, first, second = msm_starts
    assert first["raw"] == second["raw"] and len(set(first["raw"])) == 3  # element for element
    for rep in (first, second):
        assert rep["verdicts"] == rep["oracle"] == [True, False, False]
        assert rep["same_points"] == [True, True, True]


@pytest.mark.parametrize("start_", ["traced", "loaded"])
def test_msm_verifier_verdicts_on_a_mixed_bucket(msm_starts, start_):
    rep = msm_starts[1 if start_ == "traced" else 2]
    assert rep["all_valid"] == [True] * 10
    assert rep["mixed"] == rep["host"] and rep["mixed"].count(False) == 2
    assert rep["counts"]["msm_dispatch"] == 2 and rep["counts"]["msm_redispatch"] == 1
