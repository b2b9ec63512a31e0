"""The tier-1 static-analysis gates: narwhal-lint, narwhal-topo AND
narwhal-sched, driven through the combined `python -m tools.check`
runner (one process, one shared whole-program extraction, one exit
code).

Part 1 (narwhal-lint): runs the per-function analyzer over `narwhal_tpu/`
and `tests/` and fails on any non-baselined finding — this is how the
actor/JAX invariants (metered channels, non-blocking event loop,
drainable task spawns, jit purity, immutable decoded messages, no silent
excepts) stay machine-checked. Fixture tests pin each rule to one
tripping and one clean snippet so a rule regression (stops firing /
starts overfiring) is caught in the same run.

Part 2 (narwhal-topo, tools/analysis): the whole-program gate — extracts
the actor/channel topology from the wiring roots and fails on orphan
producers/consumers, bounded-channel deadlock cycles, dropped task
handles, wire-schema drift, and cross-module jit impurity. The extracted
topology is pinned as a checked-in artifact (tools/analysis/topology.json
+ .dot): wiring changes without `python -m tools.analysis
--write-artifact` fail the stale-artifact test, exactly like a stale lint
baseline.

Part 3 (narwhal-sched, tools/sched): interleaving races (multi-task
mutation without a single-writer discipline, read-modify-write spanning
an await) over the extractor's task-attributed state sites, plus the
replay-determinism family (raw entropy beside the seeded seams, the
global random stream, id()-keyed ordering, effectful set iteration) that
machine-checks the two PR-9 divergences. Regression fixtures under
tests/sched_fixtures/ pin both PR-9 bugs verbatim.
"""

from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
FIXTURES = REPO / "tests" / "lint_fixtures"

sys.path.insert(0, str(REPO))

from tools.lint import RULES, Baseline, Finding, run_lint  # noqa: E402
from tools.lint.__main__ import DEFAULT_BASELINE, main  # noqa: E402


def lint(*paths, baseline=None, rules=None):
    return run_lint([str(p) for p in paths], rules=rules, baseline=baseline, root=REPO)


# ---------------------------------------------------------------------------
# The gate: ONE combined `tools.check` run feeds every tree-clean test
# (lint + topo + sched share it; topo and sched share one extraction).
# ---------------------------------------------------------------------------

from tools.check import run_check  # noqa: E402


@pytest.fixture(scope="module")
def check_report():
    return run_check(root=REPO)


def test_tree_has_no_new_findings(check_report):
    """`python -m tools.check` (lint plane) must be clean modulo the
    checked-in baseline. If this fails: fix the finding, suppress it with a
    justified `# lint: allow(<rule>)`, or (last resort) regenerate the
    baseline via `python -m tools.lint --write-baseline narwhal_tpu/ tests/`."""
    result = check_report.results["lint"]
    assert result.files_scanned > 50  # the walk found the tree
    details = "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in result.new
    )
    assert not result.new, f"new lint findings:\n{details}"


def test_baseline_has_no_stale_entries(check_report):
    """Grandfathered findings that get fixed must leave the baseline too,
    or the file silently re-authorizes a future regression."""
    result = check_report.results["lint"]
    assert not result.stale_baseline, (
        f"stale baseline entries (regenerate with --write-baseline): "
        f"{result.stale_baseline}"
    )


def test_combined_gate_is_fast(check_report):
    """All three planes in one process must stay cheap enough to gate
    every tier-1 run — one pin for the whole `tools.check` invocation."""
    assert check_report.elapsed < 25.0, check_report.timings


# ---------------------------------------------------------------------------
# Rule catalog / fixtures
# ---------------------------------------------------------------------------

EXPECTED_RULES = {
    "no-blocking-in-async",
    "no-raw-queue",
    "tracked-task-spawn",
    "jit-purity",
    "no-shared-decode-mutation",
    "no-silent-except",
    "no-sync-store-write-in-async",
    "no-per-item-rpc-in-loop",
    "no-unbounded-channel",
    "no-wall-clock-in-actors",
    "no-untracked-jit",
    "no-per-item-cert-verify",
    "metric-naming",
    "no-direct-peer-connection",
}

FIXTURE_FOR = {
    "no-blocking-in-async": ("blocking_trip.py", "blocking_clean.py"),
    "no-raw-queue": ("raw_queue_trip.py", "raw_queue_clean.py"),
    "tracked-task-spawn": ("task_spawn_trip.py", "task_spawn_clean.py"),
    "jit-purity": ("tpu/jit_purity_trip.py", "tpu/jit_purity_clean.py"),
    "no-shared-decode-mutation": (
        "decode_mutation_trip.py",
        "decode_mutation_clean.py",
    ),
    "no-silent-except": (
        "primary/silent_except_trip.py",
        "primary/silent_except_clean.py",
    ),
    "no-sync-store-write-in-async": (
        "primary/sync_store_write_trip.py",
        "primary/sync_store_write_clean.py",
    ),
    "no-per-item-rpc-in-loop": (
        "executor/per_item_rpc_trip.py",
        "executor/per_item_rpc_clean.py",
    ),
    "no-unbounded-channel": (
        "worker/unbounded_channel_trip.py",
        "worker/unbounded_channel_clean.py",
    ),
    "no-wall-clock-in-actors": (
        "primary/wall_clock_trip.py",
        "primary/wall_clock_clean.py",
    ),
    "no-untracked-jit": (
        "tpu/untracked_jit_trip.py",
        "tpu/untracked_jit_clean.py",
    ),
    "no-per-item-cert-verify": (
        "primary/cert_verify_trip.py",
        "primary/cert_verify_clean.py",
    ),
    "metric-naming": (
        "metric_naming_trip.py",
        "metric_naming_clean.py",
    ),
    "no-direct-peer-connection": (
        "worker/direct_peer_trip.py",
        "worker/direct_peer_clean.py",
    ),
}


def test_rule_catalog_is_complete():
    assert EXPECTED_RULES <= set(RULES), sorted(RULES)
    assert set(FIXTURE_FOR) == EXPECTED_RULES
    for rule in RULES.values():
        assert rule.summary, f"{rule.name} has no summary"


@pytest.mark.parametrize("rule_name", sorted(EXPECTED_RULES))
def test_rule_trips_on_fixture(rule_name):
    trip, _ = FIXTURE_FOR[rule_name]
    result = lint(FIXTURES / trip, rules={rule_name: RULES[rule_name]})
    assert result.new, f"{rule_name} found nothing in {trip}"
    assert all(f.rule == rule_name for f in result.new)


@pytest.mark.parametrize("rule_name", sorted(EXPECTED_RULES))
def test_rule_passes_clean_fixture(rule_name):
    _, clean = FIXTURE_FOR[rule_name]
    result = lint(FIXTURES / clean, rules={rule_name: RULES[rule_name]})
    details = [(f.line, f.message) for f in result.new]
    assert not result.new, f"{rule_name} overfires on {clean}: {details}"


def test_fixture_finding_counts():
    """Pin the exact trip counts so a rule that silently loses coverage
    (fires on one pattern but stops on another) is caught, not just total
    silence."""
    counts = {
        "no-blocking-in-async": 5,  # sleep, aliased sleep, open, subprocess, .result()
        "no-raw-queue": 3,  # Queue, LifoQueue, from-import Queue
        "tracked-task-spawn": 3,  # create_task, ensure_future, loop.create_task
        "jit-purity": 4,  # print, time.time, global mutation, random under jit
        "no-shared-decode-mutation": 4,  # field, nested container, mutator, direct
        "no-silent-except": 2,  # pass-only swallow, broad unlogged catch
        "no-sync-store-write-in-async": 4,  # store write/put, engine batch, bare store
        "no-per-item-rpc-in-loop": 3,  # for+attr recv, async for, bare name
        "no-unbounded-channel": 3,  # bare, keyword-only gauge, attr form
        # time.time, time.monotonic, aliased import, loop var, chained call
        "no-wall-clock-in-actors": 5,
        # raw @jax.jit decorator, partial(jax.jit, ...) form, jax.jit(f) call
        "no-untracked-jit": 3,
        # certificate.verify, cert.verify, raw host_verify_aggregate
        "no-per-item-cert-verify": 3,
        # bad snake_case, unknown subsystem, unitless histogram
        "metric-naming": 3,
        # transport dial, raw asyncio dial, PeerClient direct + attr form
        "no-direct-peer-connection": 4,
    }
    for rule_name, expected in counts.items():
        trip, _ = FIXTURE_FOR[rule_name]
        result = lint(FIXTURES / trip, rules={rule_name: RULES[rule_name]})
        assert len(result.new) == expected, (
            rule_name,
            [(f.line, f.message) for f in result.new],
        )


# ---------------------------------------------------------------------------
# Suppressions
# ---------------------------------------------------------------------------


def test_inline_suppression(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "import asyncio\n"
        "async def g():\n"
        "    import time\n"
        "    time.sleep(1)  # lint: allow(no-blocking-in-async)\n"
    )
    result = lint(f)
    assert not result.new
    assert len(result.suppressed) == 1
    assert result.suppressed[0].rule == "no-blocking-in-async"


def test_preceding_line_suppression(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "import time\n"
        "async def g():\n"
        "    # warmup only, loop not running yet\n"
        "    # lint: allow(no-blocking-in-async)\n"
        "    time.sleep(1)\n"
    )
    result = lint(f)
    assert not result.new and len(result.suppressed) == 1


def test_suppression_is_rule_specific(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text(
        "import time\n"
        "async def g():\n"
        "    time.sleep(1)  # lint: allow(no-raw-queue)\n"
    )
    result = lint(f)
    assert len(result.new) == 1  # wrong rule named -> not suppressed


# ---------------------------------------------------------------------------
# Baseline workflow
# ---------------------------------------------------------------------------


def test_baseline_grandfathers_and_detects_new(tmp_path):
    f = tmp_path / "mod.py"
    f.write_text("import time\nasync def g():\n    time.sleep(1)\n")
    first = lint(f)
    assert len(first.new) == 1

    bl_path = tmp_path / "baseline.json"
    Baseline.dump(first.new, bl_path)
    grandfathered = lint(f, baseline=Baseline.load(bl_path))
    assert not grandfathered.new and len(grandfathered.baselined) == 1

    # A NEW finding alongside the baselined one still fails the run, and
    # the baseline survives the original line moving.
    f.write_text(
        "import time\n\nasync def g():\n    time.sleep(1)\n    open('x')\n"
    )
    again = lint(f, baseline=Baseline.load(bl_path))
    assert len(again.baselined) == 1
    assert len(again.new) == 1 and "open" in again.new[0].snippet


def test_baseline_reports_stale_entries(tmp_path):
    bl_path = tmp_path / "baseline.json"
    ghost = Finding("no-raw-queue", "gone.py", 1, 0, "m", "asyncio.Queue()")
    Baseline.dump([ghost], bl_path)
    f = tmp_path / "mod.py"
    f.write_text("x = 1\n")
    result = lint(f, baseline=Baseline.load(bl_path))
    assert result.stale_baseline == [("no-raw-queue", "gone.py", "asyncio.Queue()")]


def test_syntax_error_is_a_finding(tmp_path):
    f = tmp_path / "broken.py"
    f.write_text("def oops(:\n")
    result = lint(f)
    assert len(result.new) == 1 and result.new[0].rule == "syntax-error"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_exit_codes_and_json(tmp_path):
    trip = FIXTURES / "raw_queue_trip.py"
    clean = FIXTURES / "raw_queue_clean.py"
    env_cwd = str(REPO)

    bad = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--format", "json", str(trip)],
        capture_output=True,
        text=True,
        cwd=env_cwd,
    )
    assert bad.returncode == 1, bad.stderr
    payload = json.loads(bad.stdout)
    assert not payload["ok"] and payload["new"]
    assert {f["rule"] for f in payload["new"]} == {"no-raw-queue"}

    good = subprocess.run(
        [sys.executable, "-m", "tools.lint", str(clean)],
        capture_output=True,
        text=True,
        cwd=env_cwd,
    )
    assert good.returncode == 0, good.stdout + good.stderr


def test_cli_list_rules():
    assert main(["--list-rules"]) == 0


def test_fixture_dir_is_excluded_from_directory_walks():
    """Walking tests/ must skip lint_fixtures/ (so the tripping snippets
    never fail the gate), while explicit file arguments bypass excludes."""
    result = lint(REPO / "tests")
    assert not any("lint_fixtures" in f.path for f in result.new)
    explicit = lint(FIXTURES / "raw_queue_trip.py")
    assert explicit.new


# ---------------------------------------------------------------------------
# Cross-module jit-purity (the retired same-module caveat)
# ---------------------------------------------------------------------------


def test_jit_purity_reports_cross_module_impurities():
    """Scanning the module that DECLARES the jit root must surface impure
    sites reached in sibling modules, anchored at their real location."""
    result = lint(
        FIXTURES / "tpu" / "xmod_root.py", rules={"jit-purity": RULES["jit-purity"]}
    )
    assert len(result.new) == 2, [(f.path, f.line, f.message) for f in result.new]
    assert all(f.path.endswith("xmod_helper.py") for f in result.new)
    kinds = " ".join(f.message for f in result.new)
    assert "print" in kinds and "time.time" in kinds


def test_jit_purity_cross_module_respects_inline_allow():
    """xmod_helper.warmed carries `# lint: allow(jit-purity)` — reachable
    and impure, but justified at its own site."""
    result = lint(
        FIXTURES / "tpu" / "xmod_root.py", rules={"jit-purity": RULES["jit-purity"]}
    )
    assert not any("perf_counter" in f.message for f in result.new)


def test_jit_purity_cross_module_clean_root():
    """A root that only reaches the pure sibling helper stays silent."""
    result = lint(
        FIXTURES / "tpu" / "xmod_clean_root.py",
        rules={"jit-purity": RULES["jit-purity"]},
    )
    assert not result.new, [(f.path, f.line) for f in result.new]


# ===========================================================================
# Part 2: narwhal-topo (tools/analysis) — the whole-program gate
# ===========================================================================

from tools.analysis import (  # noqa: E402
    DETECTORS,
    Context,
    extract,
    run_detectors,
)
from tools.analysis.__main__ import (  # noqa: E402
    ARTIFACT_JSON,
    DEFAULT_BASELINE as TOPO_BASELINE,
    topology_doc,
)
from tools.analysis.extractor import DEFAULT_ROOTS  # noqa: E402

TOPO_FIXTURES = REPO / "tests" / "topo_fixtures"


def _topo_ctx():
    topo, extractor = extract(REPO)
    return Context(topo, extractor.program, REPO)


def _fixture_result(fixture: str, symbol: str, rule: str):
    # package="" loads ONLY the fixture file: detectors that scan every
    # program module (dropped-handle-escape) must not see sibling
    # fixtures' deliberate violations.
    topo, extractor = extract(
        REPO,
        package="",
        roots=[f"tests/topo_fixtures/{fixture}::{symbol}"],
    )
    ctx = Context(topo, extractor.program, REPO)
    return run_detectors(ctx, detectors={rule: DETECTORS[rule]})


# -- the gate ---------------------------------------------------------------


def test_topo_tree_has_no_new_findings(check_report):
    """`python -m tools.check` (topo plane) must be clean modulo the
    (empty) baseline. If this fails: fix the wiring, or justify with an
    inline `# lint: allow(<detector>)` at the anchor site."""
    result = check_report.results["topo"]
    details = "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in result.new
    )
    assert not result.new, f"new topology findings:\n{details}"
    # The extraction actually modeled the pipeline (not a silent no-op).
    assert len(check_report.topology.live_channels()) >= 20
    assert len(check_report.topology.tasks) >= 30
    # The one justified suppression: the protocol-bounded core<->proposer
    # wait cycle (primary/core.py).
    assert any(f.rule == "bounded-channel-cycle" for f in result.suppressed)
    # The combined runner checked artifact currency in the same pass.
    assert not check_report.artifact_stale


def test_topo_baseline_stays_empty():
    """Like lint's: the topology baseline only ever shrinks, and it starts
    (and must stay) EMPTY — new findings are fixed or justified inline."""
    baseline = json.loads(TOPO_BASELINE.read_text(encoding="utf-8"))
    assert baseline["findings"] == []


def test_topo_detector_catalog_is_complete():
    expected = {
        "orphan-producer",
        "orphan-consumer",
        "bounded-channel-cycle",
        "dropped-handle-escape",
        "wire-schema",
        "cross-module-jit-purity",
    }
    assert expected == set(DETECTORS), sorted(DETECTORS)
    for det in DETECTORS.values():
        assert det.summary, f"{det.name} has no summary"


# -- the pinned topology artifact -------------------------------------------


def test_topology_artifact_is_current():
    """The checked-in topology.json must match a fresh extraction of the
    live codebase. Wiring changed? Regenerate with
    `python -m tools.analysis --write-artifact` and review the diff —
    that review IS the point of pinning the pipeline shape."""
    topo, _ = extract(REPO)
    fresh = topology_doc(topo, DEFAULT_ROOTS)
    checked_in = json.loads(ARTIFACT_JSON.read_text(encoding="utf-8"))
    assert fresh == checked_in, (
        "stale tools/analysis/topology.json — regenerate with "
        "`python -m tools.analysis --write-artifact` and review the diff"
    )


def test_topology_artifact_matches_known_pipeline():
    """Semantic pins on the real architecture: the load-bearing edges the
    paper's pipeline (workers -> primary -> consensus -> executor) implies
    must be present in the artifact."""
    doc = json.loads(ARTIFACT_JSON.read_text(encoding="utf-8"))
    edges = {(e["task"], e["channel"], e["op"]) for e in doc["edges"]}
    # The PR-6 wedge pair: executor output produced, drained by __main__.
    assert ("ExecutorCore.run", "node/execution_output", "send_many") in edges
    assert (
        "_run_node._drain_execution_output",
        "node/execution_output",
        "recv",
    ) in edges
    # Core feeds consensus; consensus feeds the executor and the primary.
    assert ("Core.run", "node/new_certificates", "send") in edges
    assert ("Consensus.run", "node/new_certificates", "recv") in edges
    assert ("Consensus.run", "node/consensus_output", "send") in edges
    assert ("Subscriber.run", "node/consensus_output", "recv") in edges
    # The speculative tap is non-blocking by design.
    assert ("Consensus.run", "node/accepted_certificates", "try_send") in edges
    # Worker pipeline: ingest -> batch maker -> quorum -> processor.
    assert ("BatchMaker.run", "worker/quorum_waiter", "send") in edges
    assert ("QuorumWaiter.run", "worker/quorum_waiter", "recv") in edges
    caps = {c["id"]: c["capacity"] for c in doc["channels"]}
    assert caps["node/execution_output"] == 10_000
    assert caps["primary/state_handler"] == 100


def test_topology_dot_artifact_exists_and_renders_channels():
    dot = (ARTIFACT_JSON.parent / "topology.dot").read_text(encoding="utf-8")
    assert "digraph" in dot
    assert "node/execution_output" in dot and "worker/batch_maker" in dot


# -- per-detector fixtures (tripping + clean, pinned counts) ----------------


def test_orphan_producer_flags_the_pr6_wedge_fixture():
    result = _fixture_result(
        "orphan_producer_trip.py", "MiniNode", "orphan-producer"
    )
    assert len(result.new) == 1, [(f.line, f.message) for f in result.new]
    assert "node/execution_output" in result.new[0].message


def test_orphan_producer_clean_fixture():
    result = _fixture_result(
        "orphan_producer_clean.py", "MiniNode", "orphan-producer"
    )
    assert not result.new, [(f.line, f.message) for f in result.new]


def test_orphan_consumer_fixtures():
    trip = _fixture_result("orphan_consumer_trip.py", "DeadNode", "orphan-consumer")
    assert len(trip.new) == 1, [(f.line, f.message) for f in trip.new]
    assert "tx_ghost" in trip.new[0].message
    clean = _fixture_result(
        "orphan_consumer_clean.py", "DeadNode", "orphan-consumer"
    )
    assert not clean.new, [(f.line, f.message) for f in clean.new]


def test_bounded_cycle_fixtures():
    trip = _fixture_result("cycle_trip.py", "CycleNode", "bounded-channel-cycle")
    assert len(trip.new) == 1, [(f.line, f.message) for f in trip.new]
    assert "Pinger.run" in trip.new[0].message
    assert "Ponger.run" in trip.new[0].message
    clean = _fixture_result("cycle_clean.py", "CycleNode", "bounded-channel-cycle")
    assert not clean.new, [(f.line, f.message) for f in clean.new]


def test_dropped_handle_fixtures():
    """Three escapes pinned: the attr-held task, the dict-tuple park, and
    the dropped spawn() result."""
    trip = _fixture_result("dropped_handle_trip.py", "Leaky", "dropped-handle-escape")
    assert len(trip.new) == 3, [(f.line, f.message) for f in trip.new]
    msgs = " | ".join(f.message for f in trip.new)
    assert "_task" in msgs and "pending" in msgs and "spawn" in msgs
    clean = _fixture_result(
        "dropped_handle_clean.py", "Tidy", "dropped-handle-escape"
    )
    assert not clean.new, [(f.line, f.message) for f in clean.new]


def test_wire_schema_fixture_and_real_registry():
    from tools.analysis.extractor import Program, Topology

    # Tripping fixture: one duplicate tag + one missing golden entry.
    program = Program(REPO, None)
    ctx = Context(
        Topology(),
        program,
        REPO,
        messages_path="tests/topo_fixtures/wire_schema_trip.py",
        golden_path="tests/topo_fixtures/wire_schema_golden.json",
    )
    result = run_detectors(ctx, detectors={"wire-schema": DETECTORS["wire-schema"]})
    assert len(result.new) == 2, [(f.line, f.message) for f in result.new]
    msgs = " | ".join(f.message for f in result.new)
    assert "collides" in msgs and "golden entry" in msgs
    # The real registry must be tag-unique and fully snapshotted.
    real = run_detectors(
        _topo_ctx(), detectors={"wire-schema": DETECTORS["wire-schema"]}
    )
    assert not real.new, [(f.line, f.message) for f in real.new]


def test_cross_module_jit_purity_detector_on_fixture_package():
    topo, extractor = extract(
        REPO,
        package="tests/lint_fixtures/tpu",
        roots=["tests/lint_fixtures/tpu/xmod_root.py::kernel"],
    )
    ctx = Context(topo, extractor.program, REPO)
    result = run_detectors(
        ctx,
        detectors={
            "cross-module-jit-purity": DETECTORS["cross-module-jit-purity"]
        },
    )
    assert len(result.new) == 2, [(f.path, f.line) for f in result.new]
    assert all(f.path.endswith("xmod_helper.py") for f in result.new)


# -- CLI --------------------------------------------------------------------


def test_topo_cli_gate_and_artifacts(tmp_path):
    """The satellite-task invocation: detectors + JSON/DOT artifacts in
    one run, exit 0 on the clean tree with a current checked-in artifact."""
    out_json, out_dot = tmp_path / "t.json", tmp_path / "t.dot"
    proc = subprocess.run(
        [
            sys.executable, "-m", "tools.analysis",
            "--check-artifact", "--format", "json",
            "--json", str(out_json), "--dot", str(out_dot),
        ],
        capture_output=True,
        text=True,
        cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] and not payload["artifact_stale"]
    doc = json.loads(out_json.read_text())
    assert doc == json.loads(ARTIFACT_JSON.read_text(encoding="utf-8"))
    assert "digraph" in out_dot.read_text()


def test_topo_cli_exit_code_on_findings():
    proc = subprocess.run(
        [
            sys.executable, "-m", "tools.analysis",
            "--package", "tests/topo_fixtures",
            "--roots", "tests/topo_fixtures/cycle_trip.py::CycleNode",
            "--rule", "bounded-channel-cycle",
            "--no-baseline", "--format", "json",
        ],
        capture_output=True,
        text=True,
        cwd=str(REPO),
    )
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert not payload["ok"]
    assert {f["rule"] for f in payload["new"]} == {"bounded-channel-cycle"}


def test_topo_cli_list_rules():
    from tools.analysis.__main__ import main as topo_main

    assert topo_main(["--list-rules"]) == 0


# (per-plane perf pins are folded into test_combined_gate_is_fast — one
# <25s pin for the whole tools.check run; narwhal-sched keeps its own
# acceptance pin in Part 3.)


# ===========================================================================
# Part 3: narwhal-sched (tools/sched) — races + replay determinism
# ===========================================================================

from tools.sched import RULES as SCHED_RULES  # noqa: E402
from tools.sched import run_sched  # noqa: E402
from tools.sched.__main__ import DEFAULT_BASELINE as SCHED_BASELINE  # noqa: E402
from tools.sched.__main__ import main as sched_main  # noqa: E402

SCHED_FIXTURES = REPO / "tests" / "sched_fixtures"

SCHED_EXPECTED_RULES = {
    "multi-task-mutation",
    "await-interleaved-rmw",
    "raw-entropy",
    "unseeded-random",
    "id-keyed-ordering",
    "unordered-iteration",
}


def sched_scan(*paths, roots=(), baseline=None):
    """Syntactic-only run (package='', no extraction) over fixture files;
    pass roots to run the whole-program race rules too."""
    return run_sched(
        [str(p) for p in paths],
        root=REPO,
        package="",
        roots=tuple(roots),
        baseline=baseline,
    )


# -- the gate ---------------------------------------------------------------


def test_sched_tree_has_no_new_findings(check_report):
    """`python -m tools.check` (sched plane) must be clean modulo the
    (empty) baseline: fix the race, or justify the deliberate idiom with
    an inline `# lint: allow(<rule>)` at the anchor site."""
    result = check_report.results["sched"]
    assert result.files_scanned > 50
    details = "\n".join(
        f"{f.path}:{f.line}: [{f.rule}] {f.message}" for f in result.new
    )
    assert not result.new, f"new sched findings:\n{details}"
    # The tree's deliberate idioms (co-hosted caches, seeded global
    # stream, register/await/cleanup) are documented inline, not silent.
    assert len(result.suppressed) >= 20


def test_sched_baseline_stays_empty():
    """The sched baseline starts (and must stay) EMPTY — new findings are
    fixed or justified inline, never grandfathered."""
    baseline = json.loads(SCHED_BASELINE.read_text(encoding="utf-8"))
    assert baseline["findings"] == []


def test_sched_rule_catalog_is_complete():
    assert set(SCHED_RULES) == SCHED_EXPECTED_RULES
    for rule in SCHED_RULES.values():
        assert rule.summary


# -- PR-9 regressions: the two bugs these rules exist to re-find ------------


def test_refinds_pr9_set_partition_bug():
    """The connection-set iteration in set_partition (hash-order resets)
    must trip unordered-iteration at the loop."""
    result = sched_scan(SCHED_FIXTURES / "pr9_partition.py")
    assert [(f.rule, f.line) for f in result.new] == [
        ("unordered-iteration", 21)
    ]
    assert "hash" in result.new[0].message


def test_refinds_pr9_urandom_nonce_bug():
    """The os.urandom handshake nonce must trip raw-entropy at the draw."""
    result = sched_scan(SCHED_FIXTURES / "pr9_nonce.py")
    assert [(f.rule, f.line) for f in result.new] == [("raw-entropy", 14)]
    assert "set_entropy" in result.new[0].message


# -- per-rule trip/clean fixtures with pinned counts ------------------------


def test_determinism_fixture_finding_counts():
    """det_trip.py: one finding per shape, pinned; det_clean.py: zero."""
    trip = sched_scan(SCHED_FIXTURES / "det_trip.py")
    counts: dict[str, int] = {}
    for f in trip.new:
        counts[f.rule] = counts.get(f.rule, 0) + 1
    assert counts == {
        "raw-entropy": 1,  # uuid.uuid4
        "unseeded-random": 3,  # module-as-RNG, global draw, Random()
        "id-keyed-ordering": 1,
        "unordered-iteration": 1,
    }
    clean = sched_scan(SCHED_FIXTURES / "det_clean.py")
    assert not clean.new, [(f.rule, f.line) for f in clean.new]


def test_race_fixture_finding_counts():
    """races_trip.py (driven from its `main` wiring root): exactly one
    multi-task-mutation (Board poked from Writer AND Reader) and one
    await-interleaved-rmw (Counter.bump's read/await/write); the
    disciplined twin is silent."""
    trip = sched_scan(
        SCHED_FIXTURES / "races_trip.py",
        roots=("tests/sched_fixtures/races_trip.py::main",),
    )
    assert sorted((f.rule, f.line) for f in trip.new) == [
        ("await-interleaved-rmw", 30),
        ("multi-task-mutation", 39),
    ]
    clean = sched_scan(
        SCHED_FIXTURES / "races_clean.py",
        roots=("tests/sched_fixtures/races_clean.py::main",),
    )
    assert not clean.new, [(f.rule, f.line) for f in clean.new]


# -- extractor attribution (the StateSite API) ------------------------------


def test_extractor_attributes_sites_to_tasks():
    """The race detectors are only as good as the extractor's read/write
    attribution: one task writes, another reads, and every site must be
    keyed to the task that performs it."""
    topo, extractor = extract(
        REPO, package="", roots=["tests/sched_fixtures/races_trip.py::main"]
    )
    by_state: dict[str, dict[str, set[str]]] = {}
    for s in extractor.state_sites:
        by_state.setdefault(s.state, {"read": set(), "write": set()})[
            s.kind
        ].add(s.task)
    slots = by_state["Board.slots"]
    assert slots["write"] == {"init:Board", "Writer.run", "Reader.run"}
    assert {"Writer.run", "Reader.run"} <= slots["read"]
    count = by_state["Counter.count"]
    assert {"Writer.run", "Reader.run"} <= count["write"]
    # And the race rules see exactly one runtime-shared unencapsulated
    # state with multiple writers (the finding count pinned above).
    result = sched_scan(
        SCHED_FIXTURES / "races_trip.py",
        roots=("tests/sched_fixtures/races_trip.py::main",),
    )
    assert sum(f.rule == "multi-task-mutation" for f in result.new) == 1


# -- suppression ------------------------------------------------------------


def test_sched_inline_allow(tmp_path):
    src = tmp_path / "seam.py"
    src.write_text(
        "import os\n\n\n"
        "def default_entropy(n):\n"
        "    # the seam's own production default\n"
        "    return os.urandom(n)  # lint: allow(raw-entropy)\n",
        encoding="utf-8",
    )
    result = run_sched([str(src)], root=tmp_path, package="", roots=())
    assert not result.new
    assert [f.rule for f in result.suppressed] == ["raw-entropy"]


# -- CLI --------------------------------------------------------------------


def test_sched_cli_exit_codes_and_json():
    bad = subprocess.run(
        [
            sys.executable, "-m", "tools.sched",
            "tests/sched_fixtures/pr9_nonce.py",
            "--format", "json", "--no-baseline",
            "--package", "", "--roots",
        ],
        capture_output=True,
        text=True,
        cwd=str(REPO),
    )
    assert bad.returncode == 1, bad.stdout + bad.stderr
    payload = json.loads(bad.stdout)
    assert not payload["ok"]
    assert {f["rule"] for f in payload["new"]} == {"raw-entropy"}
    good = subprocess.run(
        [
            sys.executable, "-m", "tools.sched",
            "tests/sched_fixtures/det_clean.py",
            "--format", "json", "--no-baseline",
            "--package", "", "--roots",
        ],
        capture_output=True,
        text=True,
        cwd=str(REPO),
    )
    assert good.returncode == 0, good.stdout + good.stderr
    assert json.loads(good.stdout)["ok"]


def test_sched_cli_list_rules(capsys):
    assert sched_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for name in SCHED_EXPECTED_RULES:
        assert name in out


# -- --diff mode (pre-commit: only changed files) ---------------------------


def _git(repo: Path, *args: str) -> None:
    subprocess.run(
        ["git", "-C", str(repo), "-c", "user.email=t@t", "-c", "user.name=t",
         *args],
        check=True,
        capture_output=True,
    )


def test_diff_mode_scans_only_changed_files(tmp_path):
    """Synthetic two-commit repo: b.py has violated since the base rev,
    a.py picks one up in the working tree — `--diff BASE` must report the
    a.py finding and stay silent about unchanged b.py."""
    _git(tmp_path, "init", "-q")
    (tmp_path / "a.py").write_text("X = 1\n", encoding="utf-8")
    (tmp_path / "b.py").write_text(
        "import os\n\nNONCE = os.urandom(8)\n", encoding="utf-8"
    )
    _git(tmp_path, "add", "a.py", "b.py")
    _git(tmp_path, "commit", "-q", "-m", "base")
    base = "HEAD"
    (tmp_path / "a.py").write_text(
        "import uuid\n\nTOKEN = uuid.uuid4().hex\n", encoding="utf-8"
    )
    result = run_sched(
        [str(tmp_path)],
        root=tmp_path,
        package="",
        roots=(),
        diff_base=base,
    )
    assert [(f.path, f.rule) for f in result.new] == [("a.py", "raw-entropy")]
    # Without --diff the unchanged violation is reported too.
    full = run_sched([str(tmp_path)], root=tmp_path, package="", roots=())
    assert {f.path for f in full.new} == {"a.py", "b.py"}


# -- performance ------------------------------------------------------------


def test_sched_full_run_is_fast():
    """The acceptance pin: extraction + every detector over
    `narwhal_tpu/ tests/` in under 15s."""
    t0 = time.perf_counter()
    run_sched(
        [str(REPO / "narwhal_tpu"), str(REPO / "tests")],
        root=REPO,
        baseline=Baseline.load(SCHED_BASELINE),
    )
    assert time.perf_counter() - t0 < 15.0


# -- the combined runner's CLI ----------------------------------------------


def test_check_cli_combined_json():
    """`python -m tools.check --json`: one invocation, three planes, one
    exit code — the single command SKILL.md and pre-commit use."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.check", "--json"],
        capture_output=True,
        text=True,
        cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] and not payload["artifact_stale"]
    assert set(payload) >= {"lint", "topo", "sched", "ok", "elapsed"}
    for plane in ("lint", "topo", "sched"):
        assert payload[plane]["ok"], plane
