"""FaultPlan fuzzer: generator invariants, campaign smoke, shrinker.

The fuzzer (narwhal_tpu/simnet/fuzz.py, CLI `python -m narwhal_tpu.simnet.fuzz`)
spends the simnet perf win on adversarial coverage: seeded random fault
schedules held to the safety/liveness oracles. These tests pin the three
contracts the campaign artifact depends on:

* the generator is deterministic per seed and only emits
  quorum-survivable plans (so an oracle violation is a finding, never a
  fuzzer artifact);
* a small campaign runs green and replays bit-identically — the tier-1
  smoke that keeps the entry point from rotting;
* the shrinker strips a planted failure down to a minimal reproducer
  that still trips the (stand-in) oracle.
"""

from __future__ import annotations

from narwhal_tpu.simnet import fuzz
from narwhal_tpu.simnet.plan import (
    Crash,
    Equivocate,
    FaultPlan,
    LinkFault,
    LinkSpec,
    Partition,
    Reconfigure,
)

# ---------------------------------------------------------------------------
# Generator: determinism + quorum survivability
# ---------------------------------------------------------------------------


def test_generate_plan_is_deterministic_and_seed_sensitive():
    a = fuzz.generate_plan(42)
    b = fuzz.generate_plan(42)
    assert a == b  # frozen dataclasses: structural equality is exact
    distinct = {repr(fuzz.generate_plan(seed)) for seed in range(16)}
    assert len(distinct) > 1  # seeds actually steer the draw


def test_generated_plans_are_quorum_survivable():
    """The generator's own safety envelope: at most f nodes byzantine or
    permanently down, partitions always heal with runway left, and every
    plan carries at least one event. If this envelope holds, a failing
    campaign row is a protocol finding, not a malformed plan."""
    nodes, duration = 4, 2.5
    f = (nodes - 1) // 3
    safe_end = duration - 1.2  # generate_plan's _RUNWAY
    for seed in range(40):
        plan = fuzz.generate_plan(seed, nodes=nodes, duration=duration)
        assert len(plan.events) >= 1
        permanent = sum(
            1
            for e in plan.events
            if isinstance(e, Crash) and e.restart_at is None
        )
        byzantine = sum(1 for e in plan.events if isinstance(e, Equivocate))
        assert permanent + byzantine <= f
        for e in plan.events:
            if isinstance(e, Partition):
                assert e.heal <= safe_end + 1e-9
                assert min(len(g) for g in e.groups) <= nodes // 2
            if isinstance(e, Crash) and e.restart_at is not None:
                assert e.restart_at <= safe_end + 1e-9
        # Until snapshot state-sync lands (ROADMAP item 1), a node that
        # restarts across an epoch change is stranded in the old epoch —
        # the generator must never pair a crash-with-restart with a
        # Reconfigure (the first campaign's only failure class).
        restarts = any(
            isinstance(e, Crash) and e.restart_at is not None
            for e in plan.events
        )
        reconfigures = any(isinstance(e, Reconfigure) for e in plan.events)
        assert not (restarts and reconfigures)


# ---------------------------------------------------------------------------
# Campaign smoke: the tier-1 guard on `python -m narwhal_tpu.simnet.fuzz`
# ---------------------------------------------------------------------------


def test_fuzz_campaign_smoke_three_seeds_green_and_deterministic():
    """Three seeded scenarios through the full stack (oracles included),
    twice: every row green, both passes identical row-for-row. This is the
    determinism contract the ledger's campaign records rely on — seed k
    names the same scenario outcome on every run."""

    def go():
        return fuzz.run_campaign(
            count=3, base_seed=0, duration=2.0, shrink_failing=False
        )

    a = go()
    b = go()
    assert a["ok"] and b["ok"]
    assert len(a["scenarios"]) == 3
    assert a["scenarios"] == b["scenarios"]
    assert all(row["rounds"] >= 1 for row in a["scenarios"])


def test_checked_plan_replays_bit_identically_under_load():
    """Seeded-replay bit-identity (commits + event-log digest) with every
    optimization on the hot path enabled: shared verify plane with
    sign-time verdict seeding, fixed-base signing tables, batched fabric
    flushes, inline frame drains."""
    plan = fuzz.generate_plan(0, duration=2.0)
    ok_a, _, a = fuzz.check_plan(plan, duration=2.0, load_rate=60)
    ok_b, _, b = fuzz.check_plan(plan, duration=2.0, load_rate=60)
    assert ok_a and ok_b
    assert a.commits == b.commits
    assert a.rounds == b.rounds
    assert a.event_log_digest == b.event_log_digest
    assert a.event_log_len == b.event_log_len


# ---------------------------------------------------------------------------
# Shrinker: planted failure -> minimal reproducer
# ---------------------------------------------------------------------------


def test_shrink_minimizes_planted_failure_to_reproducer():
    """Plant a known-bad trigger (a partition whose window covers t=1.0)
    among noise events and a noisy default link. The shrinker must delete
    every event that is not the trigger, pull the default link to quiet,
    and hand back a plan that still trips the oracle stand-in."""
    plan = FaultPlan(
        seed=1,
        default_link=LinkSpec(latency=0.004, jitter=0.001, drop=0.01),
        events=(
            LinkFault(
                at=0.2, a=0, b=2, link=LinkSpec(latency=0.02), end=1.0
            ),
            Crash(at=0.3, node=1, restart_at=0.8),
            Partition(at=0.6, heal=1.4, groups=((0,), (1, 2, 3))),
        ),
    )

    def still_fails(candidate: FaultPlan) -> bool:
        return any(
            isinstance(e, Partition) and e.at <= 1.0 <= e.heal
            for e in candidate.events
        )

    assert still_fails(plan)
    minimal = fuzz.shrink(plan, still_fails)
    assert still_fails(minimal)  # the reproducer still trips the oracle
    assert len(minimal.events) == 1
    assert isinstance(minimal.events[0], Partition)
    # Parameter pass ran too: onset pulled earlier, link pulled to quiet.
    assert minimal.events[0].at < 0.6
    assert minimal.default_link == LinkSpec(latency=0.0, jitter=0.0, drop=0.0)


def test_shrink_is_bounded_by_max_checks():
    """A pathological predicate (always fails) cannot loop the shrinker:
    the candidate-evaluation budget caps total work."""
    plan = fuzz.generate_plan(3)
    calls = 0

    def always_fails(_candidate: FaultPlan) -> bool:
        nonlocal calls
        calls += 1
        return True

    fuzz.shrink(plan, always_fails, max_checks=10)
    assert calls <= 10
