"""Unit tests for the benchmark tooling: aggregate grouping/statistics and
the latency-throughput plotter (the reference's aggregate.py / plot.py)."""

import json

from benchmark.aggregate import aggregate
from benchmark.plot import plot
from benchmark.sweep import render_table


def _record(rate, tps, lat, **over):
    rec = {
        "faults": 0,
        "committee_size": 4,
        "workers_per_node": 1,
        "input_rate": rate,
        "tx_size": 512,
        "duration_s": 20.0,
        "consensus_tps": tps,
        "consensus_bps": tps * 512,
        "consensus_latency_ms": lat,
        "end_to_end_tps": tps * 0.98,
        "end_to_end_bps": tps * 512 * 0.98,
        "end_to_end_latency_ms": lat * 1.4,
    }
    rec.update(over)
    return rec


def test_aggregate_groups_and_stats():
    runs = [
        _record(10_000, 9_800, 250),
        _record(10_000, 10_200, 270),
        _record(20_000, 18_000, 600),
    ]
    agg = aggregate(runs)
    assert len(agg) == 2
    by_rate = {a["input_rate"]: a for a in agg}
    assert by_rate[10_000]["runs"] == 2
    assert by_rate[10_000]["consensus_tps"] == 10_000
    assert by_rate[10_000]["consensus_tps_std"] > 0
    assert by_rate[20_000]["runs"] == 1
    assert by_rate[20_000]["consensus_tps_std"] == 0.0


def test_plot_writes_png(tmp_path):
    sweep = [_record(r, min(r, 26_000) * 0.95, 200 + r / 100) for r in (5_000, 15_000, 30_000)]
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(sweep))
    out = plot([str(path)], str(tmp_path / "curve.png"))
    assert (tmp_path / "curve.png").stat().st_size > 1_000
    assert out.endswith("curve.png")


def test_sweep_table_finds_knee():
    results = [_record(5_000, 4_900, 200), _record(30_000, 26_000, 900), _record(40_000, 25_500, 1_800)]
    table = render_table(results)
    assert "knee: ~26,000" in table
    assert "| 5,000 |" in table


def test_fd_preflight_estimates_and_fails_fast(monkeypatch):
    """The liveness preflight, honest for BOTH transport models: legacy
    N=100 W=1 demands ~2·N·(N-1)·2 fds (an N=100 run died of EMFILE
    at ~19.8k mesh sockets under a 20k limit) and fails BEFORE boot with a
    message pointing at --simnet; pooled collapses that to one link per
    node pair and fits the same rlimit."""
    import resource

    import pytest

    from benchmark.liveness import estimate_required_fds, preflight_fd_check

    # Legacy estimate must at least cover the measured N=100 failure
    # (~19.8k mesh sockets => ~40k fds both-endpoints-in-process).
    assert estimate_required_fds(100, 1, pooled=False) > 19_800
    # Pooled: N(N-1)/2 pair links + N self links, worker lanes ride them —
    # ~13.5k fds, comfortably under the 20k rlimit that EMFILEd r9.
    assert estimate_required_fds(100, 1, pooled=True) < 20_000
    assert (
        estimate_required_fds(100, 1, pooled=True)
        < estimate_required_fds(100, 1, pooled=False)
    )
    # Monotone in both axes, in both models.
    for pooled in (True, False):
        assert estimate_required_fds(100, 2, pooled) > estimate_required_fds(
            100, 1, pooled
        )
        assert estimate_required_fds(200, 1, pooled) > estimate_required_fds(
            100, 1, pooled
        )

    monkeypatch.setattr(
        resource, "getrlimit", lambda which: (20_000, 20_000)
    )
    with pytest.raises(SystemExit) as err:
        preflight_fd_check(100, 1, pooled=False)
    msg = str(err.value)
    assert "--simnet" in msg and "RLIMIT_NOFILE" in msg
    # The pooled model fits the very rlimit that EMFILEd the legacy mesh.
    preflight_fd_check(100, 1, pooled=True)
    # The default resolves pooling from NARWHAL_POOL (on unless disabled).
    monkeypatch.setenv("NARWHAL_POOL", "0")
    with pytest.raises(SystemExit):
        preflight_fd_check(100, 1)
    monkeypatch.delenv("NARWHAL_POOL")
    preflight_fd_check(100, 1)
    # A committee that fits passes silently in either model.
    preflight_fd_check(10, 1, pooled=False)
