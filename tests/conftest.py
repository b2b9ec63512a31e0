import os

# Tests exercise multi-device sharding on a virtual 8-device CPU mesh; a
# chip, where there is one, is chip_smoke.py's. Naming cpu here is also what
# lets device backends boot in tests (tpu/__init__.require_device_platform).
os.environ["JAX_PLATFORMS"] = "cpu"
# Background prewarm compiles (TpuBullshark._prewarm) contend with
# foreground jit traces for XLA's compiler locks: on this 1-core CI host
# that serializes every later trace behind a minutes-long background
# compile and has deadlocked main-thread traces mid-suite. Tests compile
# whatever they actually dispatch; ahead-of-need warming is a production
# concern.
os.environ.setdefault("NARWHAL_TPU_PREWARM", "0")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import asyncio
import warnings

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test"
    )


# Test files whose failures involve whole clusters (real or simulated):
# those are the ones where a post-mortem needs the per-node flight
# recorders, and the only ones worth the report bloat.
_FLIGHT_DUMP_FILES = (
    "test_lifecycle.py",
    "test_reconfigure.py",
    "test_simnet.py",
    "test_node.py",
    "test_telemetry.py",
)
_FLIGHT_DUMP_MAX_EVENTS = 400


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_makereport(item, call):
    """On cluster/simnet test failure, attach every node's flight-recorder
    dump (live tracers + the archive of already-shutdown nodes) to the
    report, as self-contained JSON the terminal reporter prints under its
    own section. The rings accumulate span edges, backpressure/occupancy
    snapshots, and anomaly markers regardless of NARWHAL_TRACE, so even an
    untraced run leaves a usable post-mortem."""
    outcome = yield
    report = outcome.get_result()
    if report.when != "call" or not report.failed:
        return
    if not any(f in str(item.fspath) for f in _FLIGHT_DUMP_FILES):
        return
    try:
        import json

        from narwhal_tpu import tracing

        dumps = tracing.all_dumps(max_events=_FLIGHT_DUMP_MAX_EVENTS)
        if not dumps:
            return
        payload = json.dumps(dumps, sort_keys=True, indent=1, default=str)
        # Bound the section so one failure can't flood the report.
        if len(payload) > 200_000:
            payload = payload[:200_000] + "\n... [truncated]"
        report.sections.append(
            (f"flight recorder ({len(dumps)} node dumps)", payload)
        )
    except Exception as exc:  # never let diagnostics break reporting
        report.sections.append(("flight recorder", f"dump failed: {exc!r}"))


@pytest.fixture(autouse=True)
def _fresh_flight_archive():
    """Scope flight-recorder post-mortems to the failing test: dumps parked
    by a previous test's teardown must not masquerade as this test's."""
    from narwhal_tpu import tracing

    tracing.clear_archive()
    yield


@pytest.fixture
def run():
    """Run a coroutine to completion on a fresh event loop.

    Not asyncio.run(): its _cancel_all_tasks cleanup waits FOREVER for
    leftover tasks to honor their cancellation, so one task parked on a
    cancel-immune await (e.g. a run_in_executor readback) hangs the whole
    suite — observed in-suite on the 1-core host. Cleanup here is bounded:
    cancel leftovers, give them a grace window, then abandon the stragglers
    with a warning and close the loop."""

    def _run(coro, timeout=30.0, cleanup_grace=15.0):
        loop = asyncio.new_event_loop()
        try:
            return loop.run_until_complete(asyncio.wait_for(coro, timeout))
        finally:
            pending = [t for t in asyncio.all_tasks(loop) if not t.done()]
            for t in pending:
                t.cancel()
            stuck = set()
            if pending:
                # asyncio.wait with a timeout neither cancels again nor
                # blocks on stragglers — it just stops waiting.
                _, stuck = loop.run_until_complete(
                    asyncio.wait(pending, timeout=cleanup_grace)
                )
                if stuck:
                    warnings.warn(
                        f"abandoning {len(stuck)} task(s) that ignored "
                        f"cancellation for {cleanup_grace}s: "
                        + ", ".join(repr(t.get_coro()) for t in stuck),
                        RuntimeWarning,
                        stacklevel=2,
                    )
            with warnings.catch_warnings():
                # Abandoned tasks destroyed with the loop are the point of
                # the bounded cleanup; don't let their teardown chatter
                # drown the test report.
                warnings.simplefilter("ignore")
                loop.run_until_complete(loop.shutdown_asyncgens())
                if not stuck:
                    # Joins executor threads with no timeout (3.10): safe
                    # only when nothing is known to be wedged.
                    loop.run_until_complete(loop.shutdown_default_executor())
                asyncio.set_event_loop(None)
                loop.close()

    return _run
