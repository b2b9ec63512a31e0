"""One run of one cell: set-up, the measured window, the drain, teardown,
the comparison, the result.

The entry the window drives is the served path as it stands: `Cluster` ->
`PrimaryNode`/`WorkerNode` with both device backends; clients submit
`SubmitTransactionStreamMsg` to each worker lane over `NetworkClient`;
results are read from every validator's `primary.tx_execution_output`.
"""

from __future__ import annotations

import array
import asyncio
import collections
import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

from . import judge, trace_reduce, traffic
from .readers import flight_window

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")
DETOURS = ("msm_redispatch", "group_solo_redispatch", "group_host_verify")
STAGES = (
    ("seal", "worker", "worker_stage_latency_seconds"),
    ("propose", "primary", "primary_stage_latency_seconds"),
    ("certify", "primary", "primary_stage_latency_seconds"),
    ("commit", "primary", "consensus_stage_latency_seconds"),
    ("execute", "primary", "executor_stage_latency_seconds"),
)
ACK_TIMEOUT_S = 30.0
BOOT_TIMEOUT_S = 240.0
WARMED = "msm_accumulate_kernel"  # the one verify kernel all-valid traffic dispatches
TRACE_SLICE_S = 0.5
CERT_SAMPLE = 24
PENDING, ACKED, SHED, ERRORED = 0, 1, 2, 3


class HarnessFault(Exception):
    """The harness could not make a run (no chip, no boot, warm-up refused).
    Nothing the load does raises this."""


class NoChip(HarnessFault):
    """No accelerator, or fewer chips than the cell asks for: exit 2."""


def say(t0: float, msg: str) -> None:
    print(f"[chipbench +{time.monotonic() - t0:7.2f}s] {msg}", flush=True)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, mix), found by the names in
    BENCHMARK.json and nothing else."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise HarnessFault(f"no workload {workload!r} in BENCHMARK.json: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    try:  # before the committee boots, not after a window
        judge.ordering_reference(cfg["consensus_protocol"])
    except LookupError as e:
        raise HarnessFault(str(e)) from None
    return bench, cell, cfg, traffic.load_mix(cell["traffic"])


def load_reader(metric: str):
    path = os.path.join(HERE, "readers", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"chipbench_reader_{abs(hash(metric))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class _NeverHits:
    """Stands in for `types._AGG_VERDICT_CACHE` for the run (copied from
    chip_smoke.py): that cache is process-wide and seeded by each
    certificate's assembler, so co-hosted validators would answer each
    other's proof checks and the group lane would never dispatch. With it
    out of the way each validator checks each proof as one on its own
    machine would. ROADMAP D7 retires this."""

    def get(self, key):
        return None

    def put(self, key, value, weight: int = 0) -> None:
        pass


def percentile(sorted_values: list[float], q: float) -> float:
    return sorted_values[min(len(sorted_values) - 1, int(q * len(sorted_values)))]


def warm_verify(svc, bucket: int) -> tuple[float, tuple]:
    """One all-valid bucket through the service's verifier: traces and
    compiles (or loads) `msm_accumulate_kernel` at the served shape. Not
    `precompile()`: that also warms `verify_batch_kernel`, which all-valid
    traffic under the cofactored rule never dispatches."""
    import hashlib

    from narwhal_tpu.crypto import KeyPair

    keys = [KeyPair.from_seed(hashlib.sha256(b"chipbench-warm-%d" % i).digest()) for i in range(8)]
    items = []
    for i in range(bucket):
        kp = keys[i % len(keys)]
        msg = hashlib.sha256(b"chipbench-warm-msg-%d" % i).digest()
        # Not KeyPair.sign: that seeds the process-wide verified-signature cache.
        items.append((kp.public, msg, kp._private.sign(msg)))
    t0 = time.monotonic()
    verdicts = svc.verifier(items)
    if len(verdicts) != bucket or not all(verdicts):
        raise HarnessFault("warm-up bucket of valid signatures was not accepted")
    if sum(svc.verifier.counts[d] for d in DETOURS):
        raise HarnessFault(f"warm-up took a detour: {dict(svc.verifier.counts)}")
    return time.monotonic() - t0, items[0]


def warm_shapes(cfg: dict) -> int:
    """First dispatches of the small DAG kernels at the shapes this
    configuration's committee meets only now and then (a padded batch
    length, the next window size): each is a fraction of a second of jit
    trace and compile that the persistent cache does not keep, and would
    otherwise fall inside the window. The shapes are data, in the
    configuration's file, as `kernel_registry.compile_walls()` names them."""
    import numpy as np

    from narwhal_tpu.tpu import kernel_registry

    done = 0
    for kernel, signatures in cfg.get("warm_shapes", {}).items():
        fn = kernel_registry.get_kernel(kernel)
        for sig in signatures:
            args = []
            for part in sig.split(";"):
                dtype, dims = part.rstrip("]").split("[")
                shape = tuple(int(d) for d in dims.split(",") if d)
                args.append(np.zeros(shape, np.dtype(dtype)) if shape else np.dtype(dtype).type(0))
            out = fn(*args)
            for leaf in out if isinstance(out, (tuple, list)) else (out,):
                getattr(leaf, "block_until_ready", lambda: None)()
            done += 1
    return done


def take_flight(traced: bool, acked: int) -> dict | None:
    """The program's process flight ring, taken once when the drain ends:
    before the trace is written and the committee shut down, while it writes
    on into the ring, so a committee that writes fast does not push the
    window's first records out before the readers come to them. None for a
    program without the ring. A traced run whose clients were answered and
    whose snapshot holds no `ingest_first` has lost its window to the ring's
    size, which the fault names."""
    from narwhal_tpu import tracing

    dump = getattr(tracing, "flight_dump", None)
    if dump is None:
        return None
    flight = dump()
    flight["t_taken"] = time.monotonic()
    if traced and acked and not any(r.kind == "ingest_first" for r in flight["events"]):
        kept = flight_window.coverage({"flight": flight})
        raise HarnessFault(
            f"the flight ring lost the window: no ingest_first among the {kept['records']} records kept "
            f"by a ring of {kept['ring_capacity']}, which cover the last {kept['seconds_kept']:.1f} s; "
            "this committee needs a larger narwhal_tpu.tracing.FLIGHT_RING")
    return flight


def device_rtt_ms(jax) -> float:
    import numpy as np

    bump = jax.jit(lambda x: x + 1)
    dev = jax.device_put(np.zeros((8,), np.int32))
    np.asarray(bump(dev))
    trips = []
    for _ in range(20):
        t0 = time.perf_counter()
        np.asarray(bump(dev))
        trips.append(time.perf_counter() - t0)
    return 1000 * statistics.median(trips)


async def boot(cfg: dict, overrides: dict, store_base: str, attempts: int = 3):
    from narwhal_tpu.cluster import Cluster
    from narwhal_tpu.config import Parameters

    params = dataclasses.replace(
        Parameters(), **{**cfg["parameters"], **overrides.get("parameters", {})}
    )
    n = overrides.get("validators", cfg["committee"]["validators"])
    last = None
    for attempt in range(attempts):
        base = os.path.join(store_base, f"boot-{attempt}")
        cluster = Cluster(
            size=n,
            workers=cfg["committee"]["workers_per_validator"],
            parameters=params,
            crypto_backend=cfg["backends"]["crypto"],
            dag_backend=cfg["backends"]["dag"],
            consensus_protocol=cfg["consensus_protocol"],
            store_base=base,
        )
        try:
            await cluster.start()
            return cluster, base
        except OSError as e:  # a port lost between the probe and the bind
            last = e
            try:
                await asyncio.wait_for(cluster.shutdown(), 30.0)
            except Exception as e2:
                print(f"chipbench: shutdown after failed boot: {e2!r}", flush=True)
    raise HarnessFault(f"committee did not boot in {attempts} attempts: {last!r}")


def check_storage_engine(cluster, cfg: dict) -> None:
    """The configuration names the write-ahead-log engine its stores run
    (`storage_engine`, switched by `env_at_boot`). If the program stops
    honouring the switch, or stops saying which engine a store runs, the
    cell would quietly measure another engine: a harness fault instead."""
    want = cfg["storage_engine"]
    for a in cluster.authorities:
        for node in [a.primary, *a.workers.values()]:
            engine = node.storage.engine
            if not hasattr(engine, "_native"):
                raise HarnessFault("StorageEngine no longer says which engine it runs (_native): "
                                   "revisit storage_engine / env_at_boot in the configuration")
            got = "python" if engine._native is None else "native"
            if got != want:
                raise HarnessFault(f"a store runs the {got} engine, the configuration states {want}")


def counters(cluster, svc) -> dict:
    from narwhal_tpu.network.rpc import WireStats

    stages = {}
    for stage, role, metric in STAGES:
        total, count = 0.0, 0
        for a in cluster.authorities:
            regs = (
                [w.registry for w in a.workers.values()] if role == "worker" else [a.primary.registry]
            )
            for reg in regs:
                m = reg.get(metric)
                if m is not None:
                    child = m.labels(stage)
                    total += child.sum
                    count += child.count
        stages[stage] = (total, count)
    shed = 0.0
    for a in cluster.authorities:
        for w in a.workers.values():
            shed += w.registry.value("worker_ingest_shed")
    return {
        "t": time.monotonic(),
        "rounds": [a.metric("consensus_last_committed_round") for a in cluster.authorities],
        "flushes": collections.Counter(svc.flushes),
        "verifier": collections.Counter(svc.verifier.counts),
        "stages": stages,
        "wire": WireStats.snapshot(),
        "shed_bursts": shed,
    }


def delta(before: dict, after: dict) -> dict:
    return {
        "seconds": after["t"] - before["t"],
        "rounds": statistics.median(b - a for a, b in zip(before["rounds"], after["rounds"])),
        "flushes": dict(after["flushes"] - before["flushes"]),
        "verifier": dict(after["verifier"] - before["verifier"]),
        "stages": {
            s: (after["stages"][s][0] - before["stages"][s][0],
                after["stages"][s][1] - before["stages"][s][1])
            for s in after["stages"]
        },
        "wire": {
            k: after["wire"][k] - before["wire"][k]
            for k in after["wire"] if isinstance(after["wire"][k], (int, float))
        },
        "shed_bursts": after["shed_bursts"] - before["shed_bursts"],
    }


async def serve(ctx: "Ctx", args, rate: float, store_root: str, setup: dict, fault=None) -> dict:
    """Boot, ramp, window, drain, shutdown. Returns the run's record."""
    cfg, mix, svc, jax, t_proc, probe = ctx.cfg, ctx.mix, ctx.svc, ctx.jax, ctx.t_proc, ctx.probe
    from narwhal_tpu.messages import SubmitTransactionStreamMsg
    from narwhal_tpu.network import NetworkClient
    from narwhal_tpu.tpu import kernel_registry

    overrides = args.overrides
    t0 = time.monotonic()
    cluster, store_base = await boot(cfg, overrides, store_root)
    client = NetworkClient()
    drains: list[asyncio.Task] = []
    inflight: set[asyncio.Task] = set()
    tracing_on = False
    rec: dict = {"store_base": store_base}
    try:
        if not all(a.primary.crypto_pool is svc for a in cluster.authorities):
            raise HarnessFault("nodes do not share the warmed verify service")
        check_storage_engine(cluster, cfg)
        await cluster.assert_progress(commit_threshold=4, timeout=BOOT_TIMEOUT_S)
        setup["boot_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        warm_shapes(cfg)
        setup["dag_warm_s"] = time.monotonic() - t0
        n = len(cluster.authorities)
        workers = cfg["committee"]["workers_per_validator"]
        lanes = [
            a.worker_transactions_address(w) for a in cluster.authorities for w in range(workers)
        ]

        t0 = time.monotonic()
        warm_s = float(mix.get("warm_s", 0.0))
        bursts, txs = traffic.schedule(
            mix, rate, len(lanes), warm_s + args.seconds, args.seed, cfg["tx_bytes"]
        )
        max_id = len(txs) - 1
        first_window_id = next((b.first_id for b in bursts if b.due >= warm_s), max_id + 1)
        setup["schedule_s"] = time.monotonic() - t0
        rtt = device_rtt_ms(jax)

        orders: list[list[int]] = [[] for _ in range(n)]
        seen = [bytearray(max_id + 1) for _ in range(n)]
        when = [array.array("d", bytes(8 * (max_id + 1))) for _ in range(n)]
        twice, unknown = [0] * n, [0] * n

        async def drain(v: int) -> None:
            ch = cluster.authorities[v].primary.tx_execution_output
            order, mine, at = orders[v], seen[v], when[v]
            while True:
                item = await ch.recv()
                now = time.monotonic()
                while item is not None:
                    tx = bytes(item[1])
                    tx_id = int.from_bytes(tx[1:9], "big") if len(tx) >= 9 else 0
                    if 0 < tx_id <= max_id and txs[tx_id] == tx:
                        order.append(tx_id)
                        if mine[tx_id]:
                            twice[v] += 1
                        else:
                            mine[tx_id] = 1
                            at[tx_id] = now
                    else:
                        order.append(-1)
                        unknown[v] += 1
                    item = ch.try_recv()

        drains = [asyncio.ensure_future(drain(v)) for v in range(n)]
        if fault is not None:
            fault(cluster)
        state = [PENDING] * len(bursts)
        sent = [0.0] * len(bursts)

        async def submit(i: int, b) -> None:
            try:
                await client.request(
                    lanes[b.lane], SubmitTransactionStreamMsg((), b.raw), timeout=ACK_TIMEOUT_S
                )
                state[i] = ACKED
            except Exception as e:  # the load's doing, never the run's end
                state[i] = SHED if "RESOURCE_EXHAUSTED" in str(e) else ERRORED

        snaps: dict = {}
        trace_dir = os.path.join(OUT, f"trace.{args.workload}.{args.seed}")
        slice_s = min(TRACE_SLICE_S, args.seconds / 2)
        # The slice is the window's last: writing the trace out takes a
        # minute of host time, which then falls on the drain, not the window.
        trace_at = warm_s + args.seconds - slice_s
        trace_window = [0.0, 0.0]

        def start_trace() -> None:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            trace_window[0] = time.monotonic()

        def stop_trace() -> None:
            while trace_window[0] == 0.0:  # the start is still on its way
                time.sleep(0.01)
            trace_window[1] = time.monotonic()
            jax.profiler.stop_trace()
            trace_window.append(time.monotonic() - trace_window[1])

        async def probe_slice() -> None:
            """One signature of the harness's own through the service once the
            slice is open: a flush, padded to the served bucket like any
            other, so a slice that falls into a lull between rounds still
            holds one `msm_accumulate_kernel` program to read."""
            await trace_jobs[0]
            await svc.verify(*probe)

        loop = asyncio.get_running_loop()
        trace_jobs: list = []  # the profiler's start and stop, off the loop
        t_start = time.monotonic() + 0.05
        t_open = t_start + warm_s
        setup_s = t_open - t_proc
        snaps["start"] = None
        walls_open = None
        for i, b in enumerate(bursts):
            if snaps["start"] is None and b.due >= warm_s:
                snaps["start"] = counters(cluster, svc)
                walls_open = {(r["kernel"], r["mesh"], r["shapes"]) for r in kernel_registry.compile_walls()}
            if args.trace and not tracing_on and b.due >= trace_at:
                trace_jobs.append(loop.run_in_executor(None, start_trace))
                tracing_on = True
                inflight.add(asyncio.ensure_future(probe_slice()))
            if tracing_on and len(trace_jobs) == 1 and b.due >= trace_at + slice_s:
                trace_jobs.append(loop.run_in_executor(None, stop_trace))
            wait = t_start + b.due - time.monotonic()
            if wait > 0:
                await asyncio.sleep(wait)
            sent[i] = time.monotonic()
            task = asyncio.ensure_future(submit(i, b))
            inflight.add(task)
            task.add_done_callback(inflight.discard)
        wait = t_open + args.seconds - time.monotonic()
        if wait > 0:
            await asyncio.sleep(wait)
        if tracing_on and len(trace_jobs) == 1:
            trace_jobs.append(loop.run_in_executor(None, stop_trace))
        if snaps["start"] is None:
            snaps["start"] = counters(cluster, svc)
        snaps["end"] = counters(cluster, svc)
        t_close = time.monotonic()
        new_walls = [
            r for r in kernel_registry.compile_walls()
            if walls_open is not None and (r["kernel"], r["mesh"], r["shapes"]) not in walls_open
        ]
        first_in_window = [f"{r['kernel']}[{r['shapes']}] {r['wall_s']:.3f}s" for r in new_walls]

        # The drain: wait for every acknowledged burst, as long as the mix
        # allows. What has not executed everywhere by then is `failed`.
        def settled() -> bool:
            for i, b in enumerate(bursts):
                if state[i] == PENDING:
                    return False
                if state[i] == ACKED:
                    last = b.first_id + b.count - 1
                    if not all(s[last] for s in seen):
                        return False
            return True

        drain_until = t_close + float(mix["drain_s"])
        while not settled() and time.monotonic() < drain_until:
            await asyncio.sleep(0.1)
        await asyncio.sleep(0.2)  # a duplicate execution would land now
        t_drained = time.monotonic()
        flight = take_flight(bool(args.trace), state.count(ACKED))
        if trace_jobs:
            await asyncio.gather(*trace_jobs)
            setup["trace_write_s"] = trace_window[2]
        snaps["drained"] = counters(cluster, svc)

        # Reduce what the clients saw.
        import numpy as np

        window = [(i, b) for i, b in enumerate(bursts) if b.due >= warm_s]
        attempted = sum(b.count for _, b in window)
        counts = np.array([b.count for b in bursts])
        due = np.concatenate([[0.0], np.repeat([t_start + b.due for b in bursts], counts)])
        acked = np.concatenate([[False], np.repeat([st == ACKED for st in state], counts)])
        in_win = np.arange(max_id + 1) >= first_window_id
        everywhere = np.array([np.frombuffer(s, np.uint8) for s in seen]).all(axis=0)
        done = np.array([np.frombuffer(w, np.float64) for w in when]).max(axis=0)
        t_end = t_open + args.seconds
        # The rate is over all the work of the window: every transaction,
        # the ramp's too, whose last validator executed it inside it.
        in_window = int((everywhere & (done >= t_open) & (done <= t_end)).sum())
        failed = int((in_win & ~everywhere).sum())
        # A failed transaction counts at the drain's horizon: as late as the
        # run can know it to be.
        latencies = sorted(
            (1000 * (np.where(everywhere, done, t_drained) - due)[in_win]).tolist()
        )
        late = sorted(1000 * (sent[i] - (t_start + b.due)) for i, b in window)
        unexecuted_acked = np.nonzero(acked & ~everywhere)[0].tolist()
        backlog = [
            int((in_win & acked & (due <= cut) & ~(everywhere & (done <= cut))).sum())
            for cut in (t_open + args.seconds / 2, t_end)
        ]
        states = collections.Counter(state[i] for i, _ in window)
        shed_tx = sum(b.count for i, b in window if state[i] == SHED)
        rec.update(
            validators=n, workers=workers, gc_depth=cluster.parameters.gc_depth,
            consensus_protocol=cluster.consensus_protocol,
            txs=txs, orders=orders, twice=twice, unknown=unknown,
            unexecuted_acked=unexecuted_acked,
            detours=sum(snaps["drained"]["verifier"][d] for d in DETOURS),
            attempted=attempted, failed=failed, setup_s=setup_s,
            obs={
                "seconds": float(args.seconds),
                "attempted": attempted,
                "offered_tx_per_s": attempted / args.seconds,
                "executed_in_window": in_window,
                "executed_total": attempted - failed,
                "shed_tx": shed_tx,
                "executions": sum(len(o) for o in orders),
                "executed_again": sum(twice),
                "bursts": {"acked": states[ACKED], "shed": states[SHED],
                           "errored": states[ERRORED], "unanswered": states[PENDING]},
                "latencies_ms": latencies,
                "late_ms": late,
                "window": delta(snaps["start"], snaps["end"]),
                "whole": delta(snaps["start"], snaps["drained"]),
                "drain_s": t_drained - t_close,
                "backlog_mid": backlog[0],
                "backlog_end": backlog[1],
                "rtt_ms": rtt,
                "first_dispatches_in_window": first_in_window,
                "verify_bucket": svc.verifier.max_bucket,
                "trace_dir": trace_dir if tracing_on else None,
                "trace_window_s": trace_window[1] - trace_window[0],
                "flight": flight,
            },
        )
        devs = jax.devices()
        stats = [d.memory_stats() or {} for d in devs[: args.chips]]
        rec["memory_peak_bytes"] = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        return rec
    finally:
        if tracing_on and len(trace_window) < 3:  # the run broke while tracing
            try:
                await asyncio.gather(*trace_jobs, return_exceptions=True)
                if trace_window[1] == 0.0:
                    jax.profiler.stop_trace()
            except Exception as e:
                print(f"chipbench: stop_trace in teardown: {e!r}", flush=True)
        for task in list(inflight) + drains:
            task.cancel()
        await asyncio.gather(*inflight, *drains, return_exceptions=True)
        client.close()
        t0 = time.monotonic()
        try:
            await asyncio.wait_for(cluster.shutdown(), 90.0)
        except Exception as e:  # an earlier line, never an exit code
            print(f"chipbench: cluster shutdown did not finish cleanly: {e!r}", flush=True)
        setup["teardown_s"] = time.monotonic() - t0


def stop_device_plane() -> list[str]:
    """Stop every thread the device plane started (a daemon thread frozen in
    XLA at interpreter exit aborts the process after the result line)."""
    from narwhal_tpu.tpu import dag_kernels
    from narwhal_tpu.tpu.verifier import VerifyService

    stuck = [f"verify service {k}" for k, s in list(VerifyService._shared.items()) if not s.shutdown()]
    alive = dag_kernels.join_prewarm_threads(60.0)
    if alive:
        stuck.append(f"{alive} prewarm thread(s)")
    return stuck


@dataclasses.dataclass
class Ctx:
    """What one process sets up once, however many windows it then drives."""

    bench: dict
    cell: dict
    cfg: dict
    mix: dict
    jax: object
    device: dict
    svc: object
    setup: dict
    rehearsal: bool
    t_proc: float
    probe: tuple  # one valid (key, message, signature) for the traced slice


def prepare(args, t_proc: float) -> Ctx:
    """Find the cell, look for the chip, build the native libraries, warm
    the verify kernel. Raises NoChip / HarnessFault."""
    setup: dict = {}
    bench, cell, cfg, mix = load_cell(args.workload)
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    rehearsal = bool(args.overrides)
    if device["platform"] != "tpu" and not (
        rehearsal and device["platform"] == "cpu"
        and "cpu" in os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    ):
        raise NoChip(f"JAX found no TPU (platform={device['platform']}); nothing ran")
    if len(devs) < cell["chips"]:
        raise NoChip(f"cell asks for {cell['chips']} chip(s), JAX has {len(devs)}")
    args.chips = cell["chips"]
    say(t_proc, f"device {device}; cell {cell['name']}"
        + (" — REHEARSAL on CPU: counts only, no device metric" if rehearsal else ""))

    from narwhal_tpu import native
    from narwhal_tpu.tpu.verifier import VerifyService

    libs = {"storage": native.load() is not None, "scalar": native.load_scalar() is not None}
    if not all(libs.values()):
        raise HarnessFault(f"native libraries did not build or load: {libs}")
    setup["imports_native_s"] = time.monotonic() - t_proc
    # Switches of the program that the configuration states for the
    # committee, set once the native libraries are loaded and before it boots.
    os.environ.update({k: str(v) for k, v in cfg.get("env_at_boot", {}).items()})

    bucket = int(args.overrides.get("verify_bucket", cfg["verify_bucket"]))
    svc = VerifyService.shared("msm", bucket=bucket)
    if svc.verifier.max_bucket != bucket:
        raise HarnessFault(f"verify service bucket {svc.verifier.max_bucket} != {bucket}")
    setup["verify_warm_s"], probe = warm_verify(svc, bucket)
    say(t_proc, f"set-up: imports+native {setup['imports_native_s']:.2f}s, "
        f"verify warm-up {setup['verify_warm_s']:.2f}s")
    return Ctx(bench, cell, cfg, mix, jax, device, svc, setup, rehearsal, t_proc, probe)


def cell_rate(ctx: Ctx, args) -> float:
    return float(
        args.overrides.get("rate") or ctx.mix["rate_share_of_knee"] * ctx.cfg["knee_tx_per_s"]
    )


def measure(ctx: Ctx, args, rate: float, fault=None) -> dict:
    """One committee, one window, its drain and teardown, then the plain
    reference over what it left on disk. Returns the run's record."""
    from narwhal_tpu import types

    setup = dict(ctx.setup)
    # Co-hosting: each validator checks each certificate proof itself. If a
    # later PR has scoped or removed that cache, go on without the stand-in:
    # verify.group_flushes_per_round shows whether the lane ran.
    shared_cache = getattr(types, "_AGG_VERDICT_CACHE", None)
    if shared_cache is not None:
        types._AGG_VERDICT_CACHE = _NeverHits()
    store_root = tempfile.mkdtemp(prefix="chipbench-stores-")
    try:
        rec = asyncio.run(
            serve(ctx, args, rate, store_root, setup, fault=fault)
        )
        # The state is freed and the peak is read: now the plain reference.
        t0 = time.monotonic()
        numbers = judge.client_side(rec)
        store_numbers, rec["notes"] = judge.stores_side(rec, args.seed, CERT_SAMPLE)
        numbers.update(store_numbers)
        rec["reference_s"] = time.monotonic() - t0
    finally:
        if shared_cache is not None:
            types._AGG_VERDICT_CACHE = shared_cache
        shutil.rmtree(store_root, ignore_errors=True)
    rec["setup"] = setup
    rec["rate"] = rate
    rec["correct"], rec["checks"] = judge.verdict(numbers)
    say(ctx.t_proc, "set-up: " + ", ".join(f"{k} {v:.2f}s" for k, v in setup.items())
        + f"; setup_s {rec['setup_s']:.2f}")
    for big in ("txs", "orders", "unexecuted_acked"):
        rec.pop(big)
    return rec


def finish(ctx: Ctx, args, rec: dict) -> dict:
    """The result object of a run, its metrics each from a reader of its
    own, and the run's file under chipbench/out."""
    obs, device, rehearsal = rec["obs"], dict(ctx.device), ctx.rehearsal
    obs["config"] = ctx.cfg
    obs["mix"] = ctx.mix
    # The one percentile: the readers take theirs from these.
    lat, late = obs["latencies_ms"], obs["late_ms"]
    obs["latency"] = {"p50": percentile(lat, 0.5), "p95": percentile(lat, 0.95),
                      "max": lat[-1], "n": len(lat)} if lat else {}
    obs["late"] = {"p50": percentile(late, 0.5), "p95": percentile(late, 0.95),
                   "max": late[-1]} if late else {}
    trace = None
    if args.trace and not rehearsal:
        peaks = load_json(os.path.join(HERE, "peaks.json"))
        if device["kind"] not in peaks:
            raise HarnessFault(f"no peaks for device kind {device['kind']!r} in peaks.json")
        obs["peaks"] = peaks[device["kind"]]
    if obs["trace_dir"]:
        path = trace_reduce.find_xplane(obs["trace_dir"])
        trace = trace_reduce.reduce_file(path, device["platform"].upper()) if path else None
        shutil.rmtree(obs["trace_dir"], ignore_errors=True)
    obs["trace"] = trace

    device["memory_peak_bytes"] = rec["memory_peak_bytes"]
    if args.trace:
        device["busy_s"] = trace["busy_s"] if trace else 0.0
        device["window_s"] = obs["trace_window_s"]

    group = "per_layer" if args.trace else "end_to_end"
    metrics: dict = {}
    for m in ctx.bench[group]:
        if "workloads" in m and ctx.cell["name"] not in m["workloads"]:
            continue
        if rehearsal and m["source"] != "program_counter":
            continue  # a CPU run prints counts only
        value = rec["setup_s"] if m["name"] == "setup_s" else load_reader(m["name"])(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    result = {
        "correct": rec["correct"],
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": metrics,
        "device": device,
    }
    if args.trace and trace:
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    result["checks"] = rec["checks"]

    summary = {k: v for k, v in obs.items() if k not in ("latencies_ms", "late_ms", "config", "mix")}
    summary["flight"] = flight_window.coverage(obs)  # the snapshot's size, not its records
    out = {
        "workload": args.workload, "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "rate_tx_per_s": rec["rate"], "rehearsal": rehearsal,
        "setup": rec["setup"], "setup_s": rec["setup_s"], "reference_s": rec["reference_s"],
        "notes": rec["notes"], "observed": summary, "result": result,
    }
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"{args.workload}.{args.seed}.json"), "w") as f:
        json.dump(out, f, indent=1, default=str)
    say(ctx.t_proc, f"window: offered {obs['offered_tx_per_s']:.1f} tx/s, executed in window "
        f"{obs['executed_in_window']}/{rec['attempted']}, failed {rec['failed']}, bursts {obs['bursts']}, "
        f"backlog mid {obs['backlog_mid']} end {obs['backlog_end']}, "
        f"rounds {obs['window']['rounds']}, drain {obs['drain_s']:.2f}s, reference {rec['reference_s']:.2f}s, "
        f"first dispatches in window {obs['first_dispatches_in_window']}")
    if lat:
        say(ctx.t_proc, f"latency ms {obs['latency']}; generator late ms {obs['late']}")
    return result


def print_checks(rec: dict) -> None:
    """Each number compared beside its limit: the last lines of stderr."""
    for name, (value, limit) in rec["checks"].items():
        print(f"chipbench check {name}: {value} (limit {limit})", file=sys.stderr)
    print(f"chipbench correct: {rec['correct']}", file=sys.stderr, flush=True)


def run(args, t_proc: float) -> dict:
    """The whole run. Returns the result object; raises HarnessFault."""
    ctx = prepare(args, t_proc)
    rec = measure(ctx, args, cell_rate(ctx, args), fault=args.fault)
    stuck = stop_device_plane()
    if stuck:
        print(f"chipbench: still running at teardown: {stuck}", flush=True)
    result = finish(ctx, args, rec)
    print_checks(rec)
    return result
