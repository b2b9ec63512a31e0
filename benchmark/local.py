"""LocalBench: boot a real multi-process committee on localhost and measure.

Reference: /root/reference/benchmark/benchmark/local.py — generates keys and
committee files, spawns every primary/worker as its own OS process (tmux
there, subprocess here; each `python -m narwhal_tpu run ...` is the same
single-role binary shape as the reference's `node run`), injects load with
benchmark clients, then parses the logs. `faults: f` leaves the last f nodes
unbooted (the reference's only fault-injection mechanism).
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import subprocess
import sys
import time
from dataclasses import dataclass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from narwhal_tpu.config import (
    Authority,
    Committee,
    ConfigError,
    Parameters,
    WorkerCache,
    WorkerInfo,
    get_available_port,
    release_all_ports,
)
from narwhal_tpu.crypto import KeyPair

from .logs import LogParser


def parse_telemetry_addr(log_text: str) -> str | None:
    """Extract the primary's gRPC telemetry endpoint from its boot log.

    The node prints ONE machine-readable `TELEMETRY_ADDR=<host:port>`
    line at spawn (narwhal_tpu/__main__.py) — the contract that replaced
    regexing the human "gRPC public API listening on ..." log line, which
    broke whenever the log format moved. The LAST occurrence wins (a
    restarted node rebinds); an empty value means the gRPC plane is not
    mounted and there is nothing to scrape."""
    addr = None
    for line in log_text.splitlines():
        line = line.strip()
        if line.startswith("TELEMETRY_ADDR="):
            value = line.split("=", 1)[1].strip()
            addr = value or None
    return addr


@dataclass
class BenchParameters:
    nodes: int = 4
    workers: int = 1
    rate: int = 1_000
    tx_size: int = 512
    duration: int = 20
    faults: int = 0
    consensus_protocol: str = "bullshark"  # | tusk
    crypto_backend: str = "cpu"  # | pool | tpu
    dag_backend: str = "cpu"  # | tpu
    dag_shards: int = 1  # committee-axis device shards (tpu backend)
    mem_profiling: bool = False  # reference mem_profiling bench param


class LocalBench:
    def __init__(self, bench: BenchParameters, node_parameters: Parameters | None = None):
        self.bench = bench
        self.node_parameters = node_parameters or Parameters(
            max_header_delay=0.1, max_batch_delay=0.1
        )
        if bench.crypto_backend == "tpu" and node_parameters is None:
            # Default only: the whole fleet runs the tpu backend, so the
            # committee can uniformly opt into the cofactored accept set —
            # unlocking the msm batch kernel. An explicitly passed
            # Parameters keeps its verify_rule (e.g. to benchmark the
            # strict per-item kernel).
            from dataclasses import replace

            self.node_parameters = replace(
                self.node_parameters, verify_rule="cofactored"
            )
        self.base = os.path.abspath(".bench")
        self.procs: list[subprocess.Popen] = []
        # Per-primary Telemetry.Scrape snapshots from the last run()
        # (gRPC, taken just before teardown; sweep.py embeds them).
        self.telemetry_scrapes: dict[str, dict] = {}
        # Per-child open-fd counts sampled at steady state just before
        # teardown (sweep.py records the max as the per-node fd figure).
        self.child_fd_counts: dict[int, int] = {}

    # -- config generation (local.py + config.py of the reference) ---------

    def _generate_configs(self):
        shutil.rmtree(self.base, ignore_errors=True)
        os.makedirs(self.base)
        keypairs = [KeyPair.generate() for _ in range(self.bench.nodes)]
        authorities = {}
        workers = {}
        for i, kp in enumerate(keypairs):
            network_kp = KeyPair.generate()
            worker_kps = {wid: KeyPair.generate() for wid in range(self.bench.workers)}
            with open(f"{self.base}/key-{i}.json", "w") as f:
                json.dump(
                    {
                        "name": kp.public.hex(),
                        "seed": kp.private_bytes().hex(),
                        "network_seed": network_kp.private_bytes().hex(),
                        "worker_network_seeds": {
                            str(wid): wkp.private_bytes().hex()
                            for wid, wkp in worker_kps.items()
                        },
                    },
                    f,
                )
            authorities[kp.public] = Authority(
                stake=1,
                primary_address=f"127.0.0.1:{get_available_port()}",
                network_key=network_kp.public,
            )
            workers[kp.public] = {
                wid: WorkerInfo(
                    name=worker_kps[wid].public,
                    transactions=f"127.0.0.1:{get_available_port()}",
                    worker_address=f"127.0.0.1:{get_available_port()}",
                )
                for wid in range(self.bench.workers)
            }
        committee = Committee(authorities)
        committee.export(f"{self.base}/committee.json")
        WorkerCache(workers).export(f"{self.base}/workers.json")
        self.node_parameters.export(f"{self.base}/parameters.json")
        return committee, workers

    # -- process control ---------------------------------------------------

    def _spawn(self, argv: list[str], log_path: str) -> None:
        log = open(log_path, "w")
        env = dict(os.environ, PYTHONPATH=os.path.dirname(self.base) or ".")
        # This parent assigned every node's ports and holds SO_REUSEPORT
        # placeholders for them until the fleet is up; the children must
        # co-bind through those placeholders (RpcServer only sets
        # reuse_port for ports it can prove are placeheld). Advertise the
        # EXACT list — a blanket "all" would reinstate silent co-binding
        # for genuinely duplicate servers.
        from narwhal_tpu.config import placeheld_ports

        env["NARWHAL_PLACEHELD_PORTS"] = ",".join(map(str, placeheld_ports()))
        if self.bench.mem_profiling:
            env["NARWHAL_MEM_PROFILE"] = self.base
        self.procs.append(
            subprocess.Popen(
                [sys.executable, "-m", "narwhal_tpu", "-v", *argv],
                stdout=log,
                stderr=subprocess.STDOUT,
                env=env,
                cwd=os.path.dirname(os.path.abspath(__file__)) + "/..",
            )
        )

    def _wait_for_boot(self, paths: list[str], timeout: float = 180.0) -> None:
        """Block until every node log shows its boot line (the reference's
        fab-local pattern of parsing 'successfully booted'): the load window
        must not start while nodes are still importing jax/compiling —
        concurrent cold starts on a shared core can take tens of seconds,
        which would otherwise be billed to the measurement duration."""
        deadline = time.time() + timeout
        pending = set(paths)
        while pending and time.time() < deadline:
            for path in list(pending):
                try:
                    with open(path) as fh:
                        if "successfully booted" in fh.read():
                            pending.discard(path)
                except OSError:
                    pass
            for proc in self.procs:
                if proc.poll() not in (None, 0):
                    raise RuntimeError("a node process exited during boot")
            if pending:
                time.sleep(0.5)
        if pending:
            raise RuntimeError(
                f"nodes failed to boot within {timeout}s: {sorted(pending)}"
            )

    def _sample_child_fds(self) -> dict[int, int]:
        """Open-fd count of each live child (node or client) via procfs —
        the per-process number RLIMIT_NOFILE actually judges. Sampled at
        steady state, after every mesh/pool connection is up."""
        counts: dict[int, int] = {}
        for p in self.procs:
            if p.poll() is not None:
                continue
            try:
                counts[p.pid] = len(os.listdir(f"/proc/{p.pid}/fd"))
            except OSError:
                pass
        return counts

    def _kill_all(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 5
        for p in self.procs:
            try:
                p.wait(max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
        self.procs.clear()

    def _scrape_primaries(self, alive: int) -> dict:
        """Scrape each primary subprocess's gRPC Telemetry service (the
        raw-bytes mirror any process can hit) before teardown, keyed by
        node index. The bound address is ephemeral, so it is read from the
        node's own machine-readable TELEMETRY_ADDR= boot line. Best-effort:
        a bench record is still valid without its scrape."""
        from narwhal_tpu.metrics import parse_exposition

        try:
            import grpc
        except ImportError:
            return {}
        scrapes: dict[str, dict] = {}
        for i in range(alive):
            try:
                with open(f"{self.base}/primary-{i}.log") as fh:
                    addr = parse_telemetry_addr(fh.read())
                if addr is None:
                    continue
                with grpc.insecure_channel(addr) as channel:
                    text = channel.unary_unary(
                        "/narwhal.Telemetry/Scrape",
                        request_serializer=lambda b: b,
                        response_deserializer=lambda b: b,
                    )(b"", timeout=10).decode()
                scrapes[f"primary-{i}"] = {
                    name: {
                        "type": entry["type"],
                        "samples": {
                            k: v
                            for k, v in entry["samples"].items()
                            if not k.startswith("_bucket")
                        },
                    }
                    for name, entry in parse_exposition(text).items()
                }
            except Exception as e:  # scrape is diagnostics, never the bench
                print(f"telemetry scrape of primary-{i} failed: {e}")
        return scrapes

    def _require_a_device_each(self, primaries: int) -> None:
        """A chip belongs to one process at a time, and every primary here
        is its own process: with a device backend, more primaries than
        accelerator devices means the first child takes the chip and the
        rest fail or hang until the boot timeout. The count comes from a
        throwaway child — this parent must never touch JAX itself, or IT
        would hold the chip. (A CPU platform named in JAX_PLATFORMS gives
        every child its own host devices, so nothing is shared.)"""
        from narwhal_tpu.tpu import cpu_platform_named  # jax-free import

        if "tpu" not in (self.bench.crypto_backend, self.bench.dag_backend):
            return
        if cpu_platform_named():
            return
        probe = subprocess.run(
            [sys.executable, "-c", "import jax; print(len(jax.devices()))"],
            capture_output=True, text=True, timeout=120,
        )
        devices = int(probe.stdout.split()[-1]) if probe.returncode == 0 else 0
        if primaries > devices:
            raise ConfigError(
                f"{primaries} primary processes with a tpu backend need a "
                f"device each, and this host has {devices}: one process owns "
                "a chip. Run the committee in one process instead "
                "(narwhal_tpu.cluster.Cluster; python3 -m chipbench)"
            )

    def run(self, debug: bool = False) -> LogParser:
        bench = self.bench
        alive = bench.nodes - bench.faults
        self._require_a_device_each(alive)
        committee, workers = self._generate_configs()
        keys = list(committee.authorities)
        common = [
            "--committee", f"{self.base}/committee.json",
            "--workers", f"{self.base}/workers.json",
            "--parameters", f"{self.base}/parameters.json",
        ]
        try:
            for i in range(alive):
                self._spawn(
                    ["run", "--keys", f"{self.base}/key-{i}.json", *common,
                     "--store", f"{self.base}/db-{i}", "primary",
                     "--consensus-protocol", bench.consensus_protocol,
                     "--crypto-backend", bench.crypto_backend,
                     "--dag-backend", bench.dag_backend,
                     "--dag-shards", str(bench.dag_shards)],
                    f"{self.base}/primary-{i}.log",
                )
                for wid in range(bench.workers):
                    self._spawn(
                        ["run", "--keys", f"{self.base}/key-{i}.json", *common,
                         "--store", f"{self.base}/db-{i}", "worker", "--id", str(wid)],
                        f"{self.base}/worker-{i}-{wid}.log",
                    )
            self._wait_for_boot(
                [f"{self.base}/primary-{i}.log" for i in range(alive)]
                + [
                    f"{self.base}/worker-{i}-{wid}.log"
                    for i in range(alive)
                    for wid in range(bench.workers)
                ]
            )
            # The children own the assigned ports now; free the parent's
            # placeholder fds so long sweeps don't creep toward the ulimit.
            release_all_ports()
            # One client per alive worker lane (local.py: rate share).
            lanes = [
                workers[keys[i]][wid].transactions
                for i in range(alive)
                for wid in range(bench.workers)
            ]
            share = max(1, bench.rate // len(lanes))
            for j, target in enumerate(lanes):
                self._spawn(
                    ["benchmark_client", "--target", target,
                     "--rate", str(share), "--size", str(bench.tx_size),
                     "--nodes", *lanes],
                    f"{self.base}/client-{j}.log",
                )
            time.sleep(bench.duration)
            # Scrape-then-kill: the telemetry surface is only reachable
            # while the fleet is alive (sweep.py embeds this in its rows).
            self.telemetry_scrapes = self._scrape_primaries(alive)
            self.child_fd_counts = self._sample_child_fds()
        finally:
            self._kill_all()
        return LogParser.process(
            self.base, faults=bench.faults, parameters=self.node_parameters
        )
