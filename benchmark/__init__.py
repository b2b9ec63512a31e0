"""The multi-process launcher on real sockets: this repo's `fab local` /
`fab remote` (reference design: /root/reference/benchmark/ fabfile tasks,
LocalBench, LogParser). The only way to run validators in processes of
their own. On a CPU it prints counts, never a speed: how fast the system
is is `python3 -m chipbench`'s to say (BENCHMARK.json, PERF_LEDGER.jsonl).

What stays here, and why:

- local:     boots one process per primary and worker plus clients on loopback.
- remote:    the same committee over SSH (`fab remote`), for a split deployment.
- logs:      the measurement plane both read: `Created`/`Committed` log lines.
- aggregate: folds repeated runs' parsed results into one row per setting.
- plot:      draws sweep's or aggregate's rows, one curve a file.
- sweep:     drives `local` across offered rates (`.bench/sweep.json`).
- liveness:  N in-process nodes on sockets or simnet; commit progress and
             control-plane bytes per round at committee sizes `local` cannot fork.
- __main__:  `python -m benchmark`, the CLI of `local`.
"""
