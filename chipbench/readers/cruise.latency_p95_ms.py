"""95th percentile of due -> executed on the last validator, below the knee; its runs spread too widely for a bound (PERF.md section 2)."""


def read(obs):
    return obs["latency"].get("p95")
