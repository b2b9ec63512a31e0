"""Clean for metric-naming: grammar-conforming names + the sanctioned
computed-name seam (metered_channel's f-string depth gauges)."""


def build(registry, role, name):
    ok_counter = registry.counter("worker_tx_received", "clients' transactions")
    ok_gauge = registry.gauge("node_backpressure_level", "admission level")
    ok_hist = registry.histogram("primary_propose_latency_seconds", "per stage")
    # Computed names are covered by their construction seam, not this rule.
    depth = registry.gauge(f"{role}_channel_{name}_depth", "channel depth")
    return ok_counter, ok_gauge, ok_hist, depth
