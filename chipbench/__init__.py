"""chipbench: the benchmark of the served path on the chip (see PERF.md)."""
