"""Operator's readers beside the benchmark (`chipbench/` owns the record).

- flight_profile: the process flight ring and the profiler's `narwhal/*`
                  marks laid on one clock; a hand profile on the chip.
- simnet_profile: which component a simnet scenario's CPU time goes to
                  (shares of self time and fabric counts, never a speed).
"""
