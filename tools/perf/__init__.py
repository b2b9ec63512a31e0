"""The perf observatory: measurement as a first-class subsystem.

Every perf claim in this repo must survive the host-capacity-swing rule
(ROADMAP: this 1-core container varies 10-20x day to day). The modules
here turn the same-hour interleaved-A/B ritual each PR used to hand-roll
into shared, tested tooling:

- calibrate:      a pinned CPU-capacity probe + host-context snapshot,
                  run before/after every bench leg so records carry the
                  capacity the numbers were measured under;
- ledger:         the commit-keyed perf ledger — one schema-validated
                  JSONL record per bench/A/B run, appended by every
                  entry point under benchmark/, gated in tier-1;
- simnet_profile: per-component self-time attribution over a simnet
                  scenario's virtual-clock hot path (ROADMAP item 3's
                  10x target, named).

The A/B driver itself lives in benchmark/ab.py and composes these.
"""

from . import calibrate, ledger  # noqa: F401
