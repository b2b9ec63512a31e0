"""The control and the planted faults: each has to come out as not correct.

    python3 -m chipbench.faults --workload <cell> --seeds 3 --seconds 6

One process (one chip, one warm-up) drives, for each seed, a sound run and
then one run per fault, each at the cell's own load through the very entry
the benchmark times, with the fault planted underneath: where the device's
answer comes back to the verify stage, where the commit walk's masks come
back to the consensus engine, or in the channel a validator's executor
writes its results to. The benchmark's own runs never come here. The last
line is `{"ok": ...}`: true when every sound run was correct and every
faulty one was not.

  order_swapped   THE CONTROL. The guarantee broken: "every honest validator
                  executes the same transactions in the same order". The
                  last validator swaps the first two results of each batch.
  answer_altered  a result altered where it is produced: validator 1 flips
                  one bit in the first transaction of each batch.
  half_left_out   half of each batch left out on validator 2.
  replayed        validator 0 executes the first transaction of each batch
                  twice (it executes what no commit carries).
  forgery_accepted  the verify stage takes every device answer as "valid"
                  (the host epilogue of the msm kernels is skipped), and
                  validator 1 signs its headers and votes with an altered
                  `s`: forged signatures reach the stores on every validator.
                  All-valid traffic alone cannot tell such a verifier from a
                  sound one; this is what does. It stays planted for the
                  rest of the process (the shared verify service still holds
                  forged signatures when a committee is down), so its runs
                  come last.
  commit_left_out validator 2 drops one certificate from every commit mask
                  the device hands back: its recorded sequence leaves
                  Bullshark's.
"""

from __future__ import annotations

import time

_T_PROC = time.monotonic()

import argparse
import json
import os
import sys


def _plant(validator: int, mangle):
    def fault(cluster) -> None:
        v = validator % len(cluster.authorities)
        ch = cluster.authorities[v].primary.tx_execution_output
        inner = ch.send_many

        async def send_many(items):
            await inner(mangle(list(items)))

        ch.send_many = send_many

    return fault


def _swap(items):
    if len(items) >= 2:
        items[0], items[1] = items[1], items[0]
    return items


def _alter(items):
    if items:
        outcome, tx = items[0]
        tx = bytes(tx)
        items[0] = (outcome, tx[:20] + bytes([tx[20] ^ 1]) + tx[21:])
    return items


class _Forger:
    """A signature service whose every signature has one bit of `s` altered
    (R and the key still decompress, so only the group equation fails)."""

    def __init__(self, inner):
        self._inner = inner
        self.public = inner.public

    def sign(self, digest: bytes) -> bytes:
        # Not KeyPair.sign: that seeds the process-wide verified-signature cache.
        sig = self._inner._keypair._private.sign(digest)
        return sig[:40] + bytes([sig[40] ^ 1]) + sig[41:]

    async def request_signature(self, digest: bytes) -> bytes:
        return self.sign(digest)


def _forgery_accepted(cluster) -> None:
    from narwhal_tpu.tpu import verifier

    verifier.msm_epilogue_check = lambda *a, **k: True
    primary = cluster.authorities[1 % len(cluster.authorities)].primary.primary
    forger = _Forger(primary.core.signature_service)
    primary.core.signature_service = primary.proposer.signature_service = forger


def _commit_left_out(cluster) -> None:
    import numpy as np

    protocol = cluster.authorities[2 % len(cluster.authorities)].primary.consensus.protocol
    inner = protocol._materialize

    def _materialize(state, consensus_index, masks, K):
        masks = np.array(masks, copy=True)
        for k in range(K):
            hits = np.argwhere(masks[k])
            if len(hits) >= 2:
                masks[k][tuple(hits[0])] = 0  # the oldest certificate of this leader's history
        return inner(state, consensus_index, masks, K)

    protocol._materialize = _materialize


FAULTS = {
    "order_swapped": _plant(-1, _swap),
    "answer_altered": _plant(1, _alter),
    "half_left_out": _plant(2, lambda items: items[::2]),
    "replayed": _plant(0, lambda items: items[:1] + items),
    "forgery_accepted": _forgery_accepted,
    "commit_left_out": _commit_left_out,
}
CONTROL = "order_swapped"
LAST = "forgery_accepted"


def main(argv: list[str] | None = None) -> int:
    from . import run as runner
    from .__main__ import parse

    ap = argparse.ArgumentParser(prog="python3 -m chipbench.faults")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--faults", default=",".join(FAULTS))
    own = ap.parse_args(argv)
    args = parse(["--workload", own.workload, "--seed", "0", "--seconds", str(own.seconds), "--trace", "0"])
    ctx = runner.prepare(args, _T_PROC)
    rate = runner.cell_rate(ctx, args)
    ok = True
    names = sorted(own.faults.split(","), key=lambda name: name == LAST)  # stable: LAST goes last
    for name in ["sound"] + names:
        for k in range(own.seeds):
            args.seed = own.first_seed + k
            rec = runner.measure(ctx, args, rate, fault=FAULTS.get(name))
            wanted = name == "sound"
            ok = ok and rec["correct"] == wanted
            print(json.dumps({
                "fault": name, "seed": args.seed, "correct": rec["correct"],
                "as_wanted": rec["correct"] == wanted, "attempted": rec["attempted"],
                "failed": rec["failed"], "checks": rec["checks"],
            }), flush=True)
    runner.stop_device_plane()
    print(json.dumps({"ok": ok, "workload": own.workload, "rate_tx_per_s": rate,
                      "control": CONTROL, "device": ctx.device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
