"""The one traffic generator: a mix is a data file, `traffic/<mix>.json`.

Shape from the reference's `benchmark_client`: one open-loop client per
worker lane sends a burst every `tick_ms` of fixed-size transactions, each
carrying its id, on schedule whether or not the last burst was answered.

Keys of a mix:
  rate_share_of_knee  offered rate as a share of the configuration's knee
  tick_ms             burst period per lane
  warm_s              ramp at the cell's rate before the window (set-up)
  drain_s             how long after the window a transaction may still
                      execute before it counts as failed

The seed draws the payload bytes and the order in which the lanes fire
inside a tick; every seed offers the same sizes at the same instants.
"""

from __future__ import annotations

import json
import os
import random
import struct
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
_U32 = struct.Struct("<I")
ID_BYTES = 9  # 0x00 marker + u64 id, the reference client's sample format


def load_mix(name: str) -> dict:
    path = os.path.join(HERE, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    for key in ("rate_share_of_knee", "tick_ms", "drain_s"):
        if not isinstance(mix.get(key), (int, float)) or mix[key] <= 0:
            raise ValueError(f"{path}: {key} must be a positive number")
    return mix


@dataclass
class Burst:
    due: float  # seconds after the window opens
    lane: int
    first_id: int
    count: int
    raw: bytes  # wire body of the submission: u32 count | (u32 len | tx)*


def tx_bytes(seed: int, tx_id: int, size: int, noise: bytes) -> bytes:
    """Transaction `tx_id` of a seed: marker, id, then a slice of the seed's
    noise that moves with the id, so no two transactions are equal."""
    at = (tx_id * 31) % (len(noise) - size)
    return b"\x00" + tx_id.to_bytes(8, "big") + noise[at : at + size - ID_BYTES]


def noise_of(seed: int, size: int) -> bytes:
    return random.Random(f"chipbench-noise:{seed}").randbytes((1 << 16) + size)


def schedule(mix: dict, rate: float, lanes: int, seconds: float, seed: int,
             tx_size: int) -> tuple[list[Burst], list]:
    """Every burst of `seconds`, in due order, and the transactions by id
    (`txs[0]` is None; ids start at 1). Counts per tick follow an
    error-diffusing accumulator per lane, so any rate is met exactly."""
    tick = mix["tick_ms"] / 1000.0
    ticks = int(round(seconds / tick))
    rng = random.Random(f"chipbench-order:{seed}")
    noise = noise_of(seed, tx_size)
    frame = _U32.pack(tx_size)
    owed = [0.0] * lanes
    bursts: list[Burst] = []
    txs: list = [None]
    next_id = 1
    for k in range(ticks):
        order = list(range(lanes))
        rng.shuffle(order)
        for slot, lane in enumerate(order):
            owed[lane] += rate * tick / lanes
            count = int(owed[lane])
            if count <= 0:
                continue
            owed[lane] -= count
            parts = [_U32.pack(count)]
            for i in range(count):
                tx = tx_bytes(seed, next_id + i, tx_size, noise)
                txs.append(tx)
                parts.append(frame)
                parts.append(tx)
            bursts.append(
                Burst(k * tick + slot * tick / lanes, lane, next_id, count, b"".join(parts))
            )
            next_id += count
    bursts.sort(key=lambda b: b.due)
    return bursts, txs
