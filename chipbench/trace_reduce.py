"""From a profiler trace (`.xplane.pb`) to device busy time, kernel times,
the heaviest device operations and the longest idle gaps. The one reduction
every PR's numbers go through; checked on a recorded trace in the tests.

A device plane is named `/device:TPU:<n>`. Its `XLA Ops` line holds one
event per executed HLO operation and its `XLA Modules` line one per
executed program (`jit_<kernel>(<fingerprint>)`). Busy time is the union of
the operation intervals; a kernel's time is the sum of its programs'
durations.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def module_name(event_name: str) -> str:
    """`jit_msm_accumulate_kernel(1234)` -> `msm_accumulate_kernel`."""
    name = re.sub(r"\(.*\)$", "", event_name)
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An operation's event may be named by its whole HLO text; keep the
    result's name and the opcode: `%while.38 = (...) while(...)` -> `%while.38 while`."""
    if " = " not in event_name:
        return event_name[:96]
    result, rest = event_name.split(" = ", 1)
    m = re.search(r"\)?\s*([a-z][a-z0-9_\-]*)\(", rest)
    return f"{result} {m.group(1)}"[:96] if m else result[:96]


def reduce_planes(planes, platform: str = "TPU") -> dict | None:
    """`planes`: iterable of objects with `.name` and `.lines`; a line has
    `.name` and `.events`; an event `.name`, `.start_ns`, `.duration_ns`.
    Returns None when no device plane holds an operation."""
    prefix = f"/device:{platform}:"
    per_device = []
    lines_seen: dict[str, int] = {}
    for plane in planes:
        if not plane.name.startswith(prefix):
            continue
        ops, modules = [], []
        for line in plane.lines:
            events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
            lines_seen[f"{plane.name}|{line.name}"] = len(events)
            if line.name == OPS_LINE:
                ops = events
            elif line.name == MODULES_LINE:
                modules = events
        if not ops:
            ops = modules  # a backend that records programs only
        if ops:
            per_device.append((plane.name, ops, modules))
    if not per_device:
        return None

    busy_ns = 0.0
    op_seconds: dict[str, float] = {}
    kernels: dict[str, dict] = {}
    gaps: list[tuple[float, str]] = []
    for _, ops, modules in per_device:
        merged = _union([(s, s + d) for _, s, d in ops])
        busy_ns += sum(e - s for s, e in merged)
        for name, _, d in ops:
            name = op_name(name)
            op_seconds[name] = op_seconds.get(name, 0.0) + d / 1e9
        for name, _, d in modules:
            k = kernels.setdefault(module_name(name), {"seconds": 0.0, "events": 0})
            k["seconds"] += d / 1e9
            k["events"] += 1
        # A gap is named after the program that ran before it.
        starts = sorted((s, module_name(n)) for n, s, _ in modules)
        at = 0
        for (_, end), (nxt, _) in zip(merged, merged[1:]):
            while at + 1 < len(starts) and starts[at + 1][0] <= end:
                at += 1
            before = starts[at][1] if starts and starts[at][0] <= end else "unknown"
            gaps.append(((nxt - end) / 1e9, f"after:{before}"))
    n = len(per_device)
    gaps.sort(reverse=True)
    return {
        "devices": n,
        "busy_s": busy_ns / 1e9 / n,
        "kernels": kernels,
        "device_ops": [
            [name, s] for name, s in sorted(op_seconds.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": [[name, s] for s, name in gaps[:10]],
        "lines": lines_seen,
    }


def reduce_file(path: str, platform: str = "TPU") -> dict | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, platform)
