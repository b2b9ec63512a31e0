"""`python3 -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>`

The last line of standard output is the run's one JSON object. Whatever the
load did (sheds, a backlog, transactions not executed by the end of the
drain) is in its `failed` count and its metrics, and the process exits 0.
Only a harness fault (no chip, the committee cannot boot, the warm-up is
refused) ends a run otherwise: its traceback goes to standard error and to
`chipbench/out/last_failure.txt`, its last line to standard output, no
result is printed, and the exit code is 2 for "no chip" and 1 for the rest.
"""

from __future__ import annotations

import time

_T_PROC = time.monotonic()  # set-up is counted from here

import argparse
import json
import os
import sys
import traceback


def parse(argv: list[str] | None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python3 -m chipbench")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    # A CPU rehearsal (tests only) names itself: JAX_PLATFORMS=cpu and a JSON
    # object of the sizes to cut, e.g. {"verify_bucket": 16, "validators": 4}.
    raw = os.environ.get("CHIPBENCH_REHEARSAL", "")
    args.overrides = json.loads(raw) if raw else {}
    args.fault = None
    place_compile_cache(bool(args.overrides))
    return args


def place_compile_cache(rehearsal: bool) -> None:
    """JAX's persistent compile cache, before anything imports JAX: the
    directory the environment names, else a fixed one of the benchmark's own
    inside the checkout, one per platform. The program's default
    (`<checkout>/.jax_cache`) is also what the repo's CPU tests write, and
    chip runs that shared it with XLA:CPU entries compiled on every start
    (PERF.md section 5)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        here = os.path.dirname(os.path.abspath(__file__))
        own = os.path.join(here, "out", "jax_cache." + ("cpu" if rehearsal else "device"))
        os.makedirs(own, exist_ok=True)
        os.environ["JAX_COMPILATION_CACHE_DIR"] = own


def main(argv: list[str] | None = None, t_proc: float = _T_PROC) -> int:
    args = parse(argv)
    from . import run as runner

    try:
        result = runner.run(args, t_proc)
    except BaseException as e:
        text = traceback.format_exc()
        sys.stderr.write(text)
        try:
            os.makedirs(runner.OUT, exist_ok=True)
            with open(os.path.join(runner.OUT, "last_failure.txt"), "w") as f:
                f.write(f"argv: {sys.argv[1:] if argv is None else argv}\n{text}")
        except OSError:
            pass
        print(f"chipbench: harness fault, no result: {text.strip().splitlines()[-1]}", flush=True)
        return 2 if isinstance(e, runner.NoChip) else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # Every thread the run started is joined and the committee is shut down
    # by now; skip interpreter finalisation, where a thread parked inside XLA
    # can abort the process after the result line.
    os._exit(code)
