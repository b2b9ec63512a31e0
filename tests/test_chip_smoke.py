"""Contracts of the chip bring-up (ISSUE 21), as far as a CPU can hold them:
where the compile cache goes, and what `chip_smoke.py` does without a chip.
"""

import json
import os
import re
import subprocess
import sys

import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PRINT_CACHE_DIR = (
    "import narwhal_tpu.tpu.ed25519, jax; "
    "print(jax.config.jax_compilation_cache_dir)"
)


def _cache_dir_of_a_fresh_process(env_value):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c", _PRINT_CACHE_DIR],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_is_placed_from_outside(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache is exactly that directory
    (no platform subdirectory, no private variable on top). Unset: the
    fixed <checkout>/.jax_cache."""
    placed = str(tmp_path / "placed from outside")
    assert _cache_dir_of_a_fresh_process(placed) == placed
    assert _cache_dir_of_a_fresh_process(None) == os.path.join(REPO, ".jax_cache")


def test_no_code_sets_the_cache_dir_when_the_variable_is_present():
    """One place in the tree may set jax_compilation_cache_dir, and only
    under `if not os.environ.get("JAX_COMPILATION_CACHE_DIR")`."""
    setters = []
    roots = [os.path.join(REPO, d) for d in ("narwhal_tpu", "benchmark")]
    files = [os.path.join(REPO, f) for f in os.listdir(REPO) if f.endswith(".py")]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            files += [os.path.join(dirpath, n) for n in names if n.endswith(".py")]
    for path in files:
        with open(path) as f:
            src = f.read()
        assert "NARWHAL_JAX_CACHE_DIR" not in src, path
        for m in re.finditer(r"jax_compilation_cache_dir[\"']\s*,", src):
            setters.append((os.path.relpath(path, REPO), src[: m.start()]))
    assert [p for p, _ in setters] == [os.path.join("narwhal_tpu", "tpu", "__init__.py")]
    assert 'if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):' in setters[0][1]


def _report_and_verdict(stdout):
    """The last two stdout lines: the report, then the verdict — which is
    exactly {"ok", "device": {"platform", "kind", "count"}}, the shape the
    driver parses, and agrees with the report."""
    lines = stdout.strip().splitlines()
    if not lines or not lines[-1].startswith("{"):
        return None
    summary, verdict = json.loads(lines[-2]), json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"}
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert isinstance(verdict["ok"], bool)
    assert isinstance(verdict["device"]["platform"], str)
    assert isinstance(verdict["device"]["kind"], str)
    assert type(verdict["device"]["count"]) is int
    assert (summary["ok"], summary["device"]) == (verdict["ok"], verdict["device"])
    return summary


def _last_json(capsys):
    return _report_and_verdict(capsys.readouterr().out)


def test_smoke_refuses_to_run_without_a_tpu(capsys):
    """No TPU and no --rehearsal: non-zero before any phase, no result."""
    assert chip_smoke.main([]) != 0
    assert _last_json(capsys) is None


def test_rehearsal_runs_its_phases_and_never_reports_a_chip_pass(capsys, monkeypatch):
    """The explicit CPU rehearsal (here: the commit-walk phase, the one
    whose kernels tier-1 can afford to compile) says what it is, passes,
    and still does not say "ok"; a phase made to fail fails the process."""
    assert chip_smoke.main(["--rehearsal", "--phases", "walk_width"]) == 0
    summary = _last_json(capsys)
    assert summary["rehearsal"] is True and summary["ok"] is False
    assert summary["device"]["platform"] == "cpu"
    assert summary["phases"]["walk_width"]["passed"] is True
    assert summary["phases_passed"] and summary["clean_shutdown"]
    assert summary["claim"] is None

    def broken(ctx):
        raise RuntimeError("check failed: injected")

    monkeypatch.setitem(chip_smoke.RUNNERS, "walk_width", broken)
    assert chip_smoke.main(["--rehearsal", "--phases", "walk_width"]) != 0
    summary = _last_json(capsys)
    assert summary["ok"] is False and summary["phases_passed"] is False
    assert summary["phases"]["walk_width"]["passed"] is False


@pytest.mark.slow  # compiles the ed25519 kernels on XLA:CPU: minutes
def test_full_rehearsal_in_its_own_process():
    """All three phases at rehearsal sizes, the way an operator runs it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("NARWHAL_TPU_PREWARM", None)  # the defaults a node runs
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py", "--rehearsal"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=1800,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    summary = _report_and_verdict(proc.stdout)
    assert summary["ok"] is False and summary["rehearsal"] is True
    assert all(summary["phases"][p]["passed"] for p in chip_smoke.PHASES)
    assert summary["detours_on_valid_input"] == 0
