"""Process-wide kernel registry: one compile per (kernel, mesh shape).

Every jit entry point in tpu/ routes through here (enforced by the
narwhal-lint rule `no-untracked-jit`), for three reasons this repo paid
for separately before unifying them:

- **Compile dedupe.** Each `jax.jit(...)` call owns its own trace/compile
  cache, so two wrappers over the same kernel+mesh each pay the full
  XLA compile (verifier.py's `_sharded_kernels` and dag_kernels' per-mesh
  jits were separate caches that could still double-compile through
  independent construction paths). The registry is the single map
  (kernel, mesh shape) -> compiled wrapper; every verifier/engine over
  the same mesh gets the SAME object.
- **Compile-wall accounting.** The first dispatch of a (kernel, mesh
  shape, operand shapes) tuple is trace + XLA compile + one execute;
  steady-state dispatches are milliseconds. The registry times every
  first dispatch and exposes `compile_walls()` so the smoke/dryrun/bench
  artifacts can attribute a slow run to the exact compile that ate it.
- **Buffer donation.** The device-resident window kernels (`roll_window`,
  `place_batch`) update [W, N, N] tensors in place semantically; without
  donation XLA must keep both generations live and copy. Donation is a
  per-kernel property, declared once at registration.

- **No second trace of a persisted kernel.** The persistent compilation
  cache (tpu/__init__.enable_compilation_cache) makes the XLA compile a
  deserialization in every process after the first, but it is keyed by
  the lowered module, so each process still traced the Python body to
  find its entry: tens of seconds for `msm_accumulate_kernel`, on every
  start. A kernel decorated `persist=True` keeps a `jax.export` of itself
  beside that cache (`<cache dir>/kernel_artifacts/`), one file per
  (kernel, operand shapes, static arguments, device kind), under a key
  that also holds the jax and jaxlib versions and a digest of the source
  it was traced from. Its first dispatch at a shape loads the file
  and runs the exported program; the Python body is entered only where no
  file answers to the key (`TrackedKernel._load`). The process that writes
  the file dispatches through it too: the exported program has a compile
  cache key of its own, and that is the entry the next process asks for.
"""

from __future__ import annotations

import hashlib
import inspect
import json
import logging
import os
import re
import sys
import threading
import time
import types
from typing import Any, Callable, Sequence

from .. import tracing
from ..metrics import Counter

# Where the exports live, under the compile cache's directory: an artefact
# belongs to the executables compiled from it and goes when they go.
ARTIFACT_DIR = "kernel_artifacts"
_FORMAT = "narwhal-kernel-export/1"

# Process-wide like the registry; every node mounts it in its registry
# beside the verify service's series.
KERNEL_ARTIFACTS = Counter(
    "kernel_artifact_total",
    "First dispatches of a persisted kernel at a shape, by what its "
    "serialised export on disk gave (outcome=hit: loaded, the Python body "
    "never traced; miss: no file; stale: a file under another key, e.g. "
    "other source or jax version; unreadable: a file that is cut short or "
    "does not deserialise; all but hit trace, export and rewrite the file)",
    ("kernel", "outcome"),
)

logger = logging.getLogger("narwhal.tpu")

_LOCK = threading.Lock()
# kernel name -> TrackedKernel (the module-level, unsharded entry point)
_KERNELS: dict[str, "TrackedKernel"] = {}
# (kernel name, mesh key, spec signature) -> TrackedKernel (sharded wrapper)
_SHARDED: dict[tuple, "TrackedKernel"] = {}
# (kernel name, mesh desc, operand-shape signature) -> first-dispatch wall (s)
_WALLS: dict[tuple[str, str, str], float] = {}


def mesh_key(mesh) -> tuple:
    """Hashable identity of a mesh: devices + axis names + geometry."""
    if mesh is None:
        return ()
    return (
        tuple(mesh.devices.flat),
        tuple(mesh.axis_names),
        tuple(mesh.devices.shape),
    )


def mesh_desc(mesh) -> str:
    """Human/JSON-stable mesh shape label: '8:data', '4x2:data,auth',
    '1' for the unsharded single-device entry."""
    if mesh is None:
        return "1"
    dims = "x".join(str(d) for d in mesh.devices.shape)
    return f"{dims}:{','.join(mesh.axis_names)}"


def _shapes_sig(args: tuple, kwargs: dict) -> str:
    parts = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is None:
            parts.append(type(a).__name__)
        else:
            dtype = getattr(a, "dtype", "?")
            parts.append(f"{dtype}[{','.join(map(str, shape))}]")
    for k in sorted(kwargs):
        parts.append(f"{k}={kwargs[k]!r}")
    return ";".join(parts)


def source_digest(module) -> str:
    """sha256 over the source files a kernel of `module` is traced from:
    the module's own and, transitively, every module of this package whose
    code it reaches through a global (a module, or a function or class
    defined there)."""
    files: dict[str, str] = {}
    todo = [module.__name__]
    while todo:
        name = todo.pop()
        mod = sys.modules.get(name)
        ours = name == module.__name__ or (name + ".").startswith(__package__ + ".")
        if name in files or mod is None or not ours:
            continue
        files[name] = mod.__file__
        for value in vars(mod).values():
            dep = value.__name__ if isinstance(value, types.ModuleType) else getattr(value, "__module__", None)
            if isinstance(dep, str):
                todo.append(dep)
    digest = hashlib.sha256()
    for name in sorted(files):
        with open(files[name], "rb") as f:
            digest.update(name.encode() + b"\0" + f.read() + b"\0")
    return digest.hexdigest()


def artifact_dir() -> str:
    """`kernel_artifacts/` under the persistent compile cache's directory
    (what `JAX_COMPILATION_CACHE_DIR` names, else `tpu.DEFAULT_CACHE_DIR`)."""
    import jax

    from . import enable_compilation_cache

    enable_compilation_cache()
    return os.path.join(jax.config.jax_compilation_cache_dir, ARTIFACT_DIR)


def _artifact_path(key: dict) -> str:
    """One file per (kernel, operand shapes, statics, device): what else
    the key holds is in the file's head, so a file left by other source or
    another jax is found, counted `stale` and replaced, not left to pile up."""
    statics = ",".join(f"{n}={v}" for n, v in sorted(key["statics"].items()))
    stem = f"{key['kernel']}.{key['shapes']}.{statics}.{key['platform']}.{key['device_kind']}"
    return os.path.join(artifact_dir(), re.sub(r"[^A-Za-z0-9_.-]+", "_", stem) + ".export")


def _read_artifact(path: str, key: dict):
    """(the export the file holds under `key`, "hit"), else (None, why
    not). The file is one JSON line — the key, the blob's size and sha256 —
    then the blob: a file cut short or overwritten is seen before anything
    is handed to the deserialiser."""
    import jax

    try:
        with open(path, "rb") as f:
            head, blob = f.readline(), f.read()
    except FileNotFoundError:
        return None, "miss"
    try:
        meta = json.loads(head)
        if meta["key"] != key:
            return None, "stale"
        if len(blob) != meta["size"] or hashlib.sha256(blob).hexdigest() != meta["sha256"]:
            raise ValueError(f"{len(blob)} bytes after the head do not match it")
        return jax.export.deserialize(bytearray(blob)), "hit"
    except Exception as e:  # whatever a damaged file raises: trace instead
        logger.warning("kernel export %s is unreadable (%s: %s); tracing", path, type(e).__name__, e)
        return None, "unreadable"


def _write_artifact(path: str, key: dict, blob: bytes | bytearray) -> None:
    """Whole or not at all: written under a name of this thread's own and
    renamed over `path`, so processes racing to write one file (tier-1's
    workers share a cache directory) leave one whole file."""
    head = {"key": key, "size": len(blob), "sha256": hashlib.sha256(blob).hexdigest()}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
    with open(tmp, "wb") as f:
        f.write(json.dumps(head, sort_keys=True).encode() + b"\n" + blob)
    os.replace(tmp, path)


class TrackedKernel:
    """A jit-compiled kernel that self-reports its compile walls.

    Callable like the jit wrapper; `__wrapped__` is the original Python
    function (the sharded builders re-jit it with shardings), `lower(...)`
    passes through for ahead-of-need prewarm compiles."""

    def __init__(
        self, name: str, fn: Callable, jit_fn, mesh=None, persist: Sequence[str] | None = None
    ):
        self.name = name
        self.__wrapped__ = getattr(fn, "__wrapped__", fn)
        self.__name__ = name
        self.__doc__ = fn.__doc__
        self._jit = jit_fn
        self._mesh_desc = mesh_desc(mesh)
        # persist: None, or the kernel's static argument names. Then the
        # positional operands are arrays, statics go by keyword, and a
        # shape's dispatches run the exported program in `_programs`.
        self._persist = persist
        self._programs: dict[str, Callable] = {}
        self._load_lock = threading.Lock()

    def __call__(self, *args, **kwargs):
        key = (self.name, self._mesh_desc, _shapes_sig(args, kwargs))
        if key in _WALLS:
            return self._dispatch(key[2], args, kwargs)
        t0 = time.perf_counter()
        out = self._dispatch(key[2], args, kwargs)
        wall = time.perf_counter() - t0
        with _LOCK:
            # First dispatch of this (kernel, mesh, shapes): trace (or the
            # load of a persisted export) + XLA compile + one
            # (async-dispatched) execute. Keep the first observation — a
            # racing second dispatch just hit the cache.
            _WALLS.setdefault(key, wall)
        # The same, with its instant, in the process flight ring: a
        # reader counts the first dispatches that fell inside a window.
        tracing.flight("compile", self.name, key[2], time.monotonic(), wall)
        return out

    def _dispatch(self, sig: str, args: tuple, kwargs: dict):
        if self._persist is None:
            return self._jit(*args, **kwargs)
        program = self._programs.get(sig) or self._load(sig, args, kwargs)
        return program(*args)  # the statics in kwargs are part of the export

    def _load(self, sig: str, args: tuple, kwargs: dict) -> Callable:
        """The program a persisted kernel runs at `sig`: its export as the
        file under this key holds it, else traced now, exported and
        written there. Either way the dispatch goes through the exported
        program under a jit of the kernel's own name, so the executable
        this process compiles (or finds) is the one the next will ask the
        compile cache for, and a device trace names it as before."""
        with self._load_lock:
            program = self._programs.get(sig)
            if program is not None:
                return program
            import jax

            t0 = time.perf_counter()
            key = self._artifact_key(args, kwargs)
            path = _artifact_path(key)
            exported, outcome = _read_artifact(path, key)
            if exported is None:
                specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
                exported = jax.export.export(self._jit)(*specs, **kwargs)
                _write_artifact(path, key, exported.serialize())

            def program(*operands):
                return exported.call(*operands)

            program.__name__ = program.__qualname__ = self.name
            program = self._programs[sig] = jax.jit(program)
            seconds = time.perf_counter() - t0
        KERNEL_ARTIFACTS.labels(self.name, outcome).inc()
        tracing.flight("kernel_load", self.name, sig, outcome, time.monotonic(), seconds)
        logger.info("kernel %s %s: export %s (%.2fs) %s", self.name, sig, outcome, seconds, path)
        return program

    def _artifact_key(self, args: tuple, kwargs: dict) -> dict:
        """Everything the exported bytes depend on. A file under any other
        key is not loaded: a validator never verifies with a kernel its
        checkout does not contain."""
        import jax
        import jaxlib

        bound = inspect.signature(self.__wrapped__).bind(*args, **kwargs)
        bound.apply_defaults()
        device = jax.devices()[0]
        return {
            "format": _FORMAT,
            "kernel": self.name,
            "shapes": _shapes_sig(args, {}),
            "statics": {n: repr(bound.arguments[n]) for n in self._persist},
            "platform": device.platform,
            "device_kind": device.device_kind,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "x64": bool(jax.config.jax_enable_x64),
            "source": source_digest(sys.modules[self.__wrapped__.__module__]),
        }

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)


def tracked_jit(arg=None, *, name: str | None = None, persist: bool = False, **jit_kwargs):
    """`@tracked_jit` / `@tracked_jit(name=..., static_argnames=...,
    donate_argnums=...)`: the registry's replacement for a module-level
    `@jax.jit` in tpu/. Registers the kernel by name so sharded variants
    (`sharded(...)`) and the compile-wall report can find it.

    `persist=True` keeps a serialised export of the kernel beside the
    compile cache and loads it instead of tracing (module docstring). It is
    for a kernel whose trace is what a start waits for, called with arrays
    by position and its statics by keyword, on one device: the wrappers
    `sharded(...)` builds from its `__wrapped__` trace as before (an export
    under shardings is another artefact, and nothing measures one)."""

    def wrap(fn: Callable) -> TrackedKernel:
        import jax

        kname = name or fn.__name__
        statics = None
        if persist:
            if set(jit_kwargs) - {"static_argnames"}:
                raise TypeError(f"persist=True takes static_argnames only, not {sorted(jit_kwargs)}")
            statics = tuple(jit_kwargs.get("static_argnames", ()))
        kernel = TrackedKernel(kname, fn, jax.jit(fn, **jit_kwargs), persist=statics)
        with _LOCK:
            # Registration runs once at module import (decoration time),
            # never inside a trace — the decorator is what MAKES the jit
            # root, it is not reachable from compiled code.
            # lint: allow(jit-purity)
            _KERNELS[kname] = kernel
        return kernel

    if callable(arg):  # bare @tracked_jit
        return wrap(arg)
    return wrap


def sharded(
    kernel,
    mesh,
    in_specs: Sequence,
    out_specs,
    *,
    static_argnames: Sequence[str] = (),
    donate_argnums: Sequence[int] = (),
) -> TrackedKernel:
    """The process-wide mesh-sharded wrapper for `kernel` (a TrackedKernel
    or plain function): ONE jit per (kernel, mesh identity, spec set), so
    every verifier/engine over the same mesh shares one compiled program
    instead of each paying its own multi-minute compile.

    `in_specs`/`out_specs` are PartitionSpecs (or None for replicated);
    they are bound to `mesh` here so callers never hand-build
    NamedShardings."""
    name = getattr(kernel, "name", None) or getattr(kernel, "__name__", repr(kernel))
    key = (
        name,
        mesh_key(mesh),
        repr(tuple(in_specs)),
        repr(out_specs),
        tuple(static_argnames),
        tuple(donate_argnums),
    )
    with _LOCK:
        cached = _SHARDED.get(key)
    if cached is not None:
        return cached
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def bind(spec):
        # PartitionSpec subclasses tuple: test for it BEFORE recursing so a
        # P("data", None) leaf isn't mistaken for a tuple of specs.
        if spec is None or isinstance(spec, P):
            return NamedSharding(mesh, spec if spec is not None else P())
        return tuple(bind(s) for s in spec)

    fn = getattr(kernel, "__wrapped__", kernel)
    jit_kwargs: dict[str, Any] = {
        "in_shardings": tuple(bind(s) for s in in_specs),
        "out_shardings": bind(out_specs),
    }
    if static_argnames:
        jit_kwargs["static_argnames"] = tuple(static_argnames)
    if donate_argnums:
        jit_kwargs["donate_argnums"] = tuple(donate_argnums)
    wrapper = TrackedKernel(name, fn, jax.jit(fn, **jit_kwargs), mesh=mesh)
    with _LOCK:
        # First construction wins (two threads racing the same key must
        # end up dispatching through the same wrapper).
        return _SHARDED.setdefault(key, wrapper)


def get_kernel(name: str) -> TrackedKernel:
    return _KERNELS[name]


def kernel_names() -> list[str]:
    with _LOCK:
        return sorted(_KERNELS)


def sharded_entries() -> int:
    with _LOCK:
        return len(_SHARDED)


def compile_walls() -> list[dict]:
    """Snapshot of every first-dispatch wall so far, one row per (kernel,
    mesh shape, operand shapes) — the dryrun/bench artifacts embed this."""
    with _LOCK:
        items = sorted(_WALLS.items())
    return [
        {"kernel": k, "mesh": m, "shapes": s, "wall_s": round(w, 3)}
        for (k, m, s), w in items
    ]


def compile_walls_by_shape() -> dict[str, float]:
    """Aggregate walls per (kernel, mesh shape) — the satellite contract:
    'compile walls per (kernel, mesh shape)'. Shape-level detail stays
    available via compile_walls()."""
    agg: dict[str, float] = {}
    for row in compile_walls():
        key = f"{row['kernel']}@{row['mesh']}"
        agg[key] = round(agg.get(key, 0.0) + row["wall_s"], 3)
    return agg
