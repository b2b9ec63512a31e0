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

The persistent compilation cache (tpu/__init__.enable_compilation_cache)
composes with this: the registry guarantees one compile per process, the
cache makes that compile a deserialization in every process after the
first.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Sequence

from .. import tracing

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


class TrackedKernel:
    """A jit-compiled kernel that self-reports its compile walls.

    Callable like the jit wrapper; `__wrapped__` is the original Python
    function (the sharded builders re-jit it with shardings), `lower(...)`
    passes through for ahead-of-need prewarm compiles."""

    def __init__(self, name: str, fn: Callable, jit_fn, mesh=None):
        self.name = name
        self.__wrapped__ = getattr(fn, "__wrapped__", fn)
        self.__name__ = name
        self.__doc__ = fn.__doc__
        self._jit = jit_fn
        self._mesh_desc = mesh_desc(mesh)

    def __call__(self, *args, **kwargs):
        key = (self.name, self._mesh_desc, _shapes_sig(args, kwargs))
        if key in _WALLS:
            return self._jit(*args, **kwargs)
        t0 = time.perf_counter()
        out = self._jit(*args, **kwargs)
        wall = time.perf_counter() - t0
        with _LOCK:
            # First dispatch of this (kernel, mesh, shapes): trace + XLA
            # compile + one (async-dispatched) execute. Keep the first
            # observation — a racing second dispatch just hit the cache.
            _WALLS.setdefault(key, wall)
        # The same, with its instant, in the process flight ring: a
        # reader counts the first dispatches that fell inside a window.
        tracing.flight("compile", self.name, key[2], time.monotonic(), wall)
        return out

    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)


def tracked_jit(arg=None, *, name: str | None = None, **jit_kwargs):
    """`@tracked_jit` / `@tracked_jit(name=..., static_argnames=...,
    donate_argnums=...)`: the registry's replacement for a module-level
    `@jax.jit` in tpu/. Registers the kernel by name so sharded variants
    (`sharded(...)`) and the compile-wall report can find it."""

    def wrap(fn: Callable) -> TrackedKernel:
        import jax

        kname = name or fn.__name__
        kernel = TrackedKernel(kname, fn, jax.jit(fn, **jit_kwargs))
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
