"""TPU offload kernels (crypto + DAG) and their host wrappers.

The kernel modules (ed25519, dag_kernels) call `enable_compilation_cache`
when THEY import — the package itself stays jax-free so that pure-host
paths (the `pool` crypto backend, config/CLI imports) never pay the
multi-second jax import.

Compile cache contract: where `JAX_COMPILATION_CACHE_DIR` is set, JAX
itself reads it and this package sets no directory in code — the cache is
exactly where the operator put it. Where it is not set, the cache is the
fixed `<checkout>/.jax_cache` (git-ignored): the path is part of the
cache key's environment, so it is never a temp name, pid or time. The big
kernels (the per-item ed25519 Straus walk, the batch MSM accumulate, the
chain_commit scan) take tens of seconds to minutes to compile, and every
process (node, smoke, pytest) should pay that once per machine, not once
per run. The cache holds executables, keyed by the lowered module: a jit's
Python TRACE is paid again in every process, except by a kernel decorated
`@tracked_jit(persist=True)` (`msm_accumulate_kernel`, the one a node's
start waits for), whose serialised `jax.export` lives in
`<cache dir>/kernel_artifacts/` and is loaded instead
(kernel_registry.py has the key and the rules).
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger("narwhal.tpu")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_cache_enabled = False


def enable_compilation_cache() -> None:
    """Idempotent; requires jax to be importable (callers import it).
    Failures raise: a broken cache must not silently become no cache."""
    global _cache_enabled
    if _cache_enabled:
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax

        os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    _cache_enabled = True


def cpu_platform_named() -> bool:
    """Whether the caller asked for the CPU platform by name
    (`JAX_PLATFORMS` lists cpu): the tests' and the rehearsal's case."""
    return "cpu" in os.environ.get("JAX_PLATFORMS", "").lower().split(",")


def device_mesh(shards: int, axis: str, flag: str, devices=None):
    """A 1-axis mesh named `axis` over the first `shards` of `devices`
    (default `jax.devices()`). Asking for more shards than there are
    devices is a ConfigError named after `flag` — there is no quiet move
    to virtual CPU devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from ..config import ConfigError

    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < shards:
        raise ConfigError(
            f"{flag} {shards} exceeds the {len(devs)} "
            f"{'pinned' if devices is not None else devs[0].platform} devices"
        )
    return Mesh(np.array(devs[:shards]), (axis,))


def require_device_platform(backend_flag: str) -> None:
    """Boot-time check for a node asked for a device backend: log the
    platform JAX landed on, and refuse a CPU platform unless
    `JAX_PLATFORMS` explicitly names cpu (the tests' and the rehearsal's
    case). With libtpu installed and no chip, JAX itself falls back to
    CPU, and `--crypto-backend tpu` would then quietly be XLA:CPU."""
    import jax

    from ..config import ConfigError

    dev = jax.devices()[0]
    logger.info(
        "%s tpu: platform=%s device_kind=%s devices=%d",
        backend_flag, dev.platform, dev.device_kind, len(jax.devices()),
    )
    if dev.platform == "cpu" and not cpu_platform_named():
        raise ConfigError(
            f"{backend_flag} tpu was requested but JAX found no accelerator "
            "(platform=cpu); refusing to serve from XLA:CPU. Set "
            "JAX_PLATFORMS=cpu explicitly for a CPU rehearsal."
        )
