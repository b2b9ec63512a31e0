"""A persisted kernel small enough to trace and compile in a blink, for
tier-1's tests of the export-beside-the-compile-cache mechanism
(tests/test_kernel_artifacts.py, tests/test_kernel_registry.py). What the
registry does with it is what it does with `msm_accumulate_kernel`, whose
own trace takes a quarter of a minute on XLA:CPU and its compile a minute."""

from __future__ import annotations

import jax.numpy as jnp

from narwhal_tpu.tpu.kernel_registry import tracked_jit

# One entry per time the Python body ran, i.e. per trace.
TRACES: list[tuple] = []


@tracked_jit(static_argnames=("scale",), persist=True)
def tiny_persisted_kernel(x, scale=3):
    TRACES.append(tuple(x.shape))
    return (x.astype(jnp.int32) * scale).sum(axis=1)
