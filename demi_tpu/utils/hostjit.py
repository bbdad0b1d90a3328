"""host_jit: jit a function pinned to the host CPU backend.

The host oracle runs DSL handlers eagerly, one delivery at a time; compiling
them for CPU keeps the oracle fast and — crucially — keeps it off the TPU so
oracle runs never serialize against device-tier sweeps.
"""

from __future__ import annotations

import functools
import os
from typing import Callable


@functools.lru_cache(maxsize=1)
def _cpu_device():
    import jax

    try:
        return jax.local_devices(backend="cpu")[0]
    except RuntimeError as exc:
        raise RuntimeError(
            "demi_tpu's host oracle (fuzz, the device->host lift, MCS "
            "verification) runs on JAX's CPU backend beside the "
            "accelerator: leave JAX_PLATFORMS unset or set "
            "JAX_PLATFORMS=tpu,cpu (got JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r})"
        ) from exc


def host_jit(fn: Callable) -> Callable:
    import jax

    jitted = jax.jit(fn)

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.default_device(_cpu_device()):
            return jitted(*args, **kwargs)

    return wrapper
