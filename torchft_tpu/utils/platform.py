"""Platform selection helpers: which device answered, and where compiled
programs are kept."""

from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Mapping, Optional

__all__ = [
    "cpu_by_name",
    "device_round_trip",
    "enable_compile_cache",
    "on_tpu",
    "require_tpu",
]

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# Fixed and inside the checkout (git-ignored): the directory is part of the
# cache key, so a path that moves between runs (temp dir, pid, time) never
# hits.
_CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cpu_by_name(env: Optional[Mapping[str, str]] = None) -> bool:
    """True where the caller selected the CPU by name: ``JAX_PLATFORMS=cpu``
    in ``env`` (default this process's environment). The one rule for it:
    nothing in the repo picks the CPU in code, and the launcher hands out
    no chips under it. Touches no backend."""
    platforms = (os.environ if env is None else env).get("JAX_PLATFORMS", "")
    return platforms.strip().lower() == "cpu"


def on_tpu() -> bool:
    """True when the default device's PLATFORM is TPU.

    Only the device platform says whether Mosaic can compile Pallas kernels
    — every TPU-vs-elsewhere dispatch must use this check, held here once."""
    import jax

    return jax.devices()[0].platform == "tpu"


def require_tpu() -> Any:
    """The default device when it is a TPU; exits non-zero otherwise.

    For chip scripts (``chip_smoke.py``, the kernel benches and sweeps):
    with no chip JAX answers on the CPU, and a measurement taken there must
    never be printed under a device's name. Runs
    in-process — the chip belongs to one process at a time, so a probe in
    a child would take it from the caller."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0] is {dev.platform} ({dev.device_kind}); "
            "this entry point measures on the chip and does not fall back"
        )
    return dev


def device_round_trip() -> bool:
    """One compile→execute→fetch round trip on the default device, and
    whether the answer was right. In-process by design (see
    :func:`require_tpu`): the doctor's device check and the quarantine
    gate's self-probe both run where the device is, or will be, held."""
    import jax
    import jax.numpy as jnp

    x = jnp.ones((128, 128), jnp.bfloat16)
    return float(jax.jit(lambda a: a @ a)(x)[0, 0]) == 128.0


def enable_compile_cache() -> str:
    """Turns on JAX's persistent compilation cache; returns its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and no
    other directory is set in code. Where it is not, the cache lives at a
    fixed git-ignored path inside the checkout, exported to the environment
    so child processes inherit the same directory."""
    cache_dir = os.environ.get(COMPILE_CACHE_ENV)
    if not cache_dir:
        import jax

        cache_dir = str(_CHECKOUT_CACHE_DIR)
        os.environ[COMPILE_CACHE_ENV] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
