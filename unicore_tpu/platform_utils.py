"""Platform helpers: the one TPU predicate, the one explicit CPU switch and
the one compile-cache resolver.

JAX picks the TPU by itself when one is attached.  Nothing in this tree
selects the CPU unasked: ``UNICORE_TPU_PLATFORM=cpu`` (with
``UNICORE_TPU_CPU_DEVICES`` virtual devices) is the explicit switch that
tests, examples and CI use, and it must be applied before the backend
initializes.
"""

import os
from typing import Optional

#: the fixed in-checkout compile cache (listed in .gitignore).  The path is
#: part of JAX's cache key, so it never carries a temporary name, a pid or
#: a time: a directory that moves never hits.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU — THE predicate every
    kernel gate, PRNG choice and donation default in this tree reads.
    Initializes the backend: never call it from a process that must stay
    off the chip (the elastic supervisor, ``chip_smoke.py``'s parent)."""
    import jax

    return jax.default_backend() == "tpu"


def describe_devices() -> dict:
    """``{"platform", "kind", "count"}`` exactly as ``jax.devices()``
    reports them — what every entry point logs at start-up (a ``DEVICES
    {json}`` line) and what ``chip_smoke.py`` relays.  Initializes the
    backend."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def force_host_cpu(n_devices: int = 8) -> None:
    """Force the cpu platform with n virtual devices.  Must run before any
    jax backend use: a failed update raises, so a run never proceeds on
    whatever platform happened to come up."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def force_host_cpu_from_env(default_devices: int = 8) -> bool:
    """Apply the standard CPU-platform override when the operator set
    ``UNICORE_TPU_PLATFORM=cpu`` (device count from
    ``UNICORE_TPU_CPU_DEVICES``, else ``default_devices``).  One shared
    implementation for every entry point (CLIs, example scripts) — must run BEFORE any jax backend use.  Returns True when the
    override engaged."""
    if os.environ.get("UNICORE_TPU_PLATFORM", "").lower() != "cpu":
        return False
    force_host_cpu(
        int(os.environ.get("UNICORE_TPU_CPU_DEVICES", str(default_devices)))
    )
    return True


def configure_compilation_cache(flag_dir: Optional[str] = None) -> Optional[str]:
    """Place JAX's persistent compilation cache; returns the directory set
    in code, or None when none was.

    1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
       the program sets NO directory in code (whoever runs the program —
       a driver, a pod launcher — decides where the cache lives).
    2. else ``flag_dir`` (``--jax-compilation-cache-dir``) when given;
    3. else, unless an embedding program already configured one,
       :data:`DEFAULT_COMPILATION_CACHE_DIR` inside the checkout.

    Every program is cached, however quick its compile: a restart or the
    next process of the same command reloads instead of recompiling.
    """
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    if not flag_dir:
        if jax.config.jax_compilation_cache_dir:
            return None
        flag_dir = DEFAULT_COMPILATION_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", flag_dir)
    return flag_dir
