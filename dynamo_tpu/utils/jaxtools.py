"""JAX platform selection, the compile-cache rule, and the device report.

Written for the installed JAX (0.9.x): sharded code calls
``jax.shard_map`` / ``jax.lax.pcast`` directly.

Platform: JAX picks the TPU when one is attached and — silently — the
CPU when it cannot initialise one. ``DYN_JAX_PLATFORM`` (e.g. "cpu")
pins the platform for dev runs and control-plane children;
``DYN_JAX_CPU_DEVICES`` asks the CPU backend for that many virtual
devices (sharding rehearsals). ``describe_devices`` is what the engine
logs and what ``chip_smoke.py`` checks, so a run that fell back to the
CPU says so.

Compile cache — ONE rule for the engine, the benchmark, the smoke and
the tests: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no code sets another directory; where it is not, the cache
is ``<checkout>/.jax_cache`` (fixed: the path is part of the cache
key, so a directory that moves never hits). Either way the choice is
exported to the environment, so every child process lands on the same
directory. ``DYN_COMPILE_CACHE=0`` turns the cache off, also where
``JAX_COMPILATION_CACHE_DIR`` is set (``jax_enable_compilation_cache``).
"""

from __future__ import annotations

import os
from typing import Any, Optional

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def force_platform(platform: str, cpu_devices: int | None = None) -> None:
    """Pin the JAX platform. Must be called before the first JAX
    backend initialization; exported so child processes inherit it."""
    if cpu_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={cpu_devices}"
            ).strip()
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def configure_from_env() -> None:
    plat = os.environ.get("DYN_JAX_PLATFORM")
    if plat:
        n = os.environ.get("DYN_JAX_CPU_DEVICES")
        force_platform(plat, int(n) if n else None)


def compile_cache_dir() -> Optional[str]:
    """The directory the rule above resolves to (None = cache off)."""
    if os.environ.get("DYN_COMPILE_CACHE") == "0":
        return None
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        REPO_ROOT, ".jax_cache"
    )


def enable_compile_cache() -> Optional[str]:
    """Apply the compile-cache rule (idempotent); returns the directory.

    A cold start is mostly compile time — the engine prewarms a dozen
    step variants of a 32-layer model — so every restart after the
    first should read them back. Touches only jax.config and the
    environment: no backend is initialised here."""
    import jax

    cache_dir = compile_cache_dir()
    if cache_dir is None:
        # JAX reads JAX_COMPILATION_CACHE_DIR itself, so "off" has to be
        # said to JAX — and to the children, through its own variable
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    for env, name, value in (
        ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
         "jax_persistent_cache_min_compile_time_secs", 0.5),
        ("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES",
         "jax_persistent_cache_min_entry_size_bytes", 0),
    ):
        if env not in os.environ:
            os.environ[env] = str(value)
            jax.config.update(name, value)
    return cache_dir


def held_chip_nodes() -> list[str]:
    """The TPU device nodes this process holds open, as the kernel lists
    them (/proc/self/fd): ``/dev/vfio/<n>`` on a v5e host (``/dev/accel<n>``
    on older ones). It is the one thing that tells confined processes
    apart — inside a process confined to one chip the device calls
    itself id 0 at coords (0,0,0) whichever chip it is (seen on a
    four-chip v5e host, libtpu 0.0.34)."""
    held = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue  # closed meanwhile
        # /dev/vfio/vfio is the container node every process shares
        if target.startswith(("/dev/accel", "/dev/vfio/")) and \
                target != "/dev/vfio/vfio":
            held.add(target)
    return sorted(held)


def describe_devices(devices: Any = None) -> dict:
    """Platform, kind, ids and count of ``devices`` (default: all JAX
    devices), and the chips' device nodes this process holds —
    initialises the backend."""
    import jax

    devs = list(devices) if devices is not None else jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "ids": [d.id for d in devs],
        "chip_nodes": held_chip_nodes(),
    }


_cpu_warned = False


def warn_if_cpu_fallback(log: Any, what: str) -> bool:
    """One WARNING per process when JAX landed on the CPU without being
    asked to (``DYN_JAX_PLATFORM`` / ``JAX_PLATFORMS`` = cpu): JAX
    carries on there when the TPU cannot be initialised, and the
    serving path would quietly run its XLA reference kernels."""
    global _cpu_warned
    import jax

    if jax.default_backend() != "cpu" or _cpu_warned:
        return False
    asked = {
        os.environ.get("DYN_JAX_PLATFORM", ""),
        os.environ.get("JAX_PLATFORMS", ""),
    }
    if "cpu" in asked:
        return False
    _cpu_warned = True
    log.warning(
        "%s is running on the CPU backend although no CPU platform was "
        "requested (DYN_JAX_PLATFORM/JAX_PLATFORMS unset): no TPU could "
        "be initialised, so attention and matmuls take the XLA "
        "reference path", what,
    )
    return True
