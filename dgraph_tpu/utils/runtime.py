"""Where this process runs: JAX backend, devices, compile cache.

One process owns a chip. `serve` and `worker` are the processes that may:
they call `init_backend()` once before their banner, so a machine whose
requested platform cannot initialise (`JAX_PLATFORMS=tpu` without a chip)
is a start-up error instead of a first-request error, and a process that
came up on XLA:CPU says so in its banner. Host-only subcommands (`bulk`,
`zero`, `live`, `export`, `convert`, `ldbc_gen`) must never initialise a
backend — a `zero` holding the chip would starve its own workers —
and `assert_host_only()` is how they prove it.

This is the lowest layer: it knows the JAX backend and nothing of the
kernels or the storage codec above it (their fields are added where the
banner and /debug/compiles are assembled). Importing it imports no jax.
"""

from __future__ import annotations

import functools
import os
import sys
import time
from importlib import metadata

# <checkout>/.jax_cache, normalised: the path is part of what makes a
# cache entry findable again, so every entry point must spell it the same
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# cache every program, however small or quick to build: a cold server
# pays one backend compile per program family and shape class, and a
# restart should pay none (env spellings win when the operator set them)
_CACHE_THRESHOLDS = (
    ("jax_persistent_cache_min_entry_size_bytes",
     "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", -1),
    ("jax_persistent_cache_min_compile_time_secs",
     "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", 0.0),
)


def process_age_s() -> float | None:
    """Seconds since the kernel started this process (its /proc start time
    against the boot clock, 10 ms ticks): what interpreter start and
    imports cost before any code of ours could read a clock. None where
    /proc or the boot clock is missing."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return time.clock_gettime(time.CLOCK_BOOTTIME) \
            - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed place and
    return it. With JAX_COMPILATION_CACHE_DIR in the environment the
    directory is the operator's and nothing is set in code."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    for name, env, value in _CACHE_THRESHOLDS:
        if env not in os.environ:
            jax.config.update(name, value)
    return jax.config.jax_compilation_cache_dir


def init_backend() -> dict:
    """Initialise the JAX backend now and say what it is. Raises whatever
    jax raises when the requested platform has no usable device."""
    configure_compile_cache()
    return describe()


@functools.cache
def _versions() -> dict:
    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def describe() -> dict:
    """Platform, devices, versions, cache dir and live per-device memory —
    the backend part of /debug/compiles' `runtime` section. Initialises
    the backend if nothing has yet."""
    import jax

    devs = jax.devices()
    per_device = []
    for d in devs:
        st = d.memory_stats() or {}
        per_device.append({
            "id": d.id,
            "bytes_in_use": int(st.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(st.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(st.get("bytes_limit", 0))})
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "default_backend": jax.default_backend(),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        **_versions(),
        "devices": per_device,
    }


def banner_fields(info: dict) -> dict:
    """The subset of describe() a start-up banner carries."""
    return {"platform": info["platform"],
            "device_kind": info["device_kind"],
            "devices": info["device_count"],
            "compile_cache": info["compile_cache_dir"]}


def backend_initialized() -> bool:
    """True once any JAX backend client exists in this process (never
    imports jax itself)."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def assert_host_only(cmd: str) -> None:
    if backend_initialized():
        raise RuntimeError(
            f"host-only subcommand {cmd!r} initialised a JAX backend; it "
            f"must leave the device to serve/worker")
