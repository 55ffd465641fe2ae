"""What every on-chip entry point does first (``chip_smoke.py``,
``bench.py``, ``tests/perf/decode_bench.py``): place the persistent
compile cache, and refuse to run without an accelerator.

A number taken on the CPU backend or the Pallas interpreter is not a
device number, so the measuring scripts fail there instead of falling
back; every result they print names the device from
:func:`require_accelerator`.
"""

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
CACHE_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point jax's persistent compile cache somewhere stable; returns the
    directory in use. Call before the first compile.

    The directory is placed from OUTSIDE when ``JAX_COMPILATION_CACHE_DIR``
    is set — jax reads that variable itself, so nothing is set in code —
    and otherwise sits at a fixed path under the checkout. The path is
    part of the cache key, so it never carries a pid, a timestamp or a
    temp dir."""
    placed = os.environ.get(CACHE_ENV_VAR)
    if placed:
        return placed
    import jax
    cache_dir = str(CHECKOUT / ".jax_compilation_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program: a warm second run should compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def device_info() -> dict:
    """The device as jax reports it — stamped on every printed result."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def holds_accelerator() -> bool:
    """True when THIS process has initialised a non-CPU jax backend. It
    then owns the chips: a chip belongs to one process at a time, and a
    child that needs it fails or hangs. Never initialises a backend."""
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    import jax
    return jax.default_backend() != "cpu"


def require_accelerator() -> dict:
    """:func:`device_info`, or ``SystemExit`` (non-zero) when jax found
    only the CPU. Initialises the backend: the calling process holds the
    chip from here on."""
    info = device_info()
    if info["platform"] == "cpu":
        raise SystemExit(
            "no accelerator: jax.devices() found only the CPU backend "
            f"({info['kind']} x{info['count']}); this entry point measures "
            "a chip and does not fall back")
    return info
