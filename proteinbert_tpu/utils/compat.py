"""Process-level JAX set-up shared by every entry point, in ONE place.

- `request_cpu_devices(n)`: n virtual CPU devices for the multi-device
  tests, the child scripts and the driver's dry run.
- `configure_compile_cache()`: where the persistent XLA compilation
  cache lives. Called once at the top of `cli.main.main`, `bench.py`,
  `chip_smoke.py` and the test harness, so every process of a run —
  and the next run — shares one cache.
"""

from __future__ import annotations

import os
import re

_FORCE_FLAG = "--xla_force_host_platform_device_count"

# The one in-checkout cache directory (git-ignored). The path is part
# of the cache's key, so it is a fixed name: never a temporary
# directory, a pid or a time.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def request_cpu_devices(n: int) -> None:
    """Ask for `n` virtual CPU devices; call BEFORE any device use.

    On an already-initialized backend the request cannot take effect
    (the config API raises RuntimeError, swallowed here so a dry run
    inside a warm session degrades to the caller's count check instead
    of crashing) — the caller verifies `jax.device_count()` afterwards.
    """
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n)
    except RuntimeError:  # backend already initialized
        pass


def scrub_device_count_flag(flags: str) -> str:
    """Remove any --xla_force_host_platform_device_count=N from an
    XLA_FLAGS string. Test parents pinned to 8 devices use this on a
    child's env so the child's own request_cpu_devices(n) is the only
    count in play."""
    return re.sub(_FORCE_FLAG + r"=\d+", "", flags).strip()


def configure_compile_cache() -> str:
    """Arm the persistent XLA compilation cache and return its
    directory. Where `JAX_COMPILATION_CACHE_DIR` is set, that directory
    is the cache and no code sets another; otherwise it is the fixed
    `.jax_cache/` inside the checkout. A restarted process — a resumed
    trainer, a replacement serve replica, the next phase of
    `chip_smoke.py` — deserializes its warm executables instead of
    compiling them again.

    Min-compile-time defaults to 0 so EVERY executable caches (serving
    shapes are small; JAX's default threshold would skip them);
    `JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`, which JAX reads
    itself, overrides. Must run before the first compile of the
    process."""
    import jax

    directory = os.path.abspath(
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or DEFAULT_COMPILE_CACHE_DIR)
    os.makedirs(directory, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", directory)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory
