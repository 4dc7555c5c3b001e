"""Process-level JAX set-up shared by every entry point, in ONE place.

- `request_cpu_devices(n)`: n virtual CPU devices for the multi-device
  tests, the child scripts and the driver's dry run.
- `configure_compile_cache()`: where the persistent XLA compilation
  cache lives. Called once at the top of `cli.main.main`,
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
    _compile_multi_device_cpu_programs_afresh()
    # Before the first compile of the process, so that the span spine's
    # start-up collector sees that one too (obs/tracing.startup_spans).
    from proteinbert_tpu.obs import tracing

    tracing.arm()
    return directory


def _compile_multi_device_cpu_programs_afresh() -> None:
    """A CPU executable over more than one device is never LOADED from
    the cache: it is compiled again (and written again).

    jaxlib 0.9.0 runs such an executable, once loaded, with its
    independent collectives in no fixed order, each blocking a thread of
    the one pool all the virtual devices share: a sharded step whose
    scan body opens with one all-gather a weight (PR 29) runs when
    freshly compiled, but loaded its eight devices on a `data=2,fsdp=4`
    mesh wait at three different collectives and the runtime aborts the
    process after 40 s, every time (a pool of 64 threads, `NPROC=64`,
    lets it through: starvation, not a wrong program). jax offers no
    public switch per program, so this wraps the one function through
    which jax reads an entry; tests/test_parallel.py fails if that function
    moves. The chip's executables, and one-device CPU ones, load as
    before."""
    from jax._src import compilation_cache

    read = compilation_cache.get_executable_and_time
    if getattr(read, "compiles_multi_device_cpu_afresh", False):
        return

    def get_executable_and_time(cache_key, compile_options, backend,
                                executable_devices):
        if backend.platform == "cpu" and len(executable_devices) > 1:
            return None, None
        return read(cache_key, compile_options, backend, executable_devices)

    get_executable_and_time.compiles_multi_device_cpu_afresh = True
    compilation_cache.get_executable_and_time = get_executable_and_time
