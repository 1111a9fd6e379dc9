"""One rule for JAX's persistent compilation cache.

Called by the entry points that compile for a chip — ``chip_smoke.py``'s
children, ``bench.py``, the examples — and, through :func:`export`, by the
launcher for its ranks. The library itself never sets a cache.

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already honours it and nothing
here names another directory; otherwise the cache lives at the fixed path
``<repo>/.cache/jax`` (git-ignored). The path is part of the cache key, so
it is never a temporary name, pid or time. CPU-pinned processes are left
alone (see :func:`_cpu_pinned`).
"""

from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def default_dir() -> str:
    return os.path.join(_REPO, ".cache", "jax")


# JAX's default threshold (1 s) would skip most tile kernels, which are
# exactly what a tile runtime recompiles on every cold start
_MIN_COMPILE_SECS = 0.0


def _cpu_pinned(platforms) -> bool:
    """The cache is for accelerator compiles. A process pinned to the CPU
    gets none: reloading XLA:CPU executables logs a machine-feature
    mismatch per entry and "could lead to execution errors such as
    SIGILL" (cpu_aot_loader.cc)."""
    return (platforms or "").split(",")[0] == "cpu"


def export(env: dict) -> None:
    """Give child processes started with ``env`` one shared cache, through
    JAX's own environment variables (no JAX import: the launcher parent
    must not touch a backend)."""
    if _cpu_pinned(env.get("JAX_PLATFORMS")):
        return
    if not env.get(ENV_DIR):
        env[ENV_DIR] = default_dir()
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                   str(_MIN_COMPILE_SECS))


def enable() -> None:
    """Turn the persistent cache on for this process. Call before the
    first compile."""
    import jax
    if _cpu_pinned(jax.config.jax_platforms):
        return
    if not os.environ.get(ENV_DIR):
        jax.config.update("jax_compilation_cache_dir", default_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      _MIN_COMPILE_SECS)
