"""Process-level JAX settings: where compiled programs are cached and
which processes may never own an accelerator.

Both must take effect before the first device query, so entry points
call them first thing in ``main()``. Importing this module imports
nothing heavy; ``jax`` is imported only inside the calls.
"""

import os

_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def compile_cache_dir() -> str:
    """The persistent compilation cache directory: the standard
    ``JAX_COMPILATION_CACHE_DIR`` when the launcher set it (a pod spec
    does through ``--envs``), else ``<checkout>/.jax_cache``. The path
    is part of the cache key on some backends, so it is never derived
    from a temporary name, a pid or the time."""
    return os.environ.get(_ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process and return its
    directory. An elastic relaunch (same program shapes) then restores
    compiled executables from disk: recovery becomes checkpoint-read
    bound, not compile bound. JAX reads the environment variable
    itself; only the fallback path is set in code."""
    import jax

    cache_dir = compile_cache_dir()
    if not os.environ.get(_ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # Cache every program, however small or fast to compile.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def force_cpu():
    """Pin this process to the CPU backend. With libtpu the first
    process to initialise a backend holds every chip of the host, so
    control-plane processes (master, standby, row service, router),
    which import zoo code and with it jax, call this before anything
    can query a device."""
    import jax

    jax.config.update("jax_platforms", "cpu")
