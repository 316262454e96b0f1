"""presto-tpu-execution: a TPU-native Presto worker backend.

See SURVEY.md for the structural analysis of the reference (PrestoDB) this
framework is built against, and README.md for the architecture overview.

Importing the package configures JAX and initialises no backend: a chip
belongs to one process, so a parent that imports this package and then
starts workers (worker/launcher.py, benchmarks/suite_runner.py) must not
hold it.
"""
import os as _os

import jax as _jax

# The engine's value domains are 64-bit (BIGINT, DOUBLE, long decimal
# accumulators), mirroring the JVM's long/double.  x64 must be on before any
# array is created.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: pipeline shapes recur across queries and
# processes, and TPU programs can take tens of seconds to compile.  ONE
# directory per checkout: the one JAX_COMPILATION_CACHE_DIR names when it is
# set (JAX reads it itself, and no code here sets another), else this fixed,
# git-ignored path.  The path is part of the cache key, so it carries no
# host, pid, time or temporary name.
DEFAULT_COMPILE_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")


def set_compile_cache_dir(path: str) -> str:
    """Point JAX's persistent compilation cache at `path`, unless
    JAX_COMPILATION_CACHE_DIR already places it.  Returns the directory
    in use; one that cannot be created or set raises."""
    env = _os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    _os.makedirs(path, exist_ok=True)
    _jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)


# The XLA:CPU backend persists AOT executables whose recorded machine
# features can mismatch even the producing host's runtime detection
# (cpu_aot_loader warns "could lead to execution errors such as SIGILL",
# and full-suite runs twice segfaulted inside
# compilation_cache.get_executable_and_time) — so the package turns the
# cache ON except for the CPU backend.  Decided from the FIRST JAX_PLATFORMS
# entry alone (unset = the accelerator JAX finds); asking
# jax.default_backend() here would initialise a backend at import.
# Opt out with PRESTO_TPU_NO_COMPILE_CACHE=1.
if not _os.environ.get("PRESTO_TPU_NO_COMPILE_CACHE") \
        and _os.environ.get("JAX_PLATFORMS", "").split(",")[0] \
        .strip().lower() != "cpu":
    set_compile_cache_dir(DEFAULT_COMPILE_CACHE_DIR)
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)

__version__ = "0.1.0"
