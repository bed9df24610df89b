"""Where the persistent XLA compilation cache lives.

One function, called by every entry point that wants compiled programs kept
across processes (``chip_smoke.py``, the bench entry points,
``tests/conftest.py``); the package itself never turns the cache on.

The directory is placed from outside: if ``JAX_COMPILATION_CACHE_DIR`` is
set, jax already reads it and nothing here names another. Otherwise the
cache goes to ``<checkout>/.jax_cache/<host-key>`` — a fixed, git-ignored
path (the path is part of jax's cache key, so a directory that moves never
hits). ``<host-key>`` hashes the host CPU's feature set: XLA:CPU executables
cached on one machine type abort when loaded on another, and the key is a
function of the host, not of time or pid.
"""
from __future__ import annotations

import hashlib
import os

__all__ = ["enable_compile_cache", "default_cache_dir"]

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _host_key():
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
    except OSError:
        return "generic"
    flags = next((l for l in lines if l.startswith("flags")), "")
    # identical flags can still differ in XLA-derived target features
    # (prefer-no-scatter/-gather): key on the model and core count too
    model = next((l for l in lines if l.startswith("model name")), "")
    return hashlib.sha1(
        (flags + model + f"n{os.cpu_count()}").encode()).hexdigest()[:12]


def default_cache_dir():
    return os.path.join(_CHECKOUT, ".jax_cache", _host_key())


def enable_compile_cache():
    """Turn the persistent compilation cache on and return its directory.

    Every program is kept, however small or quick to compile: the eager
    path compiles one small executable per (op, shape), and those are most
    of a cold start."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", default_cache_dir())
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
