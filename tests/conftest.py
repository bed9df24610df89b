"""Test harness config.

Per SURVEY.md §4: tests run on a virtual 8-device CPU mesh
(``xla_force_host_platform_device_count``) so every collective/parallelism
strategy is exercised without TPU hardware; numeric checks pin matmul
precision to HIGHEST (TPU default bf16 matmuls would break finite-difference
gradient comparisons)."""
import os

# Unit tests run on the virtual 8-device CPU mesh regardless of hardware:
# the platform is forced before jax initializes a backend.
# PADDLE_TPU_HW_TESTS=1 opts out, keeping the real TPU backend for the
# hardware-only tests (in-kernel PRNG dropout etc.) that skip on CPU.
_HW = os.environ.get("PADDLE_TPU_HW_TESTS") == "1"
if not _HW:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax

if not _HW:
    jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

# Persistent compilation cache: the eager path compiles one executable per
# (op, shape) — cache them across tests and across pytest runs. Where it
# lives is framework/compile_cache.py's decision (JAX_COMPILATION_CACHE_DIR
# if set, else <checkout>/.jax_cache/<host-key>).
#
# KNOWN HAZARD (observed 2026-08 on jax 0.4.x; not re-checked on 0.9.0): a
# SAME-host cache round-trip of the test_models_bert_vision executables was
# broken — a cold run populated the cache and passed, the next (warm) run
# died mid-file (SIGSEGV/SIGABRT in copy.deepcopy or CompiledStep dispatch).
# A crashed warm suite is recovered by `rm -rf .jax_cache`.
from paddle_tpu.framework.compile_cache import enable_compile_cache

enable_compile_cache()
