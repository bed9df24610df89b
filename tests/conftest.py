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


# One time limit for every test. The driver gives the whole suite one clock
# and no plugin here gives a test its own, so a test that hangs would spend
# all of it and name nobody. LIMIT is twice the driver's factor over this
# sandbox (2.6, ledger PR 30) times the slowest test the suite allows (40 s,
# ROADMAP "Tier-1 bounds"), in round figures. A constant: no option, no
# environment variable, no marker raises it.
import contextlib
import faulthandler
import signal

import pytest

LIMIT = 300
_stderr_fd = 2


def pytest_configure(config):
    # output capture is suspended here: keep the real stderr for the dump
    # of a process that exits with the capture's files
    global _stderr_fd
    _stderr_fd = os.dup(2)


@contextlib.contextmanager
def time_limit(seconds, name):
    """Fail the test ``name`` once ``seconds`` have passed: SIGALRM raises
    in the main thread, where xdist's workers too run their tests. Behind it,
    for a test stuck outside the interpreter (a signal handler waits for the
    bytecode loop), faulthandler's watchdog thread dumps every stack and
    ends the process 30 s later; xdist then reports the worker's test as
    crashed and replaces the worker. Both are disarmed on the way out, and
    an enclosing limit is put back. A no-op where SIGALRM is missing."""
    if not hasattr(signal, "SIGALRM"):
        yield
        return

    def expired(signum, frame):
        pytest.fail(f"{name} ran over its time limit of {seconds} s")

    old_handler = signal.signal(signal.SIGALRM, expired)
    old_left, _ = signal.setitimer(signal.ITIMER_REAL, seconds)
    faulthandler.dump_traceback_later(seconds + 30, exit=True, file=_stderr_fd)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, old_left)
        signal.signal(signal.SIGALRM, old_handler)
        if old_left:
            faulthandler.dump_traceback_later(old_left + 30, exit=True,
                                              file=_stderr_fd)


@pytest.fixture(autouse=True)
def _time_limit(request):
    with time_limit(LIMIT, request.node.nodeid):
        yield


@pytest.fixture
def counting():
    """Telemetry on and empty for one test, off and empty again after it:
    left on, it fails whichever file the worker runs next that expects it
    off (``test_tracing.py``)."""
    from paddle_tpu.profiler import telemetry

    telemetry.enable()
    telemetry.reset()
    yield telemetry.get_telemetry()
    telemetry.disable()
    telemetry.reset()
