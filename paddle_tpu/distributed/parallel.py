"""Parallel environment (reference ``python/paddle/distributed/parallel.py:94
init_parallel_env`` and ``ParallelEnv``).

The reference spawns one process per GPU and rendezvouses through a TCPStore;
on TPU, jax is multi-controller (one process per host, all local chips
visible) and rendezvous comes from slice metadata via
``jax.distributed.initialize``. The env-var surface
(``PADDLE_TRAINER_ID``/``PADDLE_TRAINERS_NUM``) is honored for script
compatibility and for CPU-mesh testing.
"""
from __future__ import annotations

import os

import jax

_initialized = False


def _env_int(names, default):
    for n in names:
        v = os.environ.get(n)
        if v is not None:
            try:
                return int(v)
            except ValueError:
                pass
    return default


def get_rank(group=None):
    """Rank of the current *process* (reference parallel.py get_rank).

    Under jax's one-process-per-host model this is ``jax.process_index()``;
    PADDLE_TRAINER_ID is honored when set (launch-script compatibility).
    """
    if group is not None:
        return group.rank
    return _env_int(["PADDLE_TRAINER_ID", "PADDLE_RANK_IN_NODE"], jax.process_index())


def get_world_size(group=None):
    """Number of processes (reference parallel.py get_world_size)."""
    if group is not None:
        return group.world_size
    return _env_int(["PADDLE_TRAINERS_NUM"], jax.process_count())


class ParallelEnv:
    """reference ``python/paddle/fluid/dygraph/parallel.py ParallelEnv``."""

    def __init__(self):
        self._rank = get_rank()
        self._world_size = get_world_size()

    @property
    def rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def local_rank(self):
        return self._rank

    @property
    def nranks(self):
        return self._world_size

    @property
    def dev_id(self):
        return 0

    @property
    def device_type(self):
        return jax.default_backend()

    @property
    def current_endpoint(self):
        eps = self.trainer_endpoints
        return eps[self._rank] if self._rank < len(eps) else ""

    @property
    def trainer_endpoints(self):
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        return eps.split(",") if eps else []


def init_parallel_env():
    """reference ``distributed/parallel.py:94``. On TPU: multi-host jax
    initialization (controller discovery from slice metadata); single-host is
    a no-op since all local chips are already visible to this process.

    Under ``python -m paddle_tpu.distributed.launch`` the coordinator address
    and rank/world env come from the launcher (PADDLE_* surface); with
    ``--backend gloo`` cross-process CPU collectives are enabled (the
    reference's Gloo fallback for GPU-less testing)."""
    global _initialized
    if _initialized:
        return ParallelEnv()
    coord = os.environ.get("PADDLE_COORDINATOR_ADDRESS") or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    # NOTE: no jax API may run before jax.distributed.initialize — even
    # jax.devices()/process_count() would initialize the XLA backend.
    already = jax.distributed.is_initialized()
    if coord and not already and os.environ.get("PADDLE_TRAINERS_NUM"):
        if os.environ.get("PADDLE_DISTRIBUTED_BACKEND", "") == "gloo":
            jax.config.update("jax_platforms", "cpu")
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ["PADDLE_TRAINERS_NUM"]),
            process_id=int(os.environ.get("PADDLE_TRAINER_ID", 0)),
        )
    _initialized = True
    return ParallelEnv()
