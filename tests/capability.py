"""Backend capability probes for environment-dependent skips.

The tier-1 suite runs on a virtual 8-device XLA:CPU mesh (conftest.py).
Some programs the framework legitimately emits are rejected by that
backend — e.g. the SPMD partitioner cannot place a ``PartitionId``
instruction (``UNIMPLEMENTED``), which partial-manual ``shard_map`` regions
(manual over pp/sep only, auto over dp/mp) produce via ``axis_index`` /
``ppermute``. Real TPUs partition these fine.

Rather than hard-skipping by platform name, each probe ATTEMPTS the minimal
failing construct and skips only when the backend actually rejects it — so
the tests turn back on by themselves the day the backend learns the
feature. Probes run in a SUBPROCESS: near-miss variants of these programs
die in uncatchable XLA CHECK aborts (SIGABRT), which must not take the
pytest process down with them.
"""
from __future__ import annotations

import functools
import os
import subprocess
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the minimal form of the 4 known-failing tier-1 cases: a dp×sep hybrid
# mesh, replicated inputs entering jit, and the ring-attention shard_map
# (manual over sep ONLY) rotating KV chunks with ppermute/axis_index inside
_PARTITION_ID_PROBE = """
import os
if os.environ.get("PADDLE_TPU_HW_TESTS") != "1":
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8")
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from paddle_tpu.distributed import fleet
from paddle_tpu.framework.tensor import Tensor

strategy = fleet.DistributedStrategy()
strategy.hybrid_configs["dp_degree"] = 2
strategy.hybrid_configs["sep_degree"] = 2
fleet.init(is_collective=True, strategy=strategy)
from paddle_tpu.distributed.meta_parallel import ring_attention
mesh = fleet.get_hybrid_communicate_group().mesh

def f(q, k, v):
    return ring_attention(Tensor(q), Tensor(k), Tensor(v),
                          is_causal=True)._value

x = jax.device_put(jnp.ones((2, 8, 2, 4), jnp.float32),
                   NamedSharding(mesh, P()))
np.asarray(jax.jit(f)(x, x, x))
print("PROBE_OK")
"""


@functools.lru_cache(maxsize=1)
def spmd_partition_id_supported():
    """True when the backend can SPMD-partition programs containing
    ``PartitionId`` (partial-manual shard_map collectives)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PARTITION_ID_PROBE],
            env=env, capture_output=True, timeout=300)
    except Exception:
        return False
    return proc.returncode == 0 and b"PROBE_OK" in proc.stdout


def requires_spmd_partition_id():
    """Skip marker for tests whose mesh/program shape needs PartitionId
    under SPMD partitioning (hybrid meshes with auto axes alongside a
    manual shard_map axis)."""
    import pytest

    return pytest.mark.skipif(
        not spmd_partition_id_supported(),
        reason="backend cannot SPMD-partition PartitionId (partial-manual "
               "shard_map over a hybrid mesh) — UNIMPLEMENTED on XLA:CPU")
