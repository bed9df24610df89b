"""Partitioning Pallas kernel calls over a device mesh.

GSPMD cannot split a Mosaic kernel ("Mosaic kernels cannot be automatically
partitioned"), so on a multi-device mesh the kernel entry points partition
themselves: :func:`batch_sharded` wraps the call in a ``shard_map`` over the
mesh axes the step's batch is sharded on (LayerNorm rows and attention batch
entries are independent). The mesh is the one
:class:`~paddle_tpu.jit.functionalize.CompiledStep` observed on its inputs
(:func:`partition_scope`), or the operands' own when they are concrete
arrays. Kept apart from ``ops.pallas`` so that ``import paddle_tpu`` does
not import the kernels.
"""
from __future__ import annotations

import contextlib

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

__all__ = ["observed_partition", "partition_scope", "batch_sharded"]

#: (mesh, batch_axes) of the step being traced — see partition_scope
_PARTITION = None


def observed_partition(leaves):
    """``(mesh, batch_axes)`` of the first array in ``leaves`` placed on a
    multi-device mesh — ``batch_axes`` being the mesh axes its leading dim
    is sharded over — or None when every leaf lives on one device."""
    for leaf in leaves:
        sh = getattr(leaf, "sharding", None)
        if isinstance(sh, NamedSharding) and sh.mesh.size > 1:
            head = sh.spec[0] if len(sh.spec) else None
            axes = () if head is None else (
                (head,) if isinstance(head, str) else tuple(head))
            return sh.mesh, axes
    return None


@contextlib.contextmanager
def partition_scope(partition):
    """Declare the ``(mesh, batch_axes)`` (or None) the code traced inside
    runs under; :func:`batch_sharded` reads it for traced operands, whose
    placement GSPMD decides only after tracing."""
    global _PARTITION
    prev, _PARTITION = _PARTITION, partition
    try:
        yield
    finally:
        _PARTITION = prev


def batch_sharded(fn, operands, batch_dim_of, seed_index=None):
    """Run ``fn(*operands)`` — a Pallas kernel call whose dim-0 entries are
    independent — partitioned over the batch axes of the current mesh.

    ``batch_dim_of[i]`` is True when ``operands[i]`` carries the batch on
    dim 0 (sharded), False when it is shared by every batch entry
    (replicated; its cotangent is summed over the mesh by shard_map's
    transpose). ``None`` operands pass through. Outputs carry the batch on
    dim 0. ``seed_index`` names an int32 PRNG-seed operand that gets the
    shard index folded in, so shards draw distinct dropout masks.

    With no multi-device mesh in sight this is ``fn(*operands)``; a mesh
    whose batch axes do not divide the batch raises (shard_map's own
    error) — the caller never gets a different formulation."""
    part = _PARTITION
    if part is None:
        part = observed_partition(
            x for x in operands if not isinstance(x, jax.core.Tracer))
    if part is None:
        return fn(*operands)
    mesh, axes = part
    if jax.sharding.get_abstract_mesh().manual_axes:
        # already inside a shard_map body: operands are per-shard values
        return fn(*operands)
    live = [i for i, x in enumerate(operands) if x is not None]
    spec = P(axes) if axes else P()
    in_specs = tuple(spec if batch_dim_of[i] else P() for i in live)

    def body(*vals):
        full = list(operands)
        for i, v in zip(live, vals):
            full[i] = v
        if seed_index is not None and full[seed_index] is not None and axes:
            full[seed_index] = full[seed_index] ^ jax.lax.axis_index(axes)
        return fn(*full)

    return jax.shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=spec,
                         check_vma=False)(*[operands[i] for i in live])
