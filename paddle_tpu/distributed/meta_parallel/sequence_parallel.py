"""Sequence/context parallelism: blockwise ring attention over the ``sep``
mesh axis.

Reference: ABSENT — the reference's longest-context support is fused
attention kernels (``paddle/fluid/operators/fused/fused_attention_op.cu:1``,
``fused_softmax_mask.cu.h``); SURVEY §5 marks sequence parallelism
green-field. This is the TPU-native design the blueprint calls for:

* Q, K, V are sharded along the sequence dim over ``sep``; each device
  computes its Q-shard's attention against every KV-shard by rotating the
  KV chunks around the ICI ring with ``lax.ppermute`` while maintaining the
  online-softmax running (max, sum, out) — flash attention's recurrence at
  chunk granularity, so the full ``[S, S]`` score matrix never exists and
  per-device memory is O(S/N · S/N) per step.
* The backward schedule is not hand-written: differentiating through the
  ``lax.scan`` of rotations transposes each ppermute into the reverse
  rotation — the same communication volume hand-rolled ring-attention
  backwards schedule, derived by the compiler.
* Causal masking is resolved per (q-chunk, kv-chunk) pair: earlier chunks
  attend fully, the diagonal chunk applies the in-chunk causal mask, later
  chunks are masked out (their compute is the uniform-SPMD bubble).

Composes with dp/mp: the shard_map is manual ONLY over ``sep``; batch and
head dims keep their GSPMD shardings.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from ...ops.dispatch import apply_op
from ..topology import AXIS_SEP

__all__ = ["ring_attention", "split_sequence", "gather_sequence"]

_NEG_INF = -1e30


def _chunk_attend(q, k, v, o, m, l, scale, mask_mode, q_idx, kv_idx, s_local,
                  dropout_p=0.0, dropout_key=None):
    """One online-softmax update of the running (o, m, l) with a KV chunk.

    q: [b, sq, h, d]; k/v: [b, sk, h, d]; o: [b, sq, h, d] f32;
    m, l: [b, h, sq] f32. mask_mode: 0 full, 1 causal-diagonal, 2 skip —
    traced scalars resolved with jnp.where (uniform SPMD compute).

    Dropout (post-softmax, like the fused kernels): the keep mask is drawn
    from ``dropout_key`` folded by the GLOBAL (q_chunk, kv_chunk) pair, so
    every device draws the mask its chunk pair owns and the autodiff
    backward (which replays this trace) reuses the identical bits; l
    accumulates the UNdropped p, only the value product sees the mask.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    sq, sk = q.shape[1], k.shape[1]
    rows = lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    cols = lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    # global positions: row r of q-chunk i is i*s_local + r
    diag = rows + q_idx * s_local >= cols + kv_idx * s_local
    keep = jnp.where(mask_mode == 0, jnp.ones((sq, sk), bool),
                     jnp.where(mask_mode == 1, diag,
                               jnp.zeros((sq, sk), bool)))
    s = jnp.where(keep[None, None], s, _NEG_INF)

    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # fully-masked rows keep m at -inf-ish: guard the exp
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(keep[None, None], p, 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1)
    if dropout_p > 0.0:
        ck = jax.random.fold_in(dropout_key, q_idx * 65536 + kv_idx)
        drop_keep = jax.random.bernoulli(ck, 1.0 - dropout_p, p.shape)
        p = jnp.where(drop_keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * jnp.swapaxes(alpha, 1, 2)[..., None] + pv
    return o_new, m_new, l_new


# One program per (shapes, mesh, flags). Called un-jitted, shard_map runs
# eagerly: every primitive of the body is traced and lowered as a program of
# its own on EVERY call (~100 lowerings a call at sep=4), and the dropout key,
# closed over by ``local_fn``, is a fresh constant each time.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _ring_attention_impl(q, k, v, mesh, causal, scale, axis=AXIS_SEP,
                         dropout_p=0.0, dropout_key=None):
    """Global [b, S, h, d] arrays; runs the rotation ring manual over sep."""
    ax = mesh.axis_names.index(axis)
    n = mesh.devices.shape[ax]
    if n == 1:
        # degenerate ring: plain blockwise attention
        return _single_chunk(q, k, v, causal, scale, dropout_p, dropout_key)

    # nested manual regions (e.g. ring attention inside the pp-manual
    # pipeline stage body): shard_map must receive the AMBIENT abstract mesh
    # (with the outer axes already marked Manual), not the concrete one
    try:
        ambient = jax.sharding.get_abstract_mesh()
        if ambient is not None and axis in getattr(ambient, "axis_names", ()):
            if any("Manual" in str(t) for t in
                   getattr(ambient, "axis_types", ())):
                mesh = ambient
    except Exception:
        pass

    def local_fn(q_l, k_l, v_l):
        i = lax.axis_index(axis)
        s_local = q_l.shape[1]
        # mark the zero-init carries device-varying over sep so the scan
        # carry type matches the ppermute outputs (shard_map vma rules)
        o0 = lax.pcast(jnp.zeros(q_l.shape, jnp.float32), (axis,),
                       to="varying")
        m0 = lax.pcast(
            jnp.full((q_l.shape[0], q_l.shape[2], s_local), _NEG_INF,
                     jnp.float32), (axis,), to="varying")
        l0 = lax.pcast(
            jnp.zeros((q_l.shape[0], q_l.shape[2], s_local), jnp.float32),
            (axis,), to="varying")

        def attend(k_c, v_c, o, m, l, j):
            kv_idx = (i - j) % n          # chunk currently held
            if causal:
                mask_mode = jnp.where(kv_idx == i, 1,
                                      jnp.where(kv_idx < i, 0, 2))
            else:
                mask_mode = jnp.zeros((), jnp.int32)
            return _chunk_attend(q_l, k_c, v_c, o, m, l, scale,
                                 mask_mode, i, kv_idx, s_local,
                                 dropout_p, dropout_key)

        # own chunk first (no rotation), then n-1 permute-then-attend steps:
        # exactly n-1 KV rotations total
        o, m, l = attend(k_l, v_l, o0, m0, l0, 0)

        def step(carry, j):
            k_c, v_c, o, m, l = carry
            perm = [(r, (r + 1) % n) for r in range(n)]
            k_c = lax.ppermute(k_c, axis, perm)
            v_c = lax.ppermute(v_c, axis, perm)
            o, m, l = attend(k_c, v_c, o, m, l, j)
            return (k_c, v_c, o, m, l), None

        (k_f, v_f, o, m, l), _ = lax.scan(
            step, (k_l, v_l, o, m, l), jnp.arange(1, n)
        )
        l_safe = jnp.maximum(l, 1e-30)
        out = o / jnp.swapaxes(l_safe, 1, 2)[..., None]
        return out.astype(q_l.dtype)

    spec = P(None, axis)  # shard the sequence dim
    return shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names=frozenset({axis}),
    )(q, k, v)


def _single_chunk(q, k, v, causal, scale, dropout_p=0.0, dropout_key=None):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(cmask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0:
        keep = jax.random.bernoulli(jax.random.fold_in(dropout_key, 0),
                                    1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_p)), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(q.dtype)


def ring_attention(query, key, value, is_causal=True, scale=None, mesh=None,
                   axis=AXIS_SEP, dropout_p=0.0, name=None):
    """Sequence-parallel attention over the ``sep`` mesh axis.

    Args:
        query/key/value: ``[batch, seq, heads, head_dim]`` Tensors whose seq
            dim is (to be) sharded over ``sep``. Global-array convention:
            pass full-size arrays; GSPMD keeps them sharded.
        is_causal: causal masking with global positions.
        scale: softmax scale (default ``1/sqrt(head_dim)``).
        mesh: override mesh (default: the fleet hybrid mesh).
    """
    if mesh is None:
        from ..fleet.base.fleet_base import get_hybrid_communicate_group

        hcg = get_hybrid_communicate_group()
        if hcg is None:
            raise RuntimeError("ring_attention needs fleet.init (hybrid mesh)")
        mesh = hcg.mesh
    if scale is None:
        scale = 1.0 / math.sqrt(query.shape[-1])
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(
            f"dropout_p must be in [0, 1), got {dropout_p}")
    dkey = None
    if dropout_p > 0.0:
        from ...framework import random as rnd

        dkey = rnd.next_key()

    def fwd(q, k, v, dk=None):
        return _ring_attention_impl(q, k, v, mesh, bool(is_causal),
                                    float(scale), axis,
                                    float(dropout_p), dk)

    args = (query, key, value) if dkey is None else (query, key, value, dkey)
    return apply_op("ring_attention", fwd, args, {})


def split_sequence(x, mesh=None, axis_name=AXIS_SEP, seq_axis=1):
    """Annotate (shard) the sequence dim of ``x`` over ``sep``."""
    if mesh is None:
        from ..fleet.base.fleet_base import get_hybrid_communicate_group

        mesh = get_hybrid_communicate_group().mesh

    def fwd(a):
        spec = [None] * a.ndim
        spec[seq_axis] = axis_name
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(*spec))
        )

    return apply_op("split_sequence", fwd, (x,), {})


def gather_sequence(x, mesh=None, axis_name=AXIS_SEP, seq_axis=1):
    """Annotate ``x`` replicated (gathered) along ``sep``."""
    if mesh is None:
        from ..fleet.base.fleet_base import get_hybrid_communicate_group

        mesh = get_hybrid_communicate_group().mesh

    def fwd(a):
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(mesh, P(*([None] * a.ndim)))
        )

    return apply_op("gather_sequence", fwd, (x,), {})
