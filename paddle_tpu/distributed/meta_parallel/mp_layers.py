"""Tensor-parallel layers.

Reference: ``fleet/meta_parallel/parallel_layers/mp_layers.py``
(``VocabParallelEmbedding:30``, ``ColumnParallelLinear:95``,
``RowParallelLinear:171``) built on ``c_identity``/``c_concat``/
``c_allreduce_sum`` collective ops and the ``c_embedding`` /
``c_softmax_with_cross_entropy`` CUDA kernels.

TPU-native redesign: tensor parallelism is *weight sharding*, not explicit
collectives. Each layer places its weight with a ``NamedSharding`` over the
``mp`` mesh axis (column-split → output dim, row-split → input dim, vocab
split → row dim) and computes with plain matmul/take; XLA's SPMD partitioner
inserts the same all-reduce/all-gather the reference codes by hand — fused
into the surrounding program. The explicit-collective forms (for shard_map
regions and the PP scheduler) live in the functions ``*_spmd`` below.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ...framework.tensor import Tensor
from ...nn import functional as F
from ...nn.initializer import XavierNormal
from ...nn.layer.layers import Layer
from ..collective import Group
from ..topology import AXIS_MP

__all__ = [
    "VocabParallelEmbedding",
    "ColumnParallelLinear",
    "RowParallelLinear",
    "ParallelCrossEntropy",
]


def _mp_group(group):
    if group is not None:
        return group
    from ..fleet.base.fleet_base import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    if hcg is not None:
        return hcg.get_model_parallel_group()
    from ..collective import _default_group

    return _default_group()


def _replicate_activation(val, mesh):
    """Reshard an activation to replicated (the c_concat / c_allreduce_sum
    point). Under an ambient mesh (e.g. inside the pipeline schedule's
    partially-manual region, where pp is a Manual axis) a bare PartitionSpec
    must be used; otherwise constrain against the group's concrete mesh."""
    if (isinstance(val, jax.core.Tracer)
            and not jax.sharding.get_abstract_mesh().empty):
        return jax.lax.with_sharding_constraint(val, P())
    if mesh is None or getattr(mesh, "size", 0) <= 1:
        # no mesh active (single-process dryrun/tests): the constraint
        # would be a no-op anyway, and an empty mesh makes it a hard error
        return val
    return jax.lax.with_sharding_constraint(val, NamedSharding(mesh, P()))


def _shard(p, group, spec):
    """Annotate a parameter with a mesh sharding (the TP 'split')."""
    p._value = jax.device_put(p._value, NamedSharding(group.mesh, spec))
    p.is_distributed = True
    return p


class ColumnParallelLinear(Layer):
    """Weight [in, out] split on out (reference ``mp_layers.py:95``).

    y = x @ W_col; with gather_output=True the sharded output is gathered
    (reference ``c_concat``) — here a resharding to replicated.
    """

    def __init__(
        self,
        in_features,
        out_features,
        weight_attr=None,
        has_bias=True,
        gather_output=True,
        fuse_matmul_bias=False,
        mp_group=None,
        name=None,
    ):
        super().__init__()
        self.group = _mp_group(mp_group)
        nranks = self.group.nranks
        if out_features % nranks != 0:
            raise ValueError(
                f"out_features {out_features} not divisible by mp degree {nranks}"
            )
        self.gather_output = gather_output
        self._out_features = out_features
        self.weight = self.create_parameter(
            shape=[in_features, out_features],
            attr=weight_attr,
            default_initializer=XavierNormal(),
        )
        _shard(self.weight, self.group, P(None, self.group.axis_name))
        if has_bias:
            self.bias = self.create_parameter(shape=[out_features], is_bias=True)
            _shard(self.bias, self.group, P(self.group.axis_name))
        else:
            self.bias = None

    def forward(self, x):
        y = F.linear(x, self.weight, self.bias)
        if self.gather_output:
            # reshard to replicated ≙ c_concat along out dim
            y._value = _replicate_activation(y._value, self.group.mesh)
        return y


class RowParallelLinear(Layer):
    """Weight [in, out] split on in (reference ``mp_layers.py:171``).

    With input_is_parallel the incoming activation is already split on its
    last dim (the column-parallel partner's output); the partial products
    are summed by the partitioner ≙ ``c_allreduce_sum``.
    """

    def __init__(
        self,
        in_features,
        out_features,
        weight_attr=None,
        has_bias=True,
        input_is_parallel=False,
        fuse_matmul_bias=False,
        mp_group=None,
        name=None,
    ):
        super().__init__()
        self.group = _mp_group(mp_group)
        nranks = self.group.nranks
        if in_features % nranks != 0:
            raise ValueError(
                f"in_features {in_features} not divisible by mp degree {nranks}"
            )
        self.input_is_parallel = input_is_parallel
        self.weight = self.create_parameter(
            shape=[in_features, out_features],
            attr=weight_attr,
            default_initializer=XavierNormal(),
        )
        _shard(self.weight, self.group, P(self.group.axis_name, None))
        if has_bias:
            # bias added once after the cross-shard sum (kept replicated)
            self.bias = self.create_parameter(shape=[out_features], is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        y = F.linear(x, self.weight, None)
        y._value = _replicate_activation(y._value, self.group.mesh)
        if self.bias is not None:
            y = y + self.bias
        return y


class VocabParallelEmbedding(Layer):
    """Embedding table split on vocab dim (reference ``mp_layers.py:30`` /
    ``c_embedding`` kernel). Out-of-shard ids contribute zero and psum
    combines — the partitioner derives exactly this from a masked take."""

    def __init__(
        self, num_embeddings, embedding_dim, weight_attr=None, mp_group=None, name=None
    ):
        super().__init__()
        self.group = _mp_group(mp_group)
        nranks = self.group.nranks
        if num_embeddings % nranks != 0:
            raise ValueError(
                f"num_embeddings {num_embeddings} not divisible by mp degree {nranks}"
            )
        self._num_embeddings = num_embeddings
        self.weight = self.create_parameter(
            shape=[num_embeddings, embedding_dim],
            attr=weight_attr,
            default_initializer=XavierNormal(),
        )
        _shard(self.weight, self.group, P(self.group.axis_name, None))

    def forward(self, x):
        y = F.embedding(x, self.weight)
        y._value = _replicate_activation(y._value, self.group.mesh)
        return y


class ParallelCrossEntropy(Layer):
    """reference ``mp_layers.py ParallelCrossEntropy`` /
    ``c_softmax_with_cross_entropy_op``: softmax-CE over logits whose class
    dim is mp-sharded. Computed as stable log-softmax on the sharded array —
    the cross-shard max/sum reductions become mp-axis collectives in XLA."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.group = _mp_group(mp_group)
        self.ignore_index = ignore_index

    def forward(self, input, label):
        return F.softmax_with_cross_entropy(input, label, ignore_index=self.ignore_index)


# ---------------------------------------------------------------------------
# explicit spmd forms — used inside shard_map regions (PP scheduler, custom
# training steps) where arrays are *local shards* and sharding propagation
# is manual. These mirror the reference kernels 1:1.
# ---------------------------------------------------------------------------

def column_parallel_linear_spmd(x, w_shard, b_shard=None, axis_name=AXIS_MP, gather_output=False):
    """y_shard = x @ W_shard (+b); optional all_gather on last dim ≙ c_concat."""
    y = x @ w_shard
    if b_shard is not None:
        y = y + b_shard
    if gather_output:
        y = lax.all_gather(y, axis_name, axis=y.ndim - 1, tiled=True)
    return y


def row_parallel_linear_spmd(x_shard, w_shard, b=None, axis_name=AXIS_MP):
    """partial = x_shard @ W_shard; psum ≙ c_allreduce_sum; bias once."""
    y = lax.psum(x_shard @ w_shard, axis_name)
    if b is not None:
        y = y + b
    return y


def vocab_parallel_embedding_spmd(ids, table_shard, axis_name=AXIS_MP):
    """Masked local lookup + psum (the c_embedding trick)."""
    per = table_shard.shape[0]
    start = lax.axis_index(axis_name) * per
    local = ids - start
    ok = (local >= 0) & (local < per)
    safe = jnp.where(ok, local, 0)
    out = jnp.take(table_shard, safe, axis=0)
    out = jnp.where(ok[..., None], out, jnp.zeros_like(out))
    return lax.psum(out, axis_name)


def parallel_softmax_ce_spmd(logits_shard, labels, axis_name=AXIS_MP):
    """Sharded-class softmax CE (c_softmax_with_cross_entropy): global max
    and sum-exp via mp-axis collectives; only the owning shard contributes
    the label logit."""
    per = logits_shard.shape[-1]
    start = lax.axis_index(axis_name) * per
    gmax = lax.pmax(jnp.max(logits_shard, axis=-1, keepdims=True), axis_name)
    ex = jnp.exp(logits_shard - gmax)
    denom = lax.psum(jnp.sum(ex, axis=-1, keepdims=True), axis_name)
    local = labels - start
    ok = (local >= 0) & (local < per)
    safe = jnp.where(ok, local, 0)
    picked = jnp.take_along_axis(logits_shard, safe[..., None], axis=-1)[..., 0]
    picked = jnp.where(ok, picked - gmax[..., 0], 0.0)
    label_logit = lax.psum(picked, axis_name)
    return jnp.log(denom[..., 0]) - label_logit
