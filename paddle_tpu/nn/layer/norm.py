"""Norm layers (reference ``python/paddle/nn/layer/norm.py``)."""
from __future__ import annotations

import jax.numpy as jnp

from ...framework.tensor import Tensor
from .. import functional as F
from ..initializer import Constant, Normal
from .layers import Layer

__all__ = [
    "BatchNorm",
    "BatchNorm1D",
    "BatchNorm2D",
    "BatchNorm3D",
    "SyncBatchNorm",
    "LayerNorm",
    "GroupNorm",
    "InstanceNorm1D",
    "InstanceNorm2D",
    "InstanceNorm3D",
    "LocalResponseNorm",
    "SpectralNorm",
    "RMSNorm",
]


class _BatchNormBase(Layer):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None, bias_attr=None, data_format="NCHW", use_global_stats=None, name=None):
        super().__init__()
        self._num_features = num_features
        self._momentum = momentum
        self._epsilon = epsilon
        self._data_format = data_format
        self._use_global_stats = use_global_stats
        self.weight = self.create_parameter(
            shape=[num_features], attr=weight_attr, default_initializer=Constant(1.0)
        )
        self.bias = self.create_parameter(shape=[num_features], attr=bias_attr, is_bias=True)
        self.register_buffer("_mean", Tensor(jnp.zeros([num_features], self._dtype)))
        self.register_buffer("_variance", Tensor(jnp.ones([num_features], self._dtype)))

    def forward(self, input):
        return F.batch_norm(
            input,
            self._mean,
            self._variance,
            self.weight,
            self.bias,
            training=self.training,
            momentum=self._momentum,
            epsilon=self._epsilon,
            data_format=self._data_format,
            use_global_stats=self._use_global_stats,
        )

    def extra_repr(self):
        return f"num_features={self._num_features}, momentum={self._momentum}, epsilon={self._epsilon}"


class BatchNorm(_BatchNormBase):
    """Legacy fluid-style BatchNorm(num_channels) (reference
    ``fluid/dygraph/nn.py BatchNorm``) — keeps act param."""

    def __init__(self, num_channels, act=None, momentum=0.9, epsilon=1e-5, param_attr=None, bias_attr=None, dtype="float32", data_layout="NCHW", in_place=False, moving_mean_name=None, moving_variance_name=None, do_model_average_for_mean_and_var=True, use_global_stats=False, trainable_statistics=False):
        super().__init__(num_channels, momentum, epsilon, param_attr, bias_attr, data_layout, use_global_stats or None)
        self._act = act

    def forward(self, input):
        out = super().forward(input)
        if self._act:
            out = getattr(F, self._act)(out)
        return out


class BatchNorm1D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None, bias_attr=None, data_format="NCL", use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr, data_format, use_global_stats)


class BatchNorm2D(_BatchNormBase):
    pass


class BatchNorm3D(_BatchNormBase):
    def __init__(self, num_features, momentum=0.9, epsilon=1e-5, weight_attr=None, bias_attr=None, data_format="NCDHW", use_global_stats=None, name=None):
        super().__init__(num_features, momentum, epsilon, weight_attr, bias_attr, data_format, use_global_stats)


class SyncBatchNorm(_BatchNormBase):
    """Cross-replica batch norm (reference ``nn/layer/norm.py SyncBatchNorm``,
    CUDA ``sync_batch_norm_op.cu``). Under the jit/pmap path the mean/var
    reduction happens over the mesh data axis via psum (see
    paddle_tpu.distributed); in single-device eager it equals BatchNorm."""

    @staticmethod
    def _candidate_axes():
        """Mesh axes the cross-replica reduction may ride: the fleet
        data-parallel axis when a hybrid topology is initialized, the 'dp'
        convention, and the default world group's axis."""
        axes = []
        try:
            from ...distributed import fleet

            hcg = fleet.get_hybrid_communicate_group()
            if hcg is not None:
                axes.append(hcg.get_data_parallel_group().axis_name)
        except Exception:
            pass
        axes.append("dp")
        try:
            from ...distributed.collective import _default_group

            axes.append(_default_group().axis_name)
        except Exception:
            pass
        return axes

    def forward(self, input):
        from ...distributed import collective as coll

        if self.training:
            for axis_name in self._candidate_axes():
                if coll._in_spmd(axis_name):
                    return self._spmd_forward(input, axis_name)
        return super().forward(input)

    def _spmd_forward(self, input, axis_name):
        from ...ops.dispatch import op as _op

        axis = 1
        eps = self._epsilon

        @_op("sync_batch_norm")
        def _sync_bn(x, w, b):
            axes = tuple(i for i in range(x.ndim) if i != axis)
            from jax import lax

            local_mean = jnp.mean(x, axis=axes)
            local_sq = jnp.mean(jnp.square(x), axis=axes)
            mean = lax.pmean(local_mean, axis_name)
            sq = lax.pmean(local_sq, axis_name)
            var = sq - jnp.square(mean)
            shape = [1] * x.ndim
            shape[axis] = x.shape[axis]
            scale = w.reshape(shape) * lax.rsqrt(var.reshape(shape) + eps)
            out = x * scale + (b.reshape(shape) - mean.reshape(shape) * scale)
            # running buffers store the *unbiased* variance over the global
            # batch (matching F.batch_norm), normalization uses biased
            n_g = (x.size // x.shape[axis]) * lax.axis_size(axis_name)
            var_unbiased = var * (n_g / max(n_g - 1, 1))
            return out, mean, var_unbiased

        out, mean, var = _sync_bn(input, self.weight, self.bias)
        # Running-stat update with the cross-replica batch stats, so eval
        # (which reads the buffers via super().forward) sees learned
        # population statistics. Inside a shard_map region these are traced
        # values: the enclosing functionalization (CompiledStep state
        # threading, or a shard_map body that returns the buffers) carries
        # them out — the same contract as every other mutable buffer.
        mom = self._momentum
        mv = mean._value if isinstance(mean, Tensor) else mean
        vv = var._value if isinstance(var, Tensor) else var
        self._mean._value = (
            mom * self._mean._value + (1.0 - mom) * mv.astype(self._mean._value.dtype)
        )
        self._variance._value = (
            mom * self._variance._value
            + (1.0 - mom) * vv.astype(self._variance._value.dtype)
        )
        return out

    @classmethod
    def convert_sync_batchnorm(cls, layer):
        out = layer
        if isinstance(layer, _BatchNormBase) and not isinstance(layer, SyncBatchNorm):
            out = SyncBatchNorm(layer._num_features, layer._momentum, layer._epsilon, data_format=layer._data_format)
            out.weight = layer.weight
            out.bias = layer.bias
            out._mean = layer._mean
            out._variance = layer._variance
        for name, sub in layer._sub_layers.items():
            out._sub_layers[name] = cls.convert_sync_batchnorm(sub)
        return out


class LayerNorm(Layer):
    def __init__(self, normalized_shape, epsilon=1e-5, weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        if weight_attr is False:
            self.weight = None
        else:
            self.weight = self.create_parameter(
                shape=self._normalized_shape, attr=weight_attr, default_initializer=Constant(1.0)
            )
        if bias_attr is False:
            self.bias = None
        else:
            self.bias = self.create_parameter(shape=self._normalized_shape, attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.layer_norm(input, self._normalized_shape, self.weight, self.bias, self._epsilon)

    def extra_repr(self):
        return f"normalized_shape={self._normalized_shape}, epsilon={self._epsilon}"


class RMSNorm(Layer):
    """RMS norm (no reference equivalent layer; standard for LLM families)."""

    def __init__(self, normalized_shape, epsilon=1e-6, weight_attr=None, name=None):
        super().__init__()
        if isinstance(normalized_shape, int):
            normalized_shape = [normalized_shape]
        self._normalized_shape = list(normalized_shape)
        self._epsilon = epsilon
        self.weight = self.create_parameter(
            shape=self._normalized_shape, attr=weight_attr, default_initializer=Constant(1.0)
        )

    def forward(self, x):
        from ...ops.dispatch import op as _op

        eps = self._epsilon

        @_op("rms_norm")
        def _rms(xv, w):
            from jax import lax

            # float32 inside, the input's dtype outside: a bfloat16
            # stream is not squared in bfloat16, nor promoted by the gain
            xf = xv.astype(jnp.float32)
            ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
            return (xf * lax.rsqrt(ms + eps)
                    * w.astype(jnp.float32)).astype(xv.dtype)

        return _rms(x, self.weight)


class GroupNorm(Layer):
    def __init__(self, num_groups, num_channels, epsilon=1e-5, weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._num_groups = num_groups
        self._num_channels = num_channels
        self._epsilon = epsilon
        self._data_format = data_format
        self.weight = None if weight_attr is False else self.create_parameter(
            shape=[num_channels], attr=weight_attr, default_initializer=Constant(1.0)
        )
        self.bias = None if bias_attr is False else self.create_parameter(
            shape=[num_channels], attr=bias_attr, is_bias=True
        )

    def forward(self, input):
        return F.group_norm(input, self._num_groups, self._epsilon, self.weight, self.bias, self._data_format)


class _InstanceNormBase(Layer):
    def __init__(self, num_features, epsilon=1e-5, momentum=0.9, weight_attr=None, bias_attr=None, data_format="NCHW", name=None):
        super().__init__()
        self._epsilon = epsilon
        if weight_attr is False or bias_attr is False:
            self.scale = None
            self.bias = None
        else:
            self.scale = self.create_parameter(
                shape=[num_features], attr=weight_attr, default_initializer=Constant(1.0)
            )
            self.bias = self.create_parameter(shape=[num_features], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.instance_norm(input, weight=self.scale, bias=self.bias, eps=self._epsilon)


class InstanceNorm1D(_InstanceNormBase):
    pass


class InstanceNorm2D(_InstanceNormBase):
    pass


class InstanceNorm3D(_InstanceNormBase):
    pass


class LocalResponseNorm(Layer):
    def __init__(self, size, alpha=0.0001, beta=0.75, k=1.0, data_format="NCHW", name=None):
        super().__init__()
        self.size = size
        self.alpha = alpha
        self.beta = beta
        self.k = k
        self.data_format = data_format

    def forward(self, input):
        return F.local_response_norm(input, self.size, self.alpha, self.beta, self.k, self.data_format)


class SpectralNorm(Layer):
    def __init__(self, weight_shape, dim=0, power_iters=1, epsilon=1e-12, dtype="float32"):
        super().__init__()
        self._dim = dim
        self._power_iters = power_iters
        self._epsilon = epsilon
        import numpy as np

        h = weight_shape[dim]
        w = int(np.prod(weight_shape)) // h
        self.weight_u = self.create_parameter(shape=[h], default_initializer=Normal(0, 1))
        self.weight_u.stop_gradient = True
        self.weight_v = self.create_parameter(shape=[w], default_initializer=Normal(0, 1))
        self.weight_v.stop_gradient = True

    def forward(self, x):
        return F.spectral_norm(x, self.weight_u, self.weight_v, self._dim, self._power_iters, self._epsilon)
