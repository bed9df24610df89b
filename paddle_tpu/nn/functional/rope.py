"""Rotary position embeddings: a rotation of q and k by each token's own
position, fixed when the model is built and applied BEFORE k is cached.

A cached key is therefore never rotated again, and a cache may hold its rows
in any order (``serving/kv_cache.py``: a ring of ``window`` rows): the score
``R(p) q . R(j) k`` depends on ``p - j`` alone.

Two forms, both pairing channel ``c`` with ``c + rotary_dim / 2``
(``rotate_half``; not interleaved) over the FIRST ``rotary_dim`` channels of
a head and leaving the rest as they are:

* plain: pair ``c`` turns by ``p * theta^(-2c / rotary_dim)``;
* YaRN (arXiv:2309.00071, as ``transformers``' ``_compute_yarn_parameters``
  with ``truncate``): the pairs that turn fast keep their frequency, the
  slow ones are divided by ``factor``, a linear ramp between the pair that
  makes ``beta_fast`` turns over the ``original`` context and the one that
  makes ``beta_slow``; ``cos`` and ``sin`` are multiplied by
  ``attention_factor`` (default ``0.1 ln(factor) + 1``) on q and k alike.

:func:`rope_frequencies` is numpy and runs once, in a layer's constructor;
:func:`apply_rotary` is the traced part, in float32 whatever ``x`` is.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.dispatch import op

__all__ = ["rope_frequencies", "apply_rotary"]


def rope_frequencies(rotary_dim, theta, *, yarn=None):
    """``(inv_freq float32 [rotary_dim / 2], scale)``: the angle a position
    of 1 turns each pair by, and what ``cos`` and ``sin`` are multiplied by.

    ``yarn``: None for the plain form, else a mapping with ``factor`` and
    ``original_max_position_embeddings`` and optionally ``beta_fast`` (32),
    ``beta_slow`` (1), ``attention_factor``."""
    rotary_dim = int(rotary_dim)
    if rotary_dim <= 0 or rotary_dim % 2:
        raise ValueError(f"rotary_dim must be positive and even, "
                         f"got {rotary_dim}")
    half = rotary_dim // 2
    freq = float(theta) ** (-np.arange(half, dtype=np.float64) * 2.0
                            / rotary_dim)
    if yarn is None:
        return freq.astype(np.float32), 1.0
    factor = float(yarn["factor"])
    original = float(yarn["original_max_position_embeddings"])

    def pair_turning(rotations):  # the (fractional) pair that makes them
        return rotary_dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    lo = max(math.floor(pair_turning(float(yarn.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(pair_turning(float(yarn.get("beta_slow", 1)))),
             rotary_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - lo)
                   / ((hi if hi != lo else hi + 0.001) - lo), 0.0, 1.0)
    scale = yarn.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return (freq * (1.0 - ramp) + freq / factor * ramp).astype(
        np.float32), float(scale)


@op("apply_rotary")
def apply_rotary(x, position_ids, inv_freq, scale=1.0):
    """``x [b, s, heads, head_dim]`` rotated at ``position_ids [b, s]``; the
    channels past ``2 * len(inv_freq)`` pass through."""
    half = inv_freq.shape[0]
    with jax.named_scope("rope"):
        angle = position_ids.astype(jnp.float32)[..., None] \
            * jnp.asarray(inv_freq, jnp.float32)          # [b, s, half]
        cos = (jnp.cos(angle) * scale)[:, :, None, :]
        sin = (jnp.sin(angle) * scale)[:, :, None, :]
        x1 = x[..., :half].astype(jnp.float32)
        x2 = x[..., half:2 * half].astype(jnp.float32)
        turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
        return jnp.concatenate(
            [t.astype(x.dtype) for t in turned] + [x[..., 2 * half:]], -1)
