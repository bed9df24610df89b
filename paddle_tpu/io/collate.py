"""Batch collation (reference ``python/paddle/fluid/dataloader/collate.py``).
Collates to device Tensors; numbers->stacked arrays, dicts/sequences recursed."""
from __future__ import annotations

import numbers

import numpy as np

from ..framework.tensor import Tensor

__all__ = ["default_collate_fn", "numpy_collate_fn", "default_convert_fn"]


def _collate(batch, leaf):
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return leaf(np.stack(batch, axis=0))
    if isinstance(sample, Tensor):
        return leaf(np.stack([np.asarray(s._value) for s in batch], axis=0))
    if isinstance(sample, numbers.Number):
        return leaf(np.asarray(batch))
    if isinstance(sample, (str, bytes)):
        return batch
    if isinstance(sample, dict):
        return {k: _collate([s[k] for s in batch], leaf) for k in sample}
    if isinstance(sample, (list, tuple)):
        return [_collate(list(fields), leaf) for fields in zip(*batch)]
    raise TypeError(f"cannot collate batch of {type(sample)}")


def default_collate_fn(batch):
    return _collate(batch, Tensor)


def numpy_collate_fn(batch):
    """``default_collate_fn`` with ndarray leaves: what a worker PROCESS runs
    in its place. A ``Tensor`` holds a jax array, and making one in a worker
    would initialise a JAX backend there — on a TPU host the parent holds
    the chip, and a second process reaching for it fails or hangs. The
    parent wraps the leaves (``tensors_from_numpy``)."""
    return _collate(batch, lambda a: a)


def tensors_from_numpy(obj):
    """Parent side of ``numpy_collate_fn``: ndarray leaves -> Tensors."""
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, dict):
        return {k: tensors_from_numpy(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [tensors_from_numpy(v) for v in obj]
    return obj


def default_convert_fn(batch):
    if isinstance(batch, (Tensor, np.ndarray)):
        return Tensor(batch)
    if isinstance(batch, (list, tuple)):
        return [default_convert_fn(b) for b in batch]
    return batch
