"""The attention route table: which of the seven routes a call takes, read
without a chip.

``attention_route`` is a pure function of shape facts and of the two things
the platform tells, so tier-1 (XLA:CPU, where ``pallas.is_available()`` is
false) can say what the TPU compiles: a threshold that moves shows here, not
first in a cell. The expected column is what the tree did before the route
had one home (recorded from its ``_sdpa`` with the ops replaced by
recorders), not a design.
"""
import pathlib
import re

import jax.numpy as jnp
import pytest

from paddle_tpu.nn.functional import attention as A

TPU = dict(pallas=True, interpret=False)
CPU = dict(pallas=False, interpret=False)
INTERPRET = dict(pallas=True, interpret=True)  # is_available() is true there

GPT2 = dict(heads=12, kv_heads=12, head_dim=64)             # gpt2-124m
LARGE = dict(heads=20, kv_heads=20, head_dim=64, cached=True)  # gpt2-large
HYBRID = dict(heads=32, kv_heads=2, head_dim=128, cached=True)  # nemotron-3


def _row(name, platform, expect, *, sq, sk, heads, kv_heads, head_dim,
         batch=2, kv_itemsize=2, cached=False, causal=False, mask_shape=None,
         mask_trainable=False, dropout=False):
    facts = dict(batch=batch, sq=sq, sk=sk, heads=heads, kv_heads=kv_heads,
                 head_dim=head_dim, kv_itemsize=kv_itemsize, cached=cached,
                 causal=causal, mask_shape=mask_shape,
                 mask_trainable=mask_trainable, dropout=dropout)
    return pytest.param(facts, platform, expect, id=name)


TPU_ROWS = [
    # uncached: training and whole-sequence forward
    _row("tpu-train-1024", TPU, "flash_packed", sq=1024, sk=1024, causal=True,
         **GPT2),
    _row("tpu-causal-512", TPU, "einsum", sq=512, sk=512, causal=True,
         **GPT2),
    _row("tpu-causal-2048", TPU, "flash_packed", sq=2048, sk=2048,
         causal=True, **GPT2),
    _row("tpu-causal-32768-slab-cap", TPU, "flash", sq=32768, sk=32768,
         causal=True, **GPT2),
    _row("tpu-mask-b1qk-1024", TPU, "flash", sq=1024, sk=1024,
         mask_shape=(2, 1, 1024, 1024), **GPT2),
    _row("tpu-trainable-2d-bias", TPU, "flash", sq=1024, sk=1024,
         mask_shape=(1024, 1024), mask_trainable=True, **GPT2),
    _row("tpu-dropout-trainable-bias", TPU, "einsum", sq=1024, sk=1024,
         mask_shape=(1024, 1024), mask_trainable=True, dropout=True, **GPT2),
    _row("tpu-mask-not-sq-sk", TPU, "einsum", sq=1024, sk=1024,
         mask_shape=(2, 1, 1, 1024), **GPT2),
    # cached: gpt2-large, 32 slots of 1024 keys in bf16
    _row("tpu-large-decode", TPU, "flash_decode", sq=1, sk=1024, **LARGE),
    _row("tpu-large-verify-5", TPU, "flash_decode", sq=5, sk=1024, **LARGE),
    _row("tpu-large-verify-8", TPU, "flash_decode", sq=8, sk=1024, **LARGE),
    _row("tpu-large-rows-9", TPU, "blockwise", sq=9, sk=1024, **LARGE),
    _row("tpu-large-chunk-64", TPU, "blockwise", sq=64, sk=1024, **LARGE),
    _row("tpu-large-bucket-128", TPU, "flash_cached", sq=128, sk=1024,
         **LARGE),
    _row("tpu-large-bucket-1024", TPU, "flash_cached", sq=1024, sk=1024,
         **LARGE),
    _row("tpu-large-decode-sk512", TPU, "einsum", sq=1, sk=512, **LARGE),
    # cached: the hybrid's grouped K/V heads, 64 slots of 2048 keys
    _row("tpu-hybrid-decode", TPU, "einsum_grouped", sq=1, sk=2048, **HYBRID),
    _row("tpu-hybrid-bucket-256", TPU, "flash_cached", sq=256, sk=2048,
         **HYBRID),
    _row("tpu-cached-dropout", TPU, "einsum", sq=128, sk=1024, dropout=True,
         **LARGE),
]

CPU_ROWS = [
    _row("cpu-causal-1024", CPU, "blockwise", sq=1024, sk=1024, causal=True,
         **GPT2),
    _row("cpu-causal-512", CPU, "einsum", sq=512, sk=512, causal=True,
         **GPT2),
    _row("cpu-noncausal-1024", CPU, "einsum", sq=1024, sk=1024, **GPT2),
    _row("cpu-large-decode", CPU, "blockwise", sq=1, sk=1024, **LARGE),
    _row("cpu-large-decode-sk512", CPU, "einsum", sq=1, sk=512, **LARGE),
    _row("cpu-large-bucket-128", CPU, "blockwise", sq=128, sk=1024, **LARGE),
    _row("cpu-hybrid-decode", CPU, "einsum_grouped", sq=1, sk=2048, **HYBRID),
]

INTERPRET_ROWS = [
    _row("interpret-causal-128", INTERPRET, "flash_packed", sq=128, sk=128,
         causal=True, **GPT2),
    _row("interpret-causal-128-dropout", INTERPRET, "einsum", sq=128, sk=128,
         causal=True, dropout=True, **GPT2),
]


@pytest.mark.parametrize("facts, platform, expect",
                         TPU_ROWS + CPU_ROWS + INTERPRET_ROWS)
def test_route_table(facts, platform, expect):
    assert A.attention_route(**facts, **platform) == expect


#: the op each route dispatches to (``flash_packed`` and ``flash`` share one
#: op: which kernel it calls is the route's ``packed`` argument)
ROUTE_OPS = {
    "flash_packed": "_sdpa_flash", "flash": "_sdpa_flash",
    "flash_cached": "_sdpa_flash_cached", "flash_decode": "_sdpa_flash_decode",
    "einsum_grouped": "_sdpa_grouped_decode", "blockwise": "_sdpa_blockwise",
    "einsum": "_sdpa_raw",
}


def record_dispatch(monkeypatch, facts, platform):
    """Run ``_sdpa`` on arrays of the row's shapes with every attention op
    replaced by a recorder; returns the names of the ops it called."""
    from paddle_tpu.ops import pallas

    monkeypatch.setattr(pallas, "is_available", lambda: platform["pallas"])
    monkeypatch.setattr(pallas, "interpret_requested",
                        lambda: platform["interpret"])
    called = []
    for op_name in set(ROUTE_OPS.values()):
        def recorder(q, *args, _name=op_name, **kwargs):
            called.append((_name, kwargs))
            return q
        monkeypatch.setattr(A, op_name, recorder)
    b, sq, sk, d = (facts[k] for k in ("batch", "sq", "sk", "head_dim"))
    kv_dtype = {2: jnp.bfloat16, 4: jnp.float32}[facts["kv_itemsize"]]
    q = jnp.zeros((b, sq, facts["heads"], d), kv_dtype)
    k = jnp.zeros((b, sk, facts["kv_heads"], d), kv_dtype)
    mask = None
    if facts["cached"]:
        mask = A.LengthMask(jnp.zeros((b, sq), jnp.int32))
    elif facts["mask_shape"] is not None:
        import paddle_tpu as paddle
        mask = paddle.zeros(list(facts["mask_shape"]), dtype="float32")
        mask.stop_gradient = not facts["mask_trainable"]
    A._sdpa(q, k, k, mask, dropout_p=0.1 if facts["dropout"] else 0.0,
            is_causal=facts["causal"], training=True)
    return called


@pytest.mark.parametrize("facts, platform, expect", CPU_ROWS)
def test_dispatch_follows_route(monkeypatch, facts, platform, expect):
    """``_sdpa`` calls the op the route names, so the table cannot drift from
    the dispatch."""
    called = record_dispatch(monkeypatch, facts, platform)
    assert [name for name, _ in called] == [ROUTE_OPS[expect]]


def test_no_flag_reaches_a_kernel():
    """No name registered in ``framework/flags.py`` is read where a kernel is
    chosen or configured: what the chip settled is a constant beside its
    measurement, not an option."""
    from paddle_tpu.framework import flags

    root = pathlib.Path(A.__file__).parents[2]
    files = sorted((root / "ops" / "pallas").glob("*.py"))
    files += [root / "ops" / "fused.py", pathlib.Path(A.__file__)]
    names = re.compile("|".join(rf"\b{re.escape(n)}\b"
                                for n in sorted(flags._REGISTRY)))
    found = []
    for path in files:
        text = path.read_text()
        assert "flag_value" not in text and "get_flags" not in text, path
        found += [(path.name, m.group()) for m in names.finditer(text)]
    # "benchmark" is a registered compatibility flag and an English word
    assert [f for f in found if f[1] != "benchmark"] == []
