"""Seq-major packed flash attention: parity vs the einsum reference and
the layout-swapping kernel (interpret mode, CPU). Reference capability:
``paddle/fluid/operators/fused/fused_attention_op.cu``."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.flash_attention_packed import (
    flash_attention_packed,
    supports,
)

B, S, H, D = 2, 256, 4, 64


def _inputs(dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, H * D), dtype)
    k = jax.random.normal(ks[1], (B, S, H * D), dtype)
    v = jax.random.normal(ks[2], (B, S, H * D), dtype)
    bias = jax.random.normal(ks[3], (S, S), jnp.float32) * 0.5
    return q, k, v, bias


def _ref(q, k, v, causal=False, bias=None):
    qh = q.reshape(B, S, H, D)
    kh = k.reshape(B, S, H, D)
    vh = v.reshape(B, S, H, D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(D)
    if bias is not None:
        logits = logits + bias[None, None]
    if causal:
        m = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(m, logits, -1e30)
    p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(B, S, H * D)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_bias", [False, True])
def test_forward_parity(causal, use_bias):
    q, k, v, bias = _inputs()
    bb = bias if use_bias else None
    got = flash_attention_packed(q, k, v, H, bias=bb, causal=causal,
                                 block_q=128, block_k=128, interpret=True)
    want = _ref(q, k, v, causal, bb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_grad_parity_vs_einsum():
    q, k, v, _ = _inputs()
    co = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def f_packed(q, k, v):
        out = flash_attention_packed(q, k, v, H, causal=True, block_q=128,
                                     block_k=128, bwd_block=128,
                                     interpret=True)
        return jnp.vdot(out, co)

    def f_ref(q, k, v):
        return jnp.vdot(_ref(q, k, v, causal=True), co)

    gp = jax.grad(f_packed, (0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", gp, gr):
        err = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert err < 1e-4, (name, err)


def test_bwd_block_differs_from_fwd():
    """bwd re-tiles at its own block size (VMEM headroom); gradients must
    not depend on the choice."""
    q, k, v, _ = _inputs()
    co = jax.random.normal(jax.random.key(5), q.shape, jnp.float32)

    def grads(bwd_block):
        def f(q, k, v):
            out = flash_attention_packed(q, k, v, H, causal=True,
                                         block_q=256, block_k=256,
                                         bwd_block=bwd_block, interpret=True)
            return jnp.vdot(out, co)
        return jax.grad(f, (0, 1, 2))(q, k, v)

    for a, b in zip(grads(128), grads(256)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_matches_layout_swapping_kernel():
    q, k, v, _ = _inputs()
    got = flash_attention_packed(q, k, v, H, causal=True, block_q=128,
                                 block_k=128, interpret=True)
    want = flash_attention(
        q.reshape(B, S, H, D), k.reshape(B, S, H, D), v.reshape(B, S, H, D),
        causal=True, block_q=128, block_k=128, interpret=True,
    ).reshape(B, S, H * D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_supports_gate():
    assert supports(1024, 1024, 12, 768)        # d=64: two heads per group
    assert supports(1024, 1024, 6, 768)         # d=128: one head per group
    assert supports(256, 256, 8, 256)           # d=32: four heads per group
    assert not supports(100, 100, 4, 256)       # seq not 128-tileable
    assert not supports(256, 256, 5, 240)       # d=48: no 128-lane grouping
    assert not supports(256, 256, 3, 288)       # d=96: no 128-lane grouping


def test_router_prefers_packed(monkeypatch):
    """F.sdpa routes mask-free large-seq attention through the packed
    kernel (no layout transposes)."""
    import paddle_tpu  # noqa: F401
    from paddle_tpu.nn.functional import attention as A
    from paddle_tpu.ops.pallas import flash_attention_packed as packed_mod

    called = {}
    orig = packed_mod.flash_attention_packed

    def spy(*a, **kw):
        called["hit"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(packed_mod, "flash_attention_packed", spy)
    q = jnp.ones((1, 256, 4, 64), jnp.float32)
    with __import__("paddle_tpu").ops.pallas.interpret_mode():
        A._sdpa(q, q, q, is_causal=True)
    assert called.get("hit")


def _ref_rect(q, k, v, h, causal):
    """Einsum reference for sq != sk (bottom-right-aligned causal)."""
    b, sq, e = q.shape
    sk = k.shape[1]
    d = e // h
    qh = q.reshape(b, sq, h, d)
    kh = k.reshape(b, sk, h, d)
    vh = v.reshape(b, sk, h, d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) / np.sqrt(d)
    if causal:
        m = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(m, logits, -1e30)
        p = jax.nn.softmax(logits, -1)
        # fully-masked rows: softmax of all -1e30 is uniform garbage; the
        # kernel contract is output 0 for those rows
        p = jnp.where(m.any(-1)[None, None, :, None], p, 0.0)
    else:
        p = jax.nn.softmax(logits, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vh).reshape(b, sq, e)


def test_multi_tile_causal_boundary_inside_tile():
    """Advisor regression: sq > sk causal where the masked-row boundary sits
    INSIDE a q tile (offset=-128, block_q=256) — the multi-tile forward must
    zero fully-masked rows, not emit a spurious uniform softmax."""
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (1, 512, 2 * 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 384, 2 * 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 384, 2 * 64), jnp.float32)
    out = flash_attention_packed(q, k, v, 2, causal=True, block_q=256,
                                 block_k=128, interpret=True)
    # offset = -128: rows 0..127 attend nothing (inside tile qi=0)
    np.testing.assert_array_equal(np.asarray(out[0, :128]), 0.0)
    want = _ref_rect(q, k, v, 2, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_multi_tile_causal_boundary_grads_zero():
    """Advisor regression: the fused backward must give zero dq for
    fully-masked rows and zero spurious dk/dv from them."""
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (1, 512, 2 * 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 384, 2 * 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 384, 2 * 64), jnp.float32)
    co = jax.random.normal(jax.random.key(8), q.shape, jnp.float32)

    def f_packed(q, k, v):
        out = flash_attention_packed(q, k, v, 2, causal=True, block_q=256,
                                     block_k=128, bwd_block=256,
                                     interpret=True)
        return jnp.vdot(out, co)

    def f_ref(q, k, v):
        return jnp.vdot(_ref_rect(q, k, v, 2, causal=True), co)

    gp = jax.grad(f_packed, (0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, (0, 1, 2))(q, k, v)
    np.testing.assert_array_equal(np.asarray(gp[0][0, :128]), 0.0)
    for name, a, b in zip("qkv", gp, gr):
        err = float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-9))
        assert err < 1e-4, (name, err)


def test_bias_fully_masked_rows_multi_tile():
    """Review regression: a shared padding bias can fully mask rows in ANY
    tile (not just causal-boundary ones) — the multi-tile forward and
    fused backward must zero those rows even on interior/non-causal
    paths."""
    ks = jax.random.split(jax.random.key(21), 3)
    q = jax.random.normal(ks[0], (1, 512, 2 * 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 512, 2 * 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 512, 2 * 64), jnp.float32)
    # rows 0..63 are pad queries: every key masked for them
    keep = np.ones((512, 512), bool)
    keep[:64, :] = False
    co = jax.random.normal(jax.random.key(22), q.shape, jnp.float32)

    def f(q, k, v):
        out = flash_attention_packed(q, k, v, 2, bias=jnp.asarray(keep),
                                     causal=False, block_q=256, block_k=128,
                                     bwd_block=256, interpret=True)
        return jnp.vdot(out, co), out

    (_, out), grads = jax.value_and_grad(f, (0, 1, 2), has_aux=True)(q, k, v)
    np.testing.assert_array_equal(np.asarray(out[0, :64]), 0.0)
    np.testing.assert_array_equal(np.asarray(grads[0][0, :64]), 0.0)
    assert np.abs(np.asarray(out[0, 64:])).max() > 0


def test_single_tile_causal_fully_masked_rows():
    """Review regression: sq > sk causal with one k tile — query rows with
    no visible keys must output 0 (not the mean of v)."""
    q = jnp.ones((1, 256, 2 * 64), jnp.float32)
    k = jax.random.normal(jax.random.key(0), (1, 128, 2 * 64), jnp.float32)
    v = jax.random.normal(jax.random.key(1), (1, 128, 2 * 64), jnp.float32)
    out = flash_attention_packed(q, k, v, 2, causal=True, block_q=128,
                                 block_k=128, interpret=True)
    # offset = sk - sq = -128: rows 0..127 attend nothing -> zeros
    np.testing.assert_array_equal(np.asarray(out[0, :128]), 0.0)
    assert np.abs(np.asarray(out[0, 128:])).max() > 0
