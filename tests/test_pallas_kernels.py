"""Pallas kernel parity tests (interpreter mode on the CPU mesh).

Mirrors the reference's fused-op tests (e.g.
``unittests/test_fused_attention_op.py``): the fused kernel must match the
naive composition in both forward values and gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas.flash_attention import flash_attention
from paddle_tpu.ops.pallas.layer_norm import fused_layer_norm


def _ref_attention(q, k, v, bias=None, causal=False):
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cmask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cmask, logits, -1e30)
    if bias is not None:
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _rand_qkv(b=2, s=256, h=2, d=64, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32)) * 0.3
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_parity(causal):
    q, k, v = _rand_qkv()
    with pallas.interpret_mode():
        out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = _ref_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_forward_bias():
    q, k, v = _rand_qkv()
    rng = np.random.RandomState(1)
    bias = jnp.asarray(rng.randn(1, 1, 256, 256).astype(np.float32))
    with pallas.interpret_mode():
        out = flash_attention(q, k, v, bias=bias, block_q=128, block_k=128)
    ref = _ref_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_forward_bool_padding_mask():
    q, k, v = _rand_qkv()
    keep = np.ones((1, 1, 256, 256), bool)
    keep[..., 200:] = False  # mask out trailing keys
    with pallas.interpret_mode():
        out = flash_attention(q, k, v, bias=jnp.asarray(keep),
                              block_q=128, block_k=128)
    ref = _ref_attention(q, k, v, bias=jnp.where(jnp.asarray(keep), 0.0, -1e30))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_parity(causal):
    q, k, v = _rand_qkv(s=128)

    def loss_flash(q, k, v):
        with pallas.interpret_mode():
            out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
        return jnp.sum(out * jnp.cos(out))

    def loss_ref(q, k, v):
        out = _ref_attention(q, k, v, causal=causal)
        return jnp.sum(out * jnp.cos(out))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-5, rtol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal})",
        )


def test_flash_multi_kblock_grad():
    # sequence spanning several k blocks exercises the scratch accumulators
    q, k, v = _rand_qkv(s=512)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out**2)
        return f

    with pallas.interpret_mode():
        gf = jax.grad(
            loss(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128)),
            argnums=(0, 1, 2),
        )(q, k, v)
    gr = jax.grad(
        loss(lambda q, k, v: _ref_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-4)


def test_flash_causal_cross_length():
    """sq != sk: causal alignment must match the einsum path's bottom-right
    convention (tril with k = sk - sq)."""
    rng = np.random.RandomState(3)
    b, h, d = 2, 2, 64
    q = jnp.asarray(rng.randn(b, 128, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, 256, h, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, 256, h, d).astype(np.float32)) * 0.3
    with pallas.interpret_mode():
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128)
    ref = _ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flash_causal_fully_masked_rows():
    """Advisor regression (layout-swapping kernel): causal sq > sk with the
    masked-row boundary inside a q tile (offset=-128, block_q=256) — fully
    masked rows must emit output 0 and zero gradients, not a uniform
    softmax over v."""
    rng = np.random.RandomState(11)
    b, h, d = 1, 2, 64
    q = jnp.asarray(rng.randn(b, 512, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, 384, h, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, 384, h, d).astype(np.float32)) * 0.3

    def masked_ref(q, k, v):
        out = _ref_attention(q, k, v, causal=True)
        sq, sk = q.shape[1], k.shape[1]
        vis = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq).any(-1)
        return jnp.where(vis[None, :, None, None], out, 0.0)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out**2), out
        return f

    with pallas.interpret_mode():
        (val, out), gf = jax.value_and_grad(
            loss(lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=256, block_k=128)),
            argnums=(0, 1, 2), has_aux=True,
        )(q, k, v)
    np.testing.assert_array_equal(np.asarray(out[:, :128]), 0.0)
    np.testing.assert_array_equal(np.asarray(gf[0][:, :128]), 0.0)
    (_, ref), gr = jax.value_and_grad(loss(masked_ref), argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    for a, bb in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=5e-5, rtol=5e-4)


def test_flash_causal_fully_masked_rows_dbias():
    """Review regression: the trainable-bias backward must also zero
    fully-masked causal rows — dbias on those rows is exactly 0 (the
    forward output there is constant 0)."""
    rng = np.random.RandomState(13)
    b, h, d = 1, 2, 64
    q = jnp.asarray(rng.randn(b, 256, h, d).astype(np.float32)) * 0.3
    k = jnp.asarray(rng.randn(b, 128, h, d).astype(np.float32)) * 0.3
    v = jnp.asarray(rng.randn(b, 128, h, d).astype(np.float32)) * 0.3
    bias = jnp.asarray(rng.randn(256, 128).astype(np.float32)) * 0.1

    def loss(bias):
        with pallas.interpret_mode():
            out = flash_attention(q, k, v, bias=bias, causal=True,
                                  block_q=256, block_k=128, bias_grad=True)
        return jnp.sum(out**2)

    dbias = jax.grad(loss)(bias)
    # offset = -128: rows 0..127 attend nothing
    np.testing.assert_array_equal(np.asarray(dbias[:128]), 0.0)
    assert np.abs(np.asarray(dbias[128:])).max() > 0


def test_bn_running_stats_keep_declared_dtype():
    """Review regression: bf16 running mean/var must not get silently
    promoted to fp32 by the (fp32-internal) training-stat update."""
    import paddle_tpu as paddle

    bn = paddle.nn.BatchNorm2D(3)
    bn.to(dtype="bfloat16")
    bn.train()
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(2, 3, 4, 4).astype(np.float32)
    ).astype("bfloat16")
    bn(x)
    assert str(bn._mean.dtype).endswith("bfloat16"), bn._mean.dtype
    assert str(bn._variance.dtype).endswith("bfloat16"), bn._variance.dtype


def test_sdpa_broadcast_padding_mask_routes_to_einsum():
    """(b,1,1,sk) key-padding masks can't stream through the flash kernel;
    routing must fall back to the broadcasting einsum path, not crash."""
    import paddle_tpu  # noqa: F401
    from paddle_tpu.framework.tensor import Tensor
    import paddle_tpu.nn.functional as F

    q, k, v = _rand_qkv(s=128)
    mask = np.zeros((2, 1, 1, 128), np.float32)
    mask[..., 100:] = -1e30
    with pallas.interpret_mode():
        out = F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v), attn_mask=Tensor(mask)
        )
    ref = _ref_attention(q, k, v, bias=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_fused_layer_norm_parity():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 37, 256).astype(np.float32))
    gamma = jnp.asarray(rng.randn(256).astype(np.float32))
    beta = jnp.asarray(rng.randn(256).astype(np.float32))

    def ref(x, gamma, beta):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * gamma + beta

    with pallas.interpret_mode():
        out = fused_layer_norm(x, gamma, beta, eps=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, gamma, beta)),
                               atol=1e-5, rtol=1e-5)

    def loss_fused(x, gamma, beta):
        with pallas.interpret_mode():
            return jnp.sum(fused_layer_norm(x, gamma, beta, eps=1e-5) ** 2)

    def loss_ref(x, gamma, beta):
        return jnp.sum(ref(x, gamma, beta) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b, name in zip(gf, gr, ["dx", "dgamma", "dbeta"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=1e-4, err_msg=name)


def test_flash_seq_384_uses_128_block():
    """128-aligned lengths that aren't multiples of the preferred 256 block
    must still take the flash path (block falls back to 128)."""
    from paddle_tpu.ops.pallas.flash_attention import supports

    assert supports(384, 384, 64)
    q, k, v = _rand_qkv(s=384, seed=7)
    with pallas.interpret_mode():
        out = flash_attention(q, k, v, causal=True)
    ref = _ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_fused_layer_norm_multiblock_grads():
    """rows > BLOCK_ROWS exercises the cross-block dgamma/dbeta accumulation
    (init-at-block-0 + revisited output block)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(700, 128).astype(np.float32))  # 3 row blocks
    gamma = jnp.asarray(rng.randn(128).astype(np.float32))
    beta = jnp.asarray(rng.randn(128).astype(np.float32))

    def ref(x, gamma, beta):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * gamma + beta

    def loss_fused(x, gamma, beta):
        with pallas.interpret_mode():
            return jnp.sum(fused_layer_norm(x, gamma, beta, eps=1e-5) ** 2)

    def loss_ref(x, gamma, beta):
        return jnp.sum(ref(x, gamma, beta) ** 2)

    gf = jax.grad(loss_fused, argnums=(0, 1, 2))(x, gamma, beta)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(x, gamma, beta)
    for a, b, name in zip(gf, gr, ["dx", "dgamma", "dbeta"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-4, err_msg=name)


def test_sdpa_routes_to_flash_under_interpret():
    """F.scaled_dot_product_attention picks the Pallas path when available."""
    import paddle_tpu  # noqa: F401  (registers ops)
    from paddle_tpu.framework.tensor import Tensor
    import paddle_tpu.nn.functional as F

    q, k, v = _rand_qkv(s=128)
    with pallas.interpret_mode():
        out = F.scaled_dot_product_attention(
            Tensor(q), Tensor(k), Tensor(v), is_causal=True
        )
    ref = _ref_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out._value), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_sdpa_dropout_actually_drops():
    """dropout_p must change the output in training (was a silent no-op)."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    q, k, v = _rand_qkv(s=64)  # small seq -> einsum path
    out_nodrop = F.scaled_dot_product_attention(
        Tensor(q), Tensor(k), Tensor(v), dropout_p=0.0, training=True
    )
    out_drop = F.scaled_dot_product_attention(
        Tensor(q), Tensor(k), Tensor(v), dropout_p=0.5, training=True
    )
    diff = np.abs(np.asarray(out_drop._value) - np.asarray(out_nodrop._value)).max()
    assert diff > 1e-3, "attention dropout had no effect"
    # eval mode: dropout disabled
    out_eval = F.scaled_dot_product_attention(
        Tensor(q), Tensor(k), Tensor(v), dropout_p=0.5, training=False
    )
    np.testing.assert_allclose(
        np.asarray(out_eval._value), np.asarray(out_nodrop._value), atol=1e-6
    )


# ---------------------------------------------------------------------------
# length-masked (cached) kernel, and kernels on a device mesh
# ---------------------------------------------------------------------------
def _ref_cached(q, k, v, q_pos, kv_len):
    from paddle_tpu.nn.functional import LengthMask

    bias = LengthMask(q_pos, kv_len).additive(k.shape[1], jnp.float32)
    return _ref_attention(q, k, v, bias=bias)


@pytest.mark.parametrize("batch", [1, 3])
def test_flash_cached_parity_per_batch_kv_len(batch):
    """Every batch entry masks by its OWN kv_len (read from the
    scalar-prefetched vector at program_id(0))."""
    from paddle_tpu.ops.pallas import flash_attention_cached

    q, _, _ = _rand_qkv(b=batch, s=128, seed=1)
    _, k, v = _rand_qkv(b=batch, s=256, seed=2)
    q_pos = np.tile(100 + np.arange(128, dtype=np.int32), (batch, 1))
    kv_len = np.asarray([256, 130, 180][:batch], np.int32)
    with pallas.interpret_mode():
        out = flash_attention_cached(q, k, v, q_pos, kv_len,
                                     block_q=128, block_k=128)
    ref = _ref_cached(q, k, v, q_pos, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _decode_case(batch, pos, sq=1, sk=512, dtype=jnp.float32):
    """Decode-shaped operands: row 0 queries ``pos``; the other rows hold
    lengths of their own, row 1 an idle slot (position 0: only the key just
    written) and row 2 no valid key at all."""
    q, _, _ = _rand_qkv(b=batch, s=sq, seed=5)
    _, k, v = _rand_qkv(b=batch, s=sk, seed=6)
    pos0 = np.asarray([pos, 0, -sq, 300][:batch], np.int32)
    q_pos = pos0[:, None] + np.arange(sq, dtype=np.int32)[None, :]
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), q_pos


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pos", [0, 1, 127, 128, 511])
@pytest.mark.parametrize("batch", [1, 4])
def test_flash_decode_parity(batch, pos, dtype):
    """One query row per cache row, each row masked by its OWN position
    (``pos`` sits before, on and after a block boundary and on the last
    slot); blocks past the live length are neither fetched nor computed.
    Same values as the dense reference and as the blockwise scan; a row
    with no valid key gives zeros, not NaN."""
    from paddle_tpu.nn.functional.attention import _sdpa_blockwise
    from paddle_tpu.ops.pallas.flash_decode import flash_attention_decode

    q, k, v, q_pos = _decode_case(batch, pos, dtype=jnp.dtype(dtype))
    with pallas.interpret_mode():
        out = flash_attention_decode(q, k, v, q_pos, block_k=128)
    assert out.shape == q.shape and out.dtype == q.dtype
    out = np.asarray(out, np.float32)
    f32 = lambda x: x.astype(jnp.float32)
    ref = np.array(_ref_cached(f32(q), f32(k), f32(v), q_pos, None))
    scan = np.asarray(_sdpa_blockwise.raw(q, k, v, q_pos, None, block_q=1,
                                          block_k=128), np.float32)
    if batch > 2:
        ref[2] = 0.0  # the dense softmax is uniform over a fully masked row
        np.testing.assert_array_equal(out[2], np.zeros_like(out[2]))
    tol = 2e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(out, ref, atol=tol, rtol=tol)
    np.testing.assert_allclose(out, scan, atol=tol, rtol=tol)


@pytest.mark.parametrize("sq,with_kv_len", [(5, False), (8, False),
                                            (1, True), (5, True)])
def test_flash_decode_verify_window_and_kv_len(sq, with_kv_len):
    """Speculative verify's window (``spec_k + 1`` rows, each with its own
    position) takes the same kernel; an explicit ``kv_len`` bounds every row
    of its batch entry (0: nothing valid, zeros)."""
    from paddle_tpu.ops.pallas.flash_decode import flash_attention_decode

    q, k, v, q_pos = _decode_case(4, 250, sq=sq)
    q_pos = np.maximum(q_pos, 0)
    kv_len = np.asarray([200, 512, 0, 301], np.int32) if with_kv_len else None
    with pallas.interpret_mode():
        out = np.asarray(flash_attention_decode(q, k, v, q_pos, kv_len))
    ref = np.array(_ref_cached(q, k, v, q_pos, kv_len))
    if with_kv_len:
        ref[2] = 0.0
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


_DEAD = {"first": range(0, 9), "middle": range(11, 23),
         "last": range(20, 32), "all": range(32)}


@pytest.mark.parametrize("with_kv_len", [False, True],
                         ids=["no_kv_len", "kv_len"])
@pytest.mark.parametrize("sq", [1, 5, 8])
@pytest.mark.parametrize("dead", list(_DEAD))
def test_flash_decode_dead_slots(dead, sq, with_kv_len):
    """The serving engine's 32 slots, some without a request (``q_pos`` −1
    in every row): the grid visits the live (slot, block) pairs alone,
    wherever the dead slots lie. A live slot's rows are BIT FOR BIT what the
    kernel that visited every slot gave (``flash_decode_slot_grid``); a dead
    slot's rows are exactly zero, never what the output buffer held."""
    from flash_decode_slot_grid import flash_attention_decode_slot_grid
    from paddle_tpu.ops.pallas.flash_decode import (flash_attention_decode,
                                                    live_pairs)

    b, sk, block_k = 32, 384, 128
    q, _, _ = _rand_qkv(b=b, s=sq, seed=7)
    _, k, v = _rand_qkv(b=b, s=sk, seed=8)
    rng = np.random.RandomState(9)
    pos0 = rng.randint(0, sk - sq + 1, b).astype(np.int32)
    pos0[:4] = [0, block_k - 1, block_k, sk - sq]  # on the blocks' edges
    q_pos = pos0[:, None] + np.arange(sq, dtype=np.int32)[None, :]
    is_dead = np.zeros(b, bool)
    is_dead[list(_DEAD[dead])] = True
    q_pos[is_dead] = -1
    kv_len = None
    if with_kv_len:  # some bounds cut below the positions, none kills a slot
        kv_len = np.maximum(1, pos0 + sq - rng.randint(0, 3, b) * 100
                            ).astype(np.int32)
    with pallas.interpret_mode():
        out = np.asarray(flash_attention_decode(q, k, v, q_pos, kv_len,
                                                block_k=block_k))
    want = np.asarray(flash_attention_decode_slot_grid(
        q, k, v, q_pos, kv_len, block_k=block_k))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[~is_dead], want[~is_dead])
    np.testing.assert_array_equal(out[is_dead], np.zeros_like(out[is_dead]))
    # the list the grid walks: one pair a live block, slot by slot, and past
    # its end the last pair again (no block index changes: nothing fetched)
    bound = q_pos.max(axis=1) if kv_len is None else np.minimum(
        q_pos.max(axis=1), kv_len - 1)
    pairs = [(i, j) for i in range(b) if not is_dead[i]
             for j in range(bound[i] // block_k + 1)]
    slot, block, count = live_pairs(
        jnp.asarray(np.where(is_dead[:, None], -1, bound[:, None])), block_k,
        sk // block_k)
    assert int(count[0]) == len(pairs) and slot.shape == (b * 3,)
    got = list(zip(np.asarray(slot).tolist(), np.asarray(block).tolist()))
    assert got[:len(pairs)] == pairs
    assert set(got[len(pairs):]) <= {pairs[-1] if pairs else (b - 1, 0)}


def test_flash_decode_refuses_what_it_cannot_tile():
    from paddle_tpu.ops.pallas.flash_decode import (flash_attention_decode,
                                                    supports_decode)

    assert supports_decode(1, 1024, 20, 64) and supports_decode(8, 128, 2, 64)
    assert not supports_decode(9, 1024, 20, 64)      # a prefill chunk
    assert not supports_decode(1, 1000, 20, 64)      # no 128-aligned block
    assert not supports_decode(1, 1024, 512, 128)    # tiles over VMEM
    q, k, v, q_pos = _decode_case(1, 3, sq=9, sk=128)
    with pytest.raises(ValueError, match="seq_q"):
        flash_attention_decode(q, k, v, q_pos, interpret=True)


def _dp_mesh(n=4):
    from paddle_tpu.distributed.mesh import build_mesh

    return build_mesh({"dp": n})


def test_kernels_partition_over_the_step_mesh():
    """On a multi-device mesh the kernel entry points wrap themselves in a
    shard_map over the batch axes (GSPMD cannot split a Mosaic kernel):
    same values and gradients as the XLA formulations, gamma/beta
    gradients summed over the shards, outputs still batch-sharded."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas.flash_attention_packed import (
        flash_attention_packed)
    from paddle_tpu.ops.partition import partition_scope

    mesh = _dp_mesh()
    rows = NamedSharding(mesh, P("dp"))
    b, s, h, d = 8, 128, 2, 64
    rng = np.random.RandomState(0)
    x = rng.randn(b, s, h * d).astype(np.float32)
    gamma = rng.randn(h * d).astype(np.float32)
    beta = rng.randn(h * d).astype(np.float32)

    def block(x, gamma, beta):
        y = fused_layer_norm(x, gamma, beta)
        return flash_attention_packed(y, y, y, h, causal=True)

    def block_ref(x, gamma, beta):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = ((x - mu) / jnp.sqrt(var + 1e-5) * gamma + beta)
        y4 = y.reshape(b, s, h, d)
        return _ref_attention(y4, y4, y4, causal=True).reshape(b, s, h * d)

    def sharded(x, gamma, beta):
        with partition_scope((mesh, ("dp",))):
            return jax.value_and_grad(
                lambda *a: jnp.sum(block(*a) ** 2), argnums=(0, 1, 2))(
                    x, gamma, beta)

    with pallas.interpret_mode():
        assert "shard_map" in str(jax.make_jaxpr(sharded)(x, gamma, beta))
        val, grads = jax.jit(sharded)(jax.device_put(x, rows), gamma, beta)
    want, want_grads = jax.value_and_grad(
        lambda *a: jnp.sum(block_ref(*a) ** 2), argnums=(0, 1, 2))(
            x, gamma, beta)
    np.testing.assert_allclose(float(val), float(want), rtol=1e-4)
    for got, ref, name in zip(grads, want_grads, ["dx", "dgamma", "dbeta"]):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-3, rtol=1e-3, err_msg=name)
    assert grads[0].sharding.spec == P("dp")


@pytest.mark.parametrize("sq", [128, 1], ids=["cached", "decode"])
def test_kernel_on_concrete_sharded_operands_partitions_itself(sq):
    """Outside any compiled step the mesh is read off the operands."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.ops.pallas import flash_attention_cached
    from paddle_tpu.ops.pallas.flash_decode import flash_attention_decode

    mesh = _dp_mesh()
    rows = NamedSharding(mesh, P("dp"))
    q, _, _ = _rand_qkv(b=4, s=sq, seed=3)
    _, k, v = _rand_qkv(b=4, s=256, seed=4)
    q_pos = np.tile(128 + np.arange(sq, dtype=np.int32), (4, 1))
    kv_len = np.asarray([256, 140, 200, 129], np.int32)
    put = lambda a: jax.device_put(a, rows)
    with pallas.interpret_mode():
        if sq == 1:
            out = flash_attention_decode(put(q), put(k), put(v), put(q_pos),
                                         put(kv_len), block_k=128)
        else:
            out = flash_attention_cached(put(q), put(k), put(v), put(q_pos),
                                         put(kv_len), block_q=128,
                                         block_k=128)
    assert out.sharding.spec == P("dp")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_cached(q, k, v, q_pos, kv_len)),
        atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# stable kernel names: what a device trace (and its readers) holds on to
# ---------------------------------------------------------------------------
def _pallas_names(jaxpr):
    """Names of every ``pallas_call`` in a jaxpr, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names.extend(_pallas_names(sub))
    return names


def _names_of(fn, *args):
    with pallas.interpret_mode():
        return _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr)


def _sum_grad(fn, n):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=tuple(range(n)))


def _site_flash():
    q, k, v = _rand_qkv(b=1, s=128)
    return _sum_grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128), 3), (q, k, v)


def _site_flash_cached():
    from paddle_tpu.ops.pallas import flash_attention_cached

    q, k, v = _rand_qkv(b=1, s=128)
    q_pos = np.arange(128, dtype=np.int32)[None]
    return (lambda q, k, v: flash_attention_cached(
        q, k, v, q_pos, np.asarray([128], np.int32), block_q=128,
        block_k=128)), (q, k, v)


def _site_flash_banded():
    from paddle_tpu.ops.pallas import flash_attention_cached

    q, k, v = _rand_qkv(b=1, s=256)
    q_pos = np.arange(256, dtype=np.int32)[None]
    return (lambda q, k, v: flash_attention_cached(
        q, k, v, q_pos, np.asarray([250], np.int32), block_q=128,
        block_k=128, window=64)), (q, k, v)


def _site_flash_decode():
    from paddle_tpu.ops.pallas.flash_decode import flash_attention_decode

    q, k, v, q_pos = _decode_case(1, 200)
    return (lambda q, k, v: flash_attention_decode(q, k, v, q_pos)), (q, k, v)


def _site_flash_packed():
    from paddle_tpu.ops.pallas.flash_attention_packed import (
        flash_attention_packed)

    x = jnp.ones((1, 128, 2 * 64), jnp.float32)
    return _sum_grad(lambda q, k, v: flash_attention_packed(
        q, k, v, 2, causal=True, block_q=128, block_k=128,
        interpret=True), 3), (x, x, x)


def _site_layer_norm():
    x = jnp.ones((2, 8, 256), jnp.float32)
    g = jnp.ones((256,), jnp.float32)
    return _sum_grad(lambda x, g, b: fused_layer_norm(x, g, b), 3), (x, g, g)


def _site_kv_row_write():
    from paddle_tpu.ops.pallas.kv_row_write import kv_row_write

    k = jnp.ones((2, 128, 2, 64), jnp.float32)
    new = jnp.ones((2, 1, 2, 64), jnp.float32)
    starts = np.asarray([3, 127], np.int32)
    return (lambda k, v, kn, vn: kv_row_write((k, v), (kn, vn), starts)), (
        k, k, new, new)


def _site_kv_row_dma():
    from paddle_tpu.ops.pallas.kv_row_dma import kv_row_dma

    k = jnp.ones((2, 16, 2, 128), jnp.float32)
    new = jnp.ones((2, 1, 2, 128), jnp.float32)
    starts = np.asarray([3, 15], np.int32)
    return (lambda k, v, kn, vn: kv_row_dma((k, v), (kn, vn), starts)), (
        k, k, new, new)


@pytest.mark.parametrize("site,expected", [
    (_site_flash, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}),
    (_site_flash_cached, {"flash_cached_fwd"}),
    (_site_flash_banded, {"flash_banded_fwd"}),
    (_site_flash_decode, {"flash_decode_fwd"}),
    (_site_flash_packed, {"flash_packed_fwd", "flash_packed_bwd"}),
    (_site_layer_norm, {"layer_norm_fwd", "layer_norm_bwd"}),
    (_site_kv_row_write, {"kv_row_write"}),
    (_site_kv_row_dma, {"kv_row_dma"}),
], ids=["flash", "flash_cached", "flash_banded", "flash_decode",
        "flash_packed", "layer_norm", "kv_row_write", "kv_row_dma"])
def test_every_pallas_call_site_carries_its_name(site, expected):
    """Each of these eleven ``pl.pallas_call`` sites names its kernel: the
    name is the custom call's in the device trace (``%jvp_flash_packed_fwd_``
    on the v5e), which is what a reader's pattern holds on to."""
    fn, args = site()
    assert set(_names_of(fn, *args)) == expected


def test_no_pallas_call_site_is_left_unnamed():
    import pathlib
    import re

    src_dir = pathlib.Path(pallas.__file__).parent
    calls = named = 0
    for path in src_dir.glob("*.py"):
        text = path.read_text()
        for m in re.finditer(r"pl\.pallas_call\(", text):
            calls += 1
            named += bool(re.match(r"\s*[\w.()=, ]+,\s*name=\"\w+\"",
                                   text[m.end():m.end() + 200]))
    assert calls == 17 and named == calls
