"""``LengthMask(window=)``: a band of the last ``window`` keys, through every
route that takes a cached call (dense einsum, the grouped einsum, the
blockwise scan, the banded flash kernel interpreted) against a dense band
mask; the routes a windowed call takes and what ``attn.prefill_band`` names
them."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import attention as A
from paddle_tpu.ops import pallas
from paddle_tpu.profiler import telemetry

# the package exports the function under the module's name
FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def _dense(q, k, v, q_pos, kv_len, window):
    """Softmax attention under the band as a dense mask, float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    k, v = (np.repeat(a, h // hk, axis=2) for a in (k, v))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    j = np.arange(sk)[None, None, None, :]
    p = np.asarray(q_pos)[:, None, :, None]
    ok = (j <= p) & (j > p - window)
    if kv_len is not None:
        ok = ok & (j < np.asarray(kv_len)[:, None, None, None])
    s = np.where(ok, s, -np.inf)
    m = np.max(s, -1, keepdims=True)
    e = np.where(ok, np.exp(s - np.where(np.isfinite(m), m, 0.0)), 0.0)
    den = np.sum(e, -1, keepdims=True)
    return np.einsum("bhqk,bkhd->bqhd", e / np.where(den == 0, 1.0, den), v)


def _qkv(seed, b, sq, sk, h, hk, d=32):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=shape), jnp.float32)
                 for shape in ((b, sq, h, d), (b, sk, hk, d), (b, sk, hk, d)))


def test_mask_is_a_band():
    m = F.LengthMask(jnp.asarray([[0, 3, 6], [2, -1, 5]]),
                     jnp.asarray([8, 5]), window=3)
    got = np.asarray(m.valid(8))[:, 0]
    for b, row in enumerate([[0, 3, 6], [2, -1, 5]]):
        for i, p in enumerate(row):
            want = [(p - 3 < j <= p) and j < (8, 5)[b] for j in range(8)]
            assert got[b, i].tolist() == want
    assert F.LengthMask([[1]]).window is None  # today's meaning
    np.testing.assert_array_equal(
        np.asarray(F.LengthMask([[4]], window=99).valid(8)),
        np.asarray(F.LengthMask([[4]]).valid(8)))
    with pytest.raises(ValueError, match="window"):
        F.LengthMask([[1]], window=0)
    add = m.additive(8, jnp.float32)
    assert float(add[0, 0, 1, 3]) == 0.0 and float(add[0, 0, 1, 0]) < -1e8


# prompts shorter than, equal to and several times the window
@pytest.mark.parametrize("n, window", [(40, 64), (64, 64), (200, 64),
                                       (256, 64), (250, 16)])
@pytest.mark.parametrize("route", ["einsum", "blockwise", "flash_cached"])
def test_a_prompt_under_the_band(route, n, window, monkeypatch):
    # a padded bucket of 256 with n real positions, grouped K/V heads
    q, k, v = _qkv(n + window, 2, 256, 256, 4, 2)
    q_pos = jnp.broadcast_to(jnp.arange(256, dtype=jnp.int32), (2, 256))
    kv_len = jnp.asarray([n, max(n - 7, 1)], jnp.int32)
    mask = F.LengthMask(q_pos, kv_len, window=window)
    want = _dense(q, k, v, q_pos, kv_len, window)
    monkeypatch.setattr(A, "BLOCKWISE_MIN_KV", 128 if route != "einsum"
                        else 1 << 20)
    monkeypatch.setattr(A, "BLOCKWISE_BLOCK_Q", 64)
    monkeypatch.setattr(A, "BLOCKWISE_BLOCK_K", 32)
    monkeypatch.setattr(FA, "BAND_BLOCK", 128)
    taken = []
    monkeypatch.setattr(A, "_count_prefill_band", taken.append)

    def run():
        return jax.jit(lambda q, k, v: F.scaled_dot_product_attention(
            q, k, v, attn_mask=mask, training=False)._value)(q, k, v)

    if route == "flash_cached":
        with pallas.interpret_mode():
            got = run()
    else:
        got = run()
    assert taken == [route]
    real = np.arange(256)[None, :] < np.asarray(kv_len)[:, None]
    np.testing.assert_allclose(np.asarray(got)[real], want[real], atol=2e-5)


@pytest.mark.parametrize("block", [128, 256])
def test_banded_kernel_at_offsets_and_dead_rows(block):
    # a chunk whose rows begin mid-block, a batch row that belongs to no
    # request (-1 everywhere: zeros), a window that is no multiple of a block
    q, k, v = _qkv(3, 3, 256, 1024, 2, 2)
    starts = np.asarray([300, 0, 700])
    q_pos = jnp.asarray(starts[:, None] + np.arange(256)[None, :], jnp.int32)
    q_pos = q_pos.at[1].set(-1)
    kv_len = jnp.asarray([556, 9, 900], jnp.int32)
    with pallas.interpret_mode():
        got = FA.flash_attention_cached(q, k, v, q_pos, kv_len, window=200,
                                        block_q=block, block_k=block)
    want = _dense(q, k, v, q_pos, kv_len, 200)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    assert float(jnp.abs(got[1]).max()) == 0.0


def test_banded_kernel_visits_the_band_alone():
    # the sweep's length is the band's, not the cache's: 3 of 16 key blocks
    # for 512-row tiles under a window of 512, and a short cache all of it
    assert FA.band_blocks(512, 512, 512, 16) == 3
    assert FA.band_blocks(128, 128, 512, 64) == 6
    assert FA.band_blocks(512, 512, 512, 2) == 2
    assert FA.band_blocks(1024, 1024, 512, 8) == 3


def test_grouped_decode_honours_a_window():
    q, k, v = _qkv(5, 3, 1, 64, 8, 2)
    q_pos = jnp.asarray([[40], [3], [-1]], jnp.int32)
    mask = F.LengthMask(q_pos, window=16)
    got = F.scaled_dot_product_attention(q, k, v, attn_mask=mask,
                                         training=False)._value
    # (the row without a request is never read: any finite value will do)
    np.testing.assert_allclose(np.asarray(got)[:2],
                               _dense(q, k, v, q_pos, None, 16)[:2], atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()


FACTS = dict(batch=1, heads=64, kv_heads=8, head_dim=128, kv_itemsize=2,
             cached=True, causal=False, mask_shape=None, mask_trainable=False,
             dropout=False)
TPU = dict(pallas=True, interpret=False)
CPU = dict(pallas=False, interpret=False)


@pytest.mark.parametrize("sq, sk, kv_heads, platform, expect, band", [
    # the window layers of a prefill bucket on the chip, and off it
    (8192, 8192, 8, TPU, "flash_cached", "banded"),
    (1024, 1024, 8, TPU, "flash_cached", "banded"),
    (512, 512, 8, TPU, "einsum", "dense"),
    (8192, 8192, 8, CPU, "blockwise", "banded"),
    (256, 256, 8, CPU, "einsum", "dense"),
    # decode over a ring, a windowed decode over a full-length buffer
    (1, 512, 8, TPU, "einsum_grouped", None),
    (1, 9216, 8, TPU, "einsum_grouped", None),
    # without grouped heads the decode kernel knows no window: the scan
    (1, 2048, 64, TPU, "blockwise", None),
    (16, 2048, 64, TPU, "blockwise", "banded"),
])
def test_routes_of_a_windowed_call(sq, sk, kv_heads, platform, expect, band):
    facts = dict(FACTS, sq=sq, sk=sk, kv_heads=kv_heads, **platform)
    route = A.attention_route(window=512, **facts)
    assert route == expect
    if band:
        assert A.prefill_band(route) == band
    # without a window every one of these is the route it was
    plain = A.attention_route(**facts)
    assert plain == A.attention_route(window=None, **facts)
    if expect != "blockwise" or sq != 1:
        assert plain == expect or (plain, sq) == ("flash_decode", 1)


def test_prefill_band_counter(monkeypatch, counting):
    monkeypatch.setattr(A, "BLOCKWISE_MIN_KV", 128)
    q, k, v = _qkv(1, 1, 128, 128, 4, 2)
    pos = jnp.arange(128, dtype=jnp.int32)[None]
    for window, n in ((32, 1), (None, 0)):
        telemetry.reset()
        F.scaled_dot_product_attention(
            q, k, v, attn_mask=F.LengthMask(pos, window=window),
            training=False)
        got = telemetry.get_telemetry().counters()
        assert got.get("attn.prefill_band.banded", 0) == n
        assert "attn.prefill_band.dense" not in got
