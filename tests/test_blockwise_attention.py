"""Blockwise cached attention (ISSUE 15): the length-masked KV-block scan
behind ``scaled_dot_product_attention(attn_mask=LengthMask(...))``.

Contracts under test:
  * LengthMask semantics — ``valid``/``additive`` match the numpy
    reference for every (q_pos, kv_len) combination the serving engine
    builds (prefill, chunked prefill, decode, verify window);
  * numeric parity — the blockwise online-softmax scan matches the dense
    einsum fallback on the SAME LengthMask for prefill chunks, verify
    windows, and decode at mid-bucket and bucket-boundary lengths, in
    value AND gradient (the custom_vjp backward recurrence);
  * fully-masked rows — a slot with ``kv_len == 0`` yields zeros, never
    NaN (the exp(s - m) guard);
  * greedy serving stays byte-identical with blockwise forced on, and the
    PR 13 O(1)-compile gates hold unchanged: decode compiles EXACTLY once
    over 64+ tokens with the scan path active.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.nn.functional import LengthMask
from paddle_tpu.nn.functional import attention as A
from paddle_tpu.profiler import telemetry
from paddle_tpu.serving import GenerationEngine
from paddle_tpu.utils import unique_name

_CONSTANTS = ["BLOCKWISE_MIN_KV", "BLOCKWISE_BLOCK_Q", "BLOCKWISE_BLOCK_K"]


def _set(**constants):
    """Move the route's thresholds for a toy size: the module constants are
    what ``attention_route`` and ``_sdpa`` read, and nothing else sets
    them."""
    for name, value in constants.items():
        assert name in _CONSTANTS, name
        setattr(A, name, value)


@pytest.fixture(autouse=True)
def _restore_constants():
    saved = {name: getattr(A, name) for name in _CONSTANTS}
    yield
    _set(**saved)


@pytest.fixture
def _no_persistent_compile_cache():
    """Same hazard as tests/test_serving.py: parity across separately
    compiled executables is only bit-exact with in-process compiles."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _qkv(b, sq, sk, h=2, d=8, seed=0):
    rng = np.random.RandomState(seed)
    q = rng.randn(b, sq, h, d).astype(np.float32)
    k = rng.randn(b, sk, h, d).astype(np.float32)
    v = rng.randn(b, sk, h, d).astype(np.float32)
    return q, k, v


def _sdpa_lm(q, k, v, lm):
    out = F.scaled_dot_product_attention(
        Tensor(q), Tensor(k), Tensor(v), attn_mask=lm, training=False)
    return np.asarray(out._value)


def _both_paths(q, k, v, lm):
    """(dense einsum fallback, forced blockwise scan) on the same mask."""
    _set(BLOCKWISE_MIN_KV=10 ** 9)
    dense = _sdpa_lm(q, k, v, lm)
    _set(BLOCKWISE_MIN_KV=1)
    block = _sdpa_lm(q, k, v, lm)
    return dense, block


# ---------------------------------------------------------------------------
# LengthMask semantics
# ---------------------------------------------------------------------------
def test_length_mask_valid_matches_numpy_reference():
    q_pos = np.array([[3, 4, 5], [0, 1, 2]], np.int32)
    kv_len = np.array([5, 2], np.int32)
    lm = LengthMask(q_pos, kv_len)
    got = np.asarray(lm.valid(8))
    assert got.shape == (2, 1, 3, 8)
    j = np.arange(8)
    want = (j[None, None, None, :] <= q_pos[:, None, :, None]) \
        & (j[None, None, None, :] < kv_len[:, None, None, None])
    np.testing.assert_array_equal(got, want)
    # additive: 0 where valid, mask_min elsewhere, in the requested dtype
    add = np.asarray(lm.additive(8, jnp.float32))
    np.testing.assert_array_equal(add == 0.0, want)
    np.testing.assert_array_equal(add == -1e9, ~want)


def test_length_mask_without_kv_len_is_pure_causal():
    lm = LengthMask(np.arange(4, dtype=np.int32)[None, :])
    got = np.asarray(lm.valid(4))[0, 0]
    np.testing.assert_array_equal(got, np.tril(np.ones((4, 4), bool)))


# ---------------------------------------------------------------------------
# blockwise-vs-einsum numeric parity, engine-shaped masks
# ---------------------------------------------------------------------------
def test_parity_prefill_full_bucket():
    # serve_prefill: q_pos = arange(bucket)[None], kv_len = [prompt_len]
    q, k, v = _qkv(1, 16, 16)
    lm = LengthMask(np.arange(16, dtype=np.int32)[None, :],
                    np.array([9], np.int32))
    dense, block = _both_paths(q, k, v, lm)
    np.testing.assert_allclose(block, dense, rtol=1e-5, atol=1e-5)


def test_parity_prefill_chunk_at_offset():
    # serve_prefill_chunk: q_pos = offset + arange(chunk), kv = max_len
    off, chunk, max_len = 8, 8, 32
    q, k, v = _qkv(1, chunk, max_len, seed=1)
    lm = LengthMask((off + np.arange(chunk, dtype=np.int32))[None, :])
    dense, block = _both_paths(q, k, v, lm)
    np.testing.assert_allclose(block, dense, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("pos", [13, 31])  # mid-bucket / bucket boundary
def test_parity_decode_single_row(pos):
    # serve_decode: q_pos = [b, 1] clamped position, kv_len = lengths
    q, k, v = _qkv(2, 1, 32, seed=2)
    lm = LengthMask(np.array([[pos], [5]], np.int32),
                    np.array([pos + 1, 6], np.int32))
    dense, block = _both_paths(q, k, v, lm)
    np.testing.assert_allclose(block, dense, rtol=1e-5, atol=1e-5)


def test_parity_verify_window():
    # serve_verify: q_pos = pos0[:, None] + arange(W), kv_len = pos0 + W
    W = 4
    pos0 = np.array([5, 11], np.int32)
    q, k, v = _qkv(2, W, 32, seed=3)
    lm = LengthMask(pos0[:, None] + np.arange(W, dtype=np.int32)[None, :],
                    pos0 + W)
    dense, block = _both_paths(q, k, v, lm)
    np.testing.assert_allclose(block, dense, rtol=1e-5, atol=1e-5)


def test_parity_odd_lengths_pick_divisor_blocks():
    # sk = 24 with preferred block 512 -> block 24; with block_k=7 -> 6
    q, k, v = _qkv(1, 5, 24, seed=4)
    lm = LengthMask(np.full((1, 5), 23, np.int32), np.array([17], np.int32))
    _set(BLOCKWISE_BLOCK_Q=7, BLOCKWISE_BLOCK_K=7)
    dense, block = _both_paths(q, k, v, lm)
    np.testing.assert_allclose(block, dense, rtol=1e-5, atol=1e-5)


def test_blockwise_grads_match_einsum_causal_training():
    # the long-causal-training branch: attn_mask=None, is_causal=True
    q, k, v = _qkv(2, 16, 16, seed=5)
    w = np.random.RandomState(6).randn(*q.shape).astype(np.float32)

    def run():
        tq, tk, tv = (paddle.to_tensor(a, stop_gradient=False)
                      for a in (q, k, v))
        out = F.scaled_dot_product_attention(tq, tk, tv, is_causal=True)
        (out * Tensor(w)).sum().backward()
        return (np.asarray(out._value),
                [np.asarray(t.grad._value) for t in (tq, tk, tv)])

    _set(BLOCKWISE_MIN_KV=10 ** 9)  # the einsum route
    ref_out, ref_g = run()
    _set(BLOCKWISE_MIN_KV=1)
    got_out, got_g = run()
    np.testing.assert_allclose(got_out, ref_out, rtol=1e-5, atol=1e-5)
    for g, r in zip(got_g, ref_g):
        np.testing.assert_allclose(g, r, rtol=1e-4, atol=1e-5)


def test_fully_masked_rows_are_zero_not_nan():
    q, k, v = _qkv(2, 4, 16, seed=7)
    # slot 1 has an empty cache: every key invalid for every query row
    lm = LengthMask(np.tile(np.arange(4, dtype=np.int32), (2, 1)),
                    np.array([16, 0], np.int32))
    _set(BLOCKWISE_MIN_KV=1)
    out = _sdpa_lm(q, k, v, lm)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[1], np.zeros_like(out[1]))


# ---------------------------------------------------------------------------
# serving stays byte-identical + the PR 13 compile gates hold
# ---------------------------------------------------------------------------
def _serve_model(seed=0):
    with unique_name.guard():
        paddle.seed(seed)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
            max_position_embeddings=128, hidden_dropout=0.0,
            attention_dropout=0.0, initializer_range=0.6))
    model.eval()
    return model


def test_greedy_serving_byte_identical_with_blockwise_forced(
        _no_persistent_compile_cache):
    model = _serve_model()
    prompt = np.random.RandomState(11).randint(0, 512, 7).tolist()

    def gen():
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8, 16))
        return eng.generate(prompt, max_new_tokens=16)

    base = gen()
    _set(BLOCKWISE_MIN_KV=1)
    forced = gen()
    assert len(set(base)) > 2, "degenerate model; parity check is vacuous"
    assert forced == base


def test_chunked_prefill_byte_identical_with_blockwise_forced(
        _no_persistent_compile_cache):
    model = _serve_model(seed=1)
    prompt = np.random.RandomState(12).randint(0, 512, 21).tolist()

    def gen():
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8, 16, 32),
                               prefill_chunk=8)
        return eng.generate(prompt, max_new_tokens=12)

    base = gen()
    _set(BLOCKWISE_MIN_KV=1)
    forced = gen()
    assert forced == base


def test_decode_still_compiles_once_with_blockwise_forced():
    _set(BLOCKWISE_MIN_KV=1)
    model = _serve_model()
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=128,
                               prefill_buckets=(8, 16))
        out = eng.generate([5, 6, 7], max_new_tokens=65)
        counts = telemetry.get_telemetry().compile_counts()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert len(out) == 65
    assert counts.get("serve_decode") == 1, counts
    assert counts.get("serve_prefill") == 1, counts


# ---------------------------------------------------------------------------
# the decode-shaped Pallas route (ISSUE 27): chosen from platform and shape
# ---------------------------------------------------------------------------
def _traced_ops(fn):
    """Framework ops by registry name, counted while ``fn`` runs (the
    counter ``chip_smoke.py`` checks the same routes with on the chip)."""
    from chip_smoke import ops_traced

    with ops_traced() as seen:
        fn()
    return seen


@pytest.mark.parametrize("interpret,sq,sk,expected", [
    (False, 1, 128, "blockwise_sdpa"),     # XLA:CPU, tier-1: the scan stays
    (False, 128, 128, "blockwise_sdpa"),
    (True, 1, 128, "flash_sdpa_decode"),   # decode
    (True, 5, 128, "flash_sdpa_decode"),   # speculative verify, spec_k = 4
    (True, 128, 256, "flash_sdpa_cached"),  # a prefill chunk
    (True, 12, 128, "blockwise_sdpa"),     # neither kernel takes 12 rows
    (True, 1, 96, "blockwise_sdpa"),       # no 128-aligned KV block
])
def test_length_masked_routing_by_platform_and_shape(interpret, sq, sk,
                                                     expected):
    import contextlib

    from paddle_tpu.ops import pallas

    _set(BLOCKWISE_MIN_KV=1)
    q, k, v = _qkv(2, sq, sk, h=2, d=64)
    lm = LengthMask(np.tile(sk - sq + np.arange(sq, dtype=np.int32), (2, 1)))
    with pallas.interpret_mode() if interpret else contextlib.nullcontext():
        seen = _traced_ops(lambda: _sdpa_lm(q, k, v, lm))
    routes = {n for n in seen if n.endswith("sdpa") or "sdpa_" in n}
    assert routes == {expected}, dict(seen)


def test_short_caches_keep_the_dense_route_under_interpret():
    """Below ``BLOCKWISE_MIN_KV`` nothing changes route: the same threshold
    stands before both kernels."""
    from paddle_tpu.ops import pallas

    q, k, v = _qkv(2, 1, 128, h=2, d=64)
    lm = LengthMask(np.full((2, 1), 100, np.int32))
    with pallas.interpret_mode():
        seen = _traced_ops(lambda: _sdpa_lm(q, k, v, lm))
    assert seen["sdpa"] == 1 and not seen["flash_sdpa_decode"], dict(seen)


def _kernel_model(seed=0):
    """Two heads of 64 over a 128-slot cache: shapes both kernels tile."""
    with unique_name.guard():
        paddle.seed(seed)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
            max_position_embeddings=128, hidden_dropout=0.0,
            attention_dropout=0.0, initializer_range=0.6))
    model.eval()
    return model


@pytest.mark.parametrize("engine_kw,prompt_len", [
    ({}, 7), ({"spec_k": 4}, 9)], ids=["decode", "verify"])
def test_greedy_serving_byte_identical_scan_and_decode_kernel(
        _no_persistent_compile_cache, engine_kw, prompt_len):
    """The same engine through the blockwise scan (XLA:CPU) and through the
    decode kernel (interpret mode): the same greedy tokens, and each route's
    op is the one its decode (or verify) step traced."""
    from paddle_tpu.ops import pallas

    _set(BLOCKWISE_MIN_KV=1)
    model = _kernel_model()
    rng = np.random.RandomState(11)
    # a periodic prompt: the n-gram proposer drafts from the first tick
    prompt = np.tile(rng.randint(0, 512, 3), 4)[:prompt_len].tolist()

    def gen():
        eng = GenerationEngine(model, max_batch=2, max_len=128,
                               prefill_buckets=(16,), **engine_kw)
        return eng.generate(prompt, max_new_tokens=24)

    out = {}
    seen_scan = _traced_ops(lambda: out.update(scan=gen()))
    with pallas.interpret_mode():
        seen_kernel = _traced_ops(lambda: out.update(kernel=gen()))
    assert len(set(out["scan"])) > 2, "degenerate model; parity is vacuous"
    assert out["kernel"] == out["scan"]
    # the one 16-wide prefill bucket (2 layers) is the scan's either way;
    # every other length-masked call moved to the kernel
    assert not seen_scan["flash_sdpa_decode"], dict(seen_scan)
    assert seen_kernel["blockwise_sdpa"] == 2, dict(seen_kernel)
    assert (seen_kernel["flash_sdpa_decode"]
            == seen_scan["blockwise_sdpa"] - 2 > 0), dict(seen_kernel)


def test_decode_still_compiles_once_through_the_decode_kernel():
    from paddle_tpu.ops import pallas

    _set(BLOCKWISE_MIN_KV=1)
    model = _kernel_model()
    telemetry.reset()
    telemetry.enable()
    try:
        with pallas.interpret_mode():
            eng = GenerationEngine(model, max_batch=2, max_len=128,
                                   prefill_buckets=(8, 16))
            out = eng.generate([5, 6, 7], max_new_tokens=40)
        counts = telemetry.get_telemetry().compile_counts()
        recompiles = telemetry.get_telemetry().recompile_count
    finally:
        telemetry.disable()
        telemetry.reset()
    assert len(out) == 40
    assert counts.get("serve_decode") == 1, counts
    assert counts.get("serve_prefill") == 1, counts
    assert recompiles == 0
