"""A ``kv`` entry that declares a ``window``: the ring the cache allocates,
what the prefill view leaves in it whatever the bucket, where the decode
view writes, what the cache's bytes, the engine's footprints and the bucket
lint count, and the engine's refusals."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import LengthMask
from paddle_tpu.serving import (GenerationEngine, RecurrentStateError,
                                RingCacheError)
from paddle_tpu.serving.kv_cache import (DecodeView, KVCache, PrefillView,
                                         RingPrefillView, cache_route)

import laguna_tiny as tiny

KV = {"kind": "kv", "heads": 2, "head_dim": 16, "dtype": "float32"}


@pytest.mark.parametrize("entry, max_len, route", [
    (KV, 128, "full"),
    (dict(KV, window=8), 128, "ring"),
    (dict(KV, window=127), 128, "ring"),
    (dict(KV, window=128), 128, "full"),   # a window that reaches it all
    (dict(KV, window=512), 128, "full"),
    (dict(KV, window=None), 128, "full"),
])
def test_cache_route_table(entry, max_len, route):
    assert cache_route(entry, max_len) == route


def test_a_ring_is_allocated_as_what_it_is():
    spec = [KV, None, dict(KV, window=8), {"kind": "counts", "names": ()},
            dict(KV, window=512)]
    cache = KVCache.from_spec(spec, 3, 64)
    assert [None if k is None else k.shape for k in cache.ks] == [
        (3, 64, 2, 16), None, (3, 8, 2, 16), None, (3, 64, 2, 16)]
    assert cache.max_len == 64
    assert cache.nbytes() == 2 * 3 * (64 + 8 + 64) * 2 * 16 * 4
    assert "ring" in repr(cache)


@pytest.mark.parametrize("n, bucket", [(3, 8), (8, 8), (3, 32), (8, 32),
                                       (9, 32), (20, 32), (31, 32)])
def test_prefill_leaves_the_last_rows_at_position_mod_window(n, bucket):
    window = 8
    k = jnp.zeros((2, window, 1, 4)) - 1.0
    new = jnp.broadcast_to(
        jnp.arange(bucket, dtype=jnp.float32)[None, :, None, None],
        (1, bucket, 1, 4))          # row p holds p
    mask = LengthMask(jnp.arange(bucket)[None], jnp.asarray([n]),
                      window=window)
    view = RingPrefillView(k, k, jnp.int32(1), jnp.int32(n), mask)
    k_att, v_att, same = view.update(new, new + 100.0)
    assert same is view and view.mask is mask
    np.testing.assert_array_equal(np.asarray(k_att), np.asarray(new))
    ring = np.asarray(view.k)[1, :, 0, 0]
    np.testing.assert_array_equal(np.asarray(view.k)[0], -1.0)  # other slot
    for p in range(max(0, n - window), n):
        assert ring[p % window] == p            # the last min(n, window)
    np.testing.assert_array_equal(np.asarray(view.v)[1, :, 0, 0],
                                  ring + 100.0)


def test_decode_on_a_ring_is_the_plain_view_at_pos_mod_window():
    # the engine hands pos mod window and the ring's mask: no new route
    k = jnp.zeros((2, 8, 1, 4))
    new = jnp.ones((2, 1, 1, 4))
    mask = LengthMask([[7], [2]])
    view = DecodeView(k, k, jnp.asarray([19, 2]) % 8, mask)
    view.update(new, new)
    assert np.asarray(view.k)[0, 3, 0, 0] == 1.0
    assert np.asarray(view.k)[1, 2, 0, 0] == 1.0
    assert float(np.asarray(view.k).sum()) == 8.0
    assert view.mask is mask and DecodeView(k, k, 0).mask is None
    assert PrefillView(k, k, 0).mask is None


@pytest.fixture(scope="module")
def engine():
    model, _ = tiny.build(tiny.tiny_config())
    return GenerationEngine(model, max_batch=3, max_len=64)


def test_engine_keeps_the_window_layers_as_rings(engine):
    assert engine.ring_windows == [8]
    assert engine._routes == ["full", None, "ring", None, "ring", None,
                              "ring", None, "full", None]
    shapes = [None if k is None else k.shape[1] for k in engine.cache.ks]
    assert shapes == [64, None, 8, None, 8, None, 8, None, 64, None]
    row = 2 * 2 * 16 * 4                      # K and V of one position
    assert engine.cache.nbytes() == 3 * (2 * 64 + 3 * 8) * row


def test_footprints_count_a_ring_as_what_it_is(engine):
    fp = engine.predicted_footprints()
    row = 2 * 2 * 16 * 4
    assert fp["cache_bytes"] == 3 * (2 * 64 + 3 * 8) * row
    assert fp["per_token_bytes"] == 2 * row   # the two full-length layers
    for b, nbytes in fp["prefill_bucket_bytes"].items():
        assert nbytes == 2 * row * min(64, b) + 3 * row * min(8, b)


def test_bucket_lint_reads_the_full_length_buffers(engine):
    # lengths that pad badly under the bucket ladder: the finding speaks of
    # the 64-row buffers (4 of them), never of the rings
    from paddle_tpu import analysis

    args = engine.example_decode_args([17, 33, 9])
    report = analysis.lint_step(engine.decode_step, *args)
    found = report.by_rule("hbm-kv-bucket-waste")
    assert len(found) == 1
    assert found[0].data["max_len"] == 64 and "4 buffers" in found[0].message
    row = 2 * 2 * 16 * 4
    wasted = (32 + 64 + 16) - (17 + 33 + 9)
    assert found[0].data["wasted_bytes"] == pytest.approx(wasted * 2 * row)


@pytest.mark.parametrize("kw", [{"spec_k": 2}, {"prefill_chunk": 16}],
                         ids=["spec_k", "prefill_chunk"])
def test_steps_that_need_the_rings_order_raise_by_name(kw):
    model, _ = tiny.build(tiny.tiny_config())
    with pytest.raises(RingCacheError, match="rings of \\[8\\] rows"):
        GenerationEngine(model, max_batch=2, max_len=64, **kw)
    assert issubclass(RingCacheError, ValueError)
    assert not issubclass(RingCacheError, RecurrentStateError)
    # a window that reaches the whole cache is no ring: both are built
    GenerationEngine(model, max_batch=2, max_len=8, **{
        k: min(v, 4) for k, v in kw.items()})
