"""The rotary tables against the published formulas: the plain form over the
whole head and YaRN's over half of it, at the first position, either side of
the original context and at the cache's end; the rotation itself."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.rope import apply_rotary, rope_frequencies

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 64,
        "original_max_position_embeddings": 4096, "beta_slow": 1,
        "beta_fast": 64, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5}
POSITIONS = (0, 4095, 4096, 9215)


def _yarn_by_hand():
    """The issue's formulas, pair by pair, in float64."""
    f = [500000.0 ** (-2 * c / 64) for c in range(32)]

    def dim(r):
        return 64 * math.log(4096 / (2 * math.pi * r)) \
            / (2 * math.log(500000))

    lo, hi = max(math.floor(dim(64)), 0), min(math.ceil(dim(1)), 63)
    ramp = [min(max((c - lo) / (hi - lo), 0.0), 1.0) for c in range(32)]
    return lo, hi, ramp, [f[c] * (1 - ramp[c]) + f[c] / 64 * ramp[c]
                          for c in range(32)]


def test_plain_frequencies():
    inv, scale = rope_frequencies(128, 10000)
    assert scale == 1.0 and inv.shape == (64,) and inv.dtype == np.float32
    np.testing.assert_allclose(
        inv, [10000.0 ** (-2 * c / 128) for c in range(64)], rtol=1e-6)


def test_yarn_frequencies():
    lo, hi, ramp, want = _yarn_by_hand()
    assert (lo, hi) == (5, 16)  # dim(64) = 5.66, dim(1) = 15.80
    inv, scale = rope_frequencies(64, 500000, yarn=YARN)
    assert scale == pytest.approx(0.1 * math.log(64) + 1, rel=1e-7)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    plain, _ = rope_frequencies(64, 500000)
    # the fast pairs keep their frequency, the slow ones are divided by 64,
    # a ramp between
    np.testing.assert_allclose(inv[:lo + 1], plain[:lo + 1], rtol=1e-6)
    np.testing.assert_allclose(inv[hi:], plain[hi:] / 64, rtol=1e-6)
    mid = inv[lo + 1:hi] / plain[lo + 1:hi]
    assert np.all(np.diff(mid) < 0) and mid[0] < 1 and mid[-1] > 1 / 64
    # the default attention factor is the published one
    assert rope_frequencies(64, 500000, yarn={
        k: v for k, v in YARN.items() if k != "attention_factor"})[1] \
        == pytest.approx(1.4158883083359672, rel=1e-9)


@pytest.mark.parametrize("p", POSITIONS)
def test_tables_at_a_position(p):
    # cos and sin as the layer multiplies them in, read off a unit vector
    for dim, theta, yarn, head in ((128, 10000, None, 128),
                                   (64, 500000, YARN, 128)):
        inv, scale = rope_frequencies(dim, theta, yarn=yarn)
        half = dim // 2
        x = np.zeros((1, 1, 1, head), np.float32)
        x[..., :half] = 1.0                       # x1 = 1, x2 = 0
        x[..., dim:] = 7.0                        # the unrotated channels
        got = np.asarray(apply_rotary(jnp.asarray(x), jnp.asarray([[p]]),
                                      inv, scale)._value)[0, 0, 0]
        angle = np.float64(p) * inv.astype(np.float64)
        tol = 1e-6 + 2e-7 * p  # float32 angles: p ulps of the fastest pair
        np.testing.assert_allclose(got[:half], np.cos(angle) * scale,
                                   atol=tol * scale)
        np.testing.assert_allclose(got[half:dim], np.sin(angle) * scale,
                                   atol=tol * scale)
        np.testing.assert_array_equal(got[dim:], 7.0)  # unscaled too
    if p == 0:
        assert got[0] == pytest.approx(1.4158883, rel=1e-6)


def test_rotation_depends_on_the_distance_alone():
    # R(p) q . R(j) k is a function of p - j: what lets a ring hold its rows
    # in any order and a cached key never be rotated again
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(1, 1, 2, 128)), jnp.float32)
            for _ in range(2))
    for inv, scale in (rope_frequencies(128, 10000),
                       rope_frequencies(64, 500000, yarn=YARN)):
        def score(p, j):
            a = apply_rotary(q, jnp.asarray([[p]]), inv, scale)._value
            b = apply_rotary(k, jnp.asarray([[j]]), inv, scale)._value
            return float(jnp.sum(a * b))
        assert score(700, 300) == pytest.approx(score(5400, 5000), abs=2e-3)
        assert score(700, 300) != pytest.approx(score(700, 301), abs=1e-3)


def test_bad_sizes_are_refused():
    with pytest.raises(ValueError, match="rotary_dim"):
        rope_frequencies(63, 10000)
    x = jnp.ones((2, 3, 4, 8), jnp.bfloat16)
    out = apply_rotary(x, jnp.zeros((2, 3), jnp.int32),
                       *rope_frequencies(4, 10000))
    assert out.dtype == jnp.bfloat16 and out.shape == [2, 3, 4, 8]
    np.testing.assert_array_equal(np.asarray(out._value, np.float32), 1.0)
