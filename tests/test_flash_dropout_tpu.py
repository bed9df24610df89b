"""In-kernel flash-attention dropout — TPU-hardware tests.

The keep mask comes from the TPU hardware PRNG (``pltpu.prng_seed``), which
has no interpret-mode lowering, so these tests need a real (compiled) TPU
backend; under the CPU suite they skip. Run manually on the chip:

    PADDLE_TPU_HW_TESTS=1 python -m pytest \
        tests/test_flash_dropout_tpu.py -q -p no:cacheprovider

Validation strategy (the mask never leaves VMEM, so tests treat the kernel
as a deterministic function of its seed):
  * same seed -> bit-identical output; different seed -> different output
  * E_seed[output] ~= no-dropout output  (dropout is unbiased)
  * effect magnitude matches the rate (output != no-dropout for p>0)
  * autodiff gradients vs central finite differences of the SAME seeded
    function for q, k, v — this exercises the dq and dk/dv kernels' mask
    regeneration and the dS = P(dP.M/keep - delta) recurrence.

Reference capability: in-kernel curand dropout in
``paddle/fluid/operators/fused/fused_attention_op.cu``.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="in-kernel dropout needs the TPU hardware PRNG",
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fa(**kw):
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    return flash_attention(block_q=128, block_k=128, interpret=False, **kw)


def _inputs(b=1, h=2, s=256, d=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, h, d), dtype)
    v = jax.random.normal(ks[2], (b, s, h, d), dtype)
    return q, k, v


def test_deterministic_given_seed():
    q, k, v = _inputs()
    seed = jnp.array([123, 456], jnp.int32)
    o1 = _fa(q=q, k=k, v=v, dropout_p=0.2, dropout_seed=seed)
    o2 = _fa(q=q, k=k, v=v, dropout_p=0.2, dropout_seed=seed)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    o3 = _fa(q=q, k=k, v=v, dropout_p=0.2,
             dropout_seed=jnp.array([124, 456], jnp.int32))
    assert not np.allclose(np.asarray(o1), np.asarray(o3))


def test_dropout_unbiased_mean():
    q, k, v = _inputs()
    base = np.asarray(_fa(q=q, k=k, v=v, dropout_p=0.0))
    n = 96
    acc = np.zeros_like(base, np.float64)
    run = jax.jit(lambda s: _fa(q=q, k=k, v=v, dropout_p=0.3, dropout_seed=s))
    for i in range(n):
        o = np.asarray(run(jnp.array([i, 9000 + i], jnp.int32)))
        assert not np.allclose(o, base), "p=0.3 must perturb the output"
        acc += o
    mean = acc / n
    # measured scaling on v5e: err 0.091@n=48, 0.066@n=96, 0.046@n=192 —
    # the clean 1/sqrt(n) of an unbiased estimator
    err = np.abs(mean - base).mean() / (np.abs(base).mean() + 1e-9)
    assert err < 0.08, err


@pytest.mark.parametrize("wrt", [0, 1, 2])
def test_grad_matches_finite_difference(wrt):
    # small shapes keep central differences affordable on-chip
    q, k, v = _inputs(b=1, h=1, s=128, d=64)
    seed = jnp.array([77, 88], jnp.int32)
    co = jax.random.normal(jax.random.key(3), q.shape, jnp.float32)

    def f(*args):
        out = _fa(q=args[0], k=args[1], v=args[2], dropout_p=0.25,
                  dropout_seed=seed, causal=True)
        return jnp.vdot(out, co)

    args = [q, k, v]
    g = jax.grad(f, argnums=wrt)(*args)
    g = np.asarray(g)

    rng = np.random.RandomState(0)
    x = np.asarray(args[wrt])
    eps = 1e-2
    for _ in range(6):
        idx = tuple(rng.randint(0, dim) for dim in x.shape)
        e = np.zeros_like(x)
        e[idx] = eps
        hi = [a if i != wrt else jnp.asarray(x + e) for i, a in enumerate(args)]
        lo = [a if i != wrt else jnp.asarray(x - e) for i, a in enumerate(args)]
        fd = (float(f(*hi)) - float(f(*lo))) / (2 * eps)
        assert abs(fd - g[idx]) < 2e-2 + 0.05 * abs(fd), (idx, fd, g[idx])


@pytest.mark.parametrize("wrt", [0, 1, 2])
def test_packed_grad_matches_finite_difference(wrt):
    """Packed-kernel dropout: fwd and bwd MUST re-tile identically (the
    PRNG mask depends on tile index and shape) — this FD check fails if
    bwd_block were allowed to diverge from the forward blocks."""
    from paddle_tpu.ops.pallas.flash_attention_packed import (
        flash_attention_packed,
    )

    b, s, h, d = 1, 256, 2, 64
    ks = jax.random.split(jax.random.key(11), 3)
    args = [jax.random.normal(k_, (b, s, h * d), jnp.float32) for k_ in ks]
    seed = jnp.array([55, 66], jnp.int32)
    co = jax.random.normal(jax.random.key(4), args[0].shape, jnp.float32)

    def f(*a):
        out = flash_attention_packed(
            a[0], a[1], a[2], h, causal=True, dropout_p=0.25,
            dropout_seed=seed, block_q=256, block_k=256, bwd_block=128,
            interpret=False)
        return jnp.vdot(out, co)

    g = np.asarray(jax.grad(f, argnums=wrt)(*args))
    rng = np.random.RandomState(1)
    x = np.asarray(args[wrt])
    eps = 1e-2
    for _ in range(6):
        idx = tuple(rng.randint(0, dim) for dim in x.shape)
        e = np.zeros_like(x)
        e[idx] = eps
        hi = [a if i != wrt else jnp.asarray(x + e) for i, a in enumerate(args)]
        lo = [a if i != wrt else jnp.asarray(x - e) for i, a in enumerate(args)]
        fd = (float(f(*hi)) - float(f(*lo))) / (2 * eps)
        assert abs(fd - g[idx]) < 2e-2 + 0.05 * abs(fd), (idx, fd, g[idx])


@pytest.mark.parametrize("wrt", [0, 2])
def test_packed_canonical_units_grad_fd(wrt):
    """Flagship tiling with dropout: fwd at 1024 single-k tiles, bwd at
    512 — the canonical 512x512 dropout units must give both the SAME
    mask; a finite-difference check fails if they diverge."""
    from paddle_tpu.ops.pallas.flash_attention_packed import (
        flash_attention_packed,
    )

    b, s, h, d = 1, 1024, 2, 64
    ks = jax.random.split(jax.random.key(21), 3)
    args = [jax.random.normal(k_, (b, s, h * d), jnp.float32) * 0.3
            for k_ in ks]
    seed = jnp.array([7, 9], jnp.int32)
    co = jax.random.normal(jax.random.key(2), args[0].shape, jnp.float32)

    def f(*a):
        out = flash_attention_packed(
            a[0], a[1], a[2], h, causal=True, dropout_p=0.25,
            dropout_seed=seed, block_q=1024, block_k=1024, bwd_block=512,
            interpret=False)
        return jnp.vdot(out, co)

    g = np.asarray(jax.grad(f, argnums=wrt)(*args))
    rng = np.random.RandomState(3)
    x = np.asarray(args[wrt])
    eps = 1e-2
    for _ in range(4):
        idx = tuple(rng.randint(0, dim) for dim in x.shape)
        e = np.zeros_like(x)
        e[idx] = eps
        hi = [a if i != wrt else jnp.asarray(x + e) for i, a in enumerate(args)]
        lo = [a if i != wrt else jnp.asarray(x - e) for i, a in enumerate(args)]
        fd = (float(f(*hi)) - float(f(*lo))) / (2 * eps)
        assert abs(fd - g[idx]) < 2e-2 + 0.05 * abs(fd), (idx, fd, g[idx])


def test_sdpa_router_keeps_flash_with_dropout():
    """F.scaled_dot_product_attention with dropout>0 must stay on the flash
    path on a compiled TPU backend."""
    from paddle_tpu.nn.functional.attention import attention_route

    assert attention_route(
        batch=8, sq=1024, sk=1024, heads=12, kv_heads=12, head_dim=64,
        kv_itemsize=2, cached=False, causal=True, mask_shape=None,
        mask_trainable=False, dropout=True, pallas=True,
        interpret=False) == "flash_packed"
