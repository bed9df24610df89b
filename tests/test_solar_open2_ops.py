"""The delta-rule recurrence's three forms against each other, ``kda_step``
and the gated grouped product (interpreted) against their XLA formulations
and a loop, and the route table of the decode step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import kda
from paddle_tpu.nn.layer import experts as X
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import kda_step as KS, moe_grouped
from paddle_tpu.profiler import telemetry


def _inputs(b, L, H, dk, dv, seed=0, decay=1.0, beta_shift=0.0):
    """``q, k`` as the mixer hands them (unit ``k``, ``q`` scaled), ``g``
    about ``-decay`` a position, a NON-ZERO start state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    return (unit(jax.random.normal(ks[0], (b, L, H, dk))) * dk ** -0.5,
            unit(jax.random.normal(ks[1], (b, L, H, dk))),
            jax.random.normal(ks[2], (b, L, H, dv)),
            -decay * jax.nn.softplus(jax.random.normal(ks[3], (b, L, H, dk))),
            2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, L, H))
                               + beta_shift),
            jax.random.normal(ks[5], (b, H, dk, dv)))


@pytest.fixture(scope="module")
def scanned():
    """Inputs, and what the plain scan makes of them (compiled once)."""
    inputs = _inputs(2, 64, 4, 16, 8)
    return inputs, jax.jit(kda.kda_scan_plain)(*inputs)


@pytest.mark.parametrize("chunk", [8, 32, 64])
def test_chunked_scan_is_the_plain_scan(scanned, chunk):
    # chunk 8: one sub-block a chunk; 32, 64: sub-blocks of 16 merged
    inputs, (o0, s0) = scanned
    o1, s1 = jax.jit(kda.kda_scan_chunked, static_argnums=6)(*inputs, chunk)
    np.testing.assert_allclose(o1, o0, atol=2e-5)
    np.testing.assert_allclose(s1, s0, atol=2e-5)


@pytest.mark.parametrize("g, beta_shift", [(-1.6, 0.0), (-40.0, 0.0),
                                           (-0.01, 6.0)],
                         ids=["published", "beyond", "beta_near_2"])
def test_strong_decay_and_beta_near_two_stay_finite_and_agree(g, beta_shift):
    # -1.6 a position is the strongest published decay (A = 16, dt = 0.1):
    # over a chunk of 64 its factor exp(-G) alone is e^102, past float32
    q, k, v, _, beta, S0 = _inputs(1, 128, 2, 16, 8, seed=1,
                                   beta_shift=beta_shift)
    gs = jnp.full(q.shape, g)
    o0, s0 = jax.jit(kda.kda_scan_plain)(q, k, v, gs, beta, S0)
    o1, s1 = jax.jit(kda.kda_scan_chunked)(q, k, v, gs, beta, S0)
    assert np.isfinite(np.asarray(o1)).all() and np.isfinite(s1).all()
    if beta_shift:
        assert float(jnp.min(beta)) > 1.9
    np.testing.assert_allclose(o1, o0, atol=5e-5)
    np.testing.assert_allclose(s1, s0, atol=5e-5)


def test_zero_beta_and_g_leave_the_state_at_the_last_valid_position(scanned):
    # a padded bucket: 19 real positions of 64; the state after the bucket
    # is the state after the 19th, which is where 19 single steps arrive too
    (q, k, v, g, beta, S0), _ = scanned
    _, got = jax.jit(kda.kda_scan_chunked, static_argnums=6)(
        q, k, v, g.at[:, 19:].set(0.0), beta.at[:, 19:].set(0.0), S0, 16)
    step = jax.jit(kda.kda_step)
    S = S0
    for t in range(19):
        _, S = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
    np.testing.assert_allclose(got, S, atol=2e-5)


def test_steps_are_the_plain_scan(scanned):
    (q, k, v, g, beta, S), (o0, _) = scanned
    step = jax.jit(kda.kda_step)
    for t in range(6):
        o, S = step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        np.testing.assert_allclose(o, o0[:, t], atol=2e-5)


def test_unit_lower_inverse_by_blocks():
    rng = np.random.default_rng(0)
    M = np.tril(rng.normal(size=(3, 64, 64)) * 0.2, -1).astype(np.float32)
    X_ = np.asarray(jax.jit(kda._unit_lower_inverse)(jnp.asarray(M)))
    want = np.linalg.inv(np.eye(64) + M.astype(np.float64))
    np.testing.assert_allclose(X_, want, atol=1e-5 * np.abs(want).max())
    assert not np.triu(X_ - np.eye(64, dtype=np.float32)).any()


@pytest.mark.parametrize("heads", [8, 32])
def test_kda_step_kernel_interpreted(heads, counting):
    # 8: one block of heads a slot; 32: two blocks of 16
    q, k, v, g, beta, S = _inputs(3, 1, heads, 16, 128, seed=2)
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], S)
    want = kda._step_xla(*args)
    count = lambda: telemetry.get_telemetry().counters().get(
        "kda.step_route.kernel", 0)
    before = count()
    with pallas.interpret_mode():
        got = kda.kda_step(*args)
    assert count() == before + 1
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-6)


@pytest.mark.parametrize("shape, pallas_on, route", [
    ((128, 64, 128, 128), True, "kernel"),   # the published widths
    ((128, 64, 128, 128), False, "xla"),     # XLA:CPU, tier-1
    ((3, 4, 16, 16), True, "xla"),           # dv does not fill the lanes
    ((3, 4, 16, 128), True, "xla"),          # no block of 8 heads
    ((3, 24, 16, 128), True, "kernel"),      # blocks of 8 of 24 heads
], ids=["published", "no_pallas", "dv16", "heads4", "heads24"])
def test_step_route_table(shape, pallas_on, route):
    assert kda.step_route(state_shape=shape, pallas=pallas_on) == route


def test_head_blocks():
    assert KS.head_block(64) == 16 and KS.head_block(24) == 8
    assert KS.head_block(4) == 0 and KS.head_block(40) == 8


def _loop_over_experts(xs, w, te, tm, f):
    """Each tile against its expert, an expert at a time."""
    out = np.zeros((xs.shape[0], f), np.float32)
    for t, e in enumerate(np.asarray(te)):
        rows = np.asarray(xs[t * tm:(t + 1) * tm], np.float64)
        gate = rows @ np.asarray(w[e, :f], np.float64).T
        up = rows @ np.asarray(w[e, f:], np.float64).T
        out[t * tm:(t + 1) * tm] = gate / (1 + np.exp(-gate)) * up
    return out


@pytest.mark.parametrize("how", ["xla", "pallas"])
def test_gated_grouped_product_against_a_loop(how):
    rng = np.random.default_rng(0)
    T, k, E, K, f, tm = 40, 2, 4, 256, 128, 16
    local = jnp.asarray(rng.integers(-1, E, (T, k)), jnp.int32)
    tok, dest, te, na, counts = X.dispatch(local, jnp.ones(T, bool), E, tm)
    x = jnp.asarray(rng.normal(size=(T, K)) * 0.3, jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, 2 * f, K)) * 0.3, jnp.float32)
    xs = x[tok]
    want = _loop_over_experts(xs, w, te, tm, f)
    if how == "xla":
        got = moe_grouped.grouped_matmul_xla(xs, w, te, tm, "swiglu", True)
    else:
        with pallas.interpret_mode():
            got = moe_grouped.grouped_matmul_pallas(xs, w, te, na, tm,
                                                    "swiglu", True)
        # padding tiles past the live ones are written as zeros
        assert not np.asarray(got)[int(na[0]) * tm:].any()
    assert got.shape == (xs.shape[0], f)
    live = np.asarray(dest).reshape(-1)
    live = live[live < xs.shape[0]]
    assert len(live) == int(jnp.sum(counts))
    np.testing.assert_allclose(np.asarray(got)[live], want[live], rtol=1e-4,
                               atol=1e-4)


def test_gated_product_takes_its_stack_out_major():
    z = jnp.zeros
    with pytest.raises(ValueError, match="out-major"):
        moe_grouped.grouped_matmul_pallas(
            z((16, 128)), z((1, 128, 256)), z((1,), jnp.int32),
            z((1,), jnp.int32), 16, "swiglu", False)
    # one block holds the same rows of both matrices: it is priced double
    assert moe_grouped.pick_blocks(4096, 1280, 4) == (4096, 256)
    assert moe_grouped.supports_gated(16, 4096, 1280)
