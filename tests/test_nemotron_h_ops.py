"""The Mamba-2 recurrence's three forms against each other, the Pallas
kernels (interpreted) against their XLA formulations, and attention with
grouped K/V heads through the cached routes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional import ssm
from paddle_tpu.nn.functional.attention import LengthMask
from paddle_tpu.nn.layer import experts as X
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import moe_grouped
from paddle_tpu.profiler import telemetry

import reference_nemotron_h as R


def _ssm_inputs(b, L, H, P, G, N, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (b, L, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (b, L, H)) - 2),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (b, L, G, N)),
            jax.random.normal(k[4], (b, L, G, N)),
            jax.random.normal(k[5], (b, H, P, N)))


@pytest.fixture(scope="module")
def scanned():
    """Inputs, and what the plain scan makes of them (compiled once)."""
    inputs = _ssm_inputs(2, 32, 4, 8, 2, 16)
    return inputs, jax.jit(ssm.ssm_scan_plain)(*inputs)


@pytest.mark.parametrize("chunk", [8, 128])
def test_chunked_scan_is_the_plain_scan(scanned, chunk):
    (x, dt, A, B, C, S0), (y0, s0) = scanned
    y1, s1 = jax.jit(ssm.ssm_scan_chunked, static_argnums=6)(
        x, dt, A, B, C, S0, chunk)
    np.testing.assert_allclose(y1, y0, atol=2e-5)
    np.testing.assert_allclose(s1, s0, atol=2e-5)


def test_zero_dt_leaves_the_state_at_the_last_valid_position(scanned):
    # a padded bucket: 19 real positions of 32; the state after the bucket
    # is the state after the 19th, which is where 19 single steps arrive too
    (x, dt, A, B, C, S0), _ = scanned
    _, got = jax.jit(ssm.ssm_scan_chunked, static_argnums=6)(
        x, dt.at[:, 19:].set(0.0), A, B, C, S0, 8)
    step = jax.jit(ssm.ssm_step)
    S = S0
    for t in range(19):
        _, S = step(x[:, t], dt[:, t], A, B[:, t], C[:, t], S)
    np.testing.assert_allclose(got, S, atol=2e-5)


def test_steps_are_the_plain_scan(scanned):
    (x, dt, A, B, C, S), (y0, _) = scanned
    step = jax.jit(ssm.ssm_step)
    for t in range(6):
        y, S = step(x[:, t], dt[:, t], A, B[:, t], C[:, t], S)
        np.testing.assert_allclose(y, y0[:, t], atol=2e-5)


@pytest.mark.parametrize("kernel", ["step", "carry"])
def test_ssm_kernels_interpreted(kernel):
    x, dt, A, B, C, S = _ssm_inputs(2, 16, 8, 8, 2, 128, seed=3)
    if kernel == "step":
        want = ssm.ssm_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], S)
        with pallas.interpret_mode():
            got = ssm.ssm_step(x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], S)
    else:
        # a fresh function each: jit would hand the second call the first's
        # trace, taken before the kernels were switched on
        want = jax.jit(lambda *a: ssm.ssm_scan_chunked(*a, 4))(
            x, dt, A, B, C, S)
        with pallas.interpret_mode():
            got = jax.jit(lambda *a: ssm.ssm_scan_chunked(*a, 4))(
                x, dt, A, B, C, S)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


@pytest.mark.parametrize("transpose_rhs", [False, True])
@pytest.mark.parametrize("activation", [None, "relu2"])
def test_grouped_product_kernel_interpreted(transpose_rhs, activation):
    rng = np.random.default_rng(0)
    T, k, E, K, N, tm = 40, 2, 4, 128, 256, 16
    local = jnp.asarray(rng.integers(-1, E, (T, k)), jnp.int32)
    tok, dest, te, na, counts = X.dispatch(local, jnp.ones(T, bool), E, tm)
    x = jnp.asarray(rng.normal(size=(T, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, N, K) if transpose_rhs
                               else (E, K, N)), jnp.float32)
    xs = x[tok]
    want = moe_grouped.grouped_matmul_xla(xs, w, te, tm, activation,
                                          transpose_rhs)
    with pallas.interpret_mode():
        got = moe_grouped.grouped_matmul_pallas(xs, w, te, na, tm,
                                                activation, transpose_rhs)
    live = np.asarray(dest).reshape(-1)
    live = live[live < xs.shape[0]]
    assert len(live) == int(jnp.sum(counts)) == int(jnp.sum(local >= 0))
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=1e-4, atol=1e-4)
    # padding tiles past the live ones are written as zeros
    assert not np.asarray(got)[int(na[0]) * tm:].any()


def test_dispatch_starts_every_expert_on_a_tile_boundary():
    local = jnp.asarray([[0, 2], [2, -1], [2, 3], [0, 2], [-1, -1]], jnp.int32)
    valid = jnp.asarray([True, True, True, True, False])
    tok, dest, te, na, counts = X.dispatch(local, valid, 4, 16)
    assert counts.tolist() == [2, 0, 4, 1] and int(na[0]) == 3
    assert te.tolist()[:3] == [0, 2, 3]
    assert dest.tolist()[:4] == [[0, 16], [17, 80], [18, 32], [1, 19]]
    assert dest.tolist()[4] == [80, 80]  # a padding token: nowhere
    assert tok[:2].tolist() == [0, 3] and tok[16:20].tolist() == [0, 1, 2, 3]


@pytest.mark.parametrize("sq", [1, 3])
def test_grouped_kv_decode_attention(sq, counting):
    # 4 query heads over 2 K/V heads against a static-shape cache
    rng = np.random.default_rng(sq)
    b, sk, h, hk, d = 3, 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, hk, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, hk, d)), jnp.float32)
    pos = jnp.asarray(rng.integers(sq, sk - sq, (b, 1)) + np.arange(sq),
                      jnp.int32)
    before = telemetry.get_telemetry().counters().get(
        "attn.decode_route.einsum_grouped", 0)
    out = F.scaled_dot_product_attention(q, k, v, attn_mask=LengthMask(pos),
                                         training=False)
    assert telemetry.get_telemetry().counters()[
        "attn.decode_route.einsum_grouped"] == before + 1
    kr, vr = (jnp.repeat(a, h // hk, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kr) / np.sqrt(d)
    ok = jnp.arange(sk)[None, None, None, :] <= pos[:, None, :, None]
    want = jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(jnp.where(ok, s, -jnp.inf), -1), vr)
    np.testing.assert_allclose(np.asarray(getattr(out, "_value", out)), want,
                               atol=1e-5)


def test_grouped_kv_causal_attention_is_the_references():
    rng = np.random.default_rng(11)
    L, h, hk, d, hid = 24, 4, 2, 16, 32
    p = {n: jnp.asarray(rng.normal(size=s) * 0.2, jnp.float32)
         for n, s in (("q_proj", (hid, h * d)), ("k_proj", (hid, hk * d)),
                      ("v_proj", (hid, hk * d)), ("o_proj", (h * d, hid)))}
    u = jnp.asarray(rng.normal(size=(L, hid)), jnp.float32)
    cfg = {"num_attention_heads": h, "num_key_value_heads": hk, "head_dim": d}
    want = R.attention(u, p, cfg)
    q = (u @ p["q_proj"]).reshape(1, L, h, d)
    k = (u @ p["k_proj"]).reshape(1, L, hk, d)
    v = (u @ p["v_proj"]).reshape(1, L, hk, d)
    o = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                       training=False)
    got = getattr(o, "_value", o).reshape(L, h * d) @ p["o_proj"]
    np.testing.assert_allclose(got, want, atol=1e-4)
