"""Kimi Delta Attention's recurrence (arXiv:2510.26692), three forms of one
equation.

Per head, with the state ``S [dk, dv]`` in float32, a decay PER CHANNEL
``alpha_t = exp(g_t)`` in ``(0, 1]^dk`` and a step ``beta_t`` in ``[0, 2)``::

    S' = Diag(alpha_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T        o_t = S_t^T q_t

that is ``(I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``:
the delta rule, ``k`` of unit length, so every factor is a contraction for
``beta`` under 2.

* :func:`kda_scan_plain` — ``lax.scan`` over single positions: what the
  other two are tested against;
* :func:`kda_scan_chunked` — prefill: chunks of ``chunk`` positions. Inside
  a chunk the corrections ``u_t = beta_t (v_t - S'^T k_t)`` of all
  positions solve one unit lower-triangular system (the WY / UT transform):
  ``(I + tril(Diag(beta) A, -1)) U = Diag(beta) (V - (K . Gamma) S_0)`` with
  ``A[t, i] = sum_d k_t[d] k_i[d] exp(G_t[d] - G_i[d])`` and ``G`` the
  running sum of ``g`` inside the chunk; between chunks a scan carries the
  state. ``exp(G_t - G_i)`` is at most 1 but its factors ``exp(G_t)``,
  ``exp(-G_i)`` are not: with a decay per channel ``exp(-G_i)`` passes
  float32 inside one chunk (``g`` of -1.6 a position: ``e^102``), so the
  products are taken PAIRWISE inside sub-blocks of :data:`SUB` positions and
  relative to the sub-block's first position between them: every exponent
  is at most 0, for any decay;
* :func:`kda_step` — decode: one position for every slot; on the TPU the
  Pallas kernel ``kda_step_fwd`` (``ops/pallas/kda_step.py``), which reads
  and writes each slot's state once, in place. :func:`step_route` says
  which, from shapes alone.

All take ``q`` and ``k`` as the mixer hands them (normalised, ``q`` scaled),
``g = log(alpha)`` and ``beta``. A position whose ``g`` and ``beta`` are 0
leaves the state as it was: that is how a padded prefill bucket returns the
state at its last valid position. Convolutions, norms and gates are the
mixer's (``nn/layer/kda.py``).

Shapes: ``q, k, g [b, L, H, dk]``, ``v [b, L, H, dv]``, ``beta [b, L, H]``,
``S [b, H, dk, dv]``; the step drops ``L``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
#: positions of a chunk of the chunked form: the program's, not the model's
CHUNK = 64
#: positions of a sub-block of a chunk (pairwise products inside)
SUB = 16


def _step_xla(q, k, v, g, beta, S):
    S = jnp.exp(g)[..., None] * S
    w = v - jnp.einsum("bhdv,bhd->bhv", S, k, precision=_HI)
    S = S + (beta[..., None] * k)[..., None] * w[..., None, :]
    return jnp.einsum("bhdv,bhd->bhv", S, q, precision=_HI), S


def step_route(*, state_shape, pallas):
    """The one place the decode step's route is chosen: ``kernel``
    (``ops/pallas/kda_step.py``) or ``xla``. Shape facts and what the
    platform tells (``pallas.is_available()``) come in as arguments and no
    global state is read, so a test can ask what the chip compiles."""
    from ...ops.pallas.kda_step import supports_step

    return "kernel" if pallas and supports_step(state_shape) else "xla"


def _count_step_route(route):
    """Counter ``kda.step_route.<route>``: bumped when a decode-shaped call
    is TRACED, once a layer, as ``kv.row_write_route.<route>`` is."""
    from ...profiler import telemetry

    if telemetry.enabled():
        telemetry.get_telemetry().inc(f"kda.step_route.{route}")


def kda_step(q, k, v, g, beta, S):
    """One position: ``q, k, g [b, H, dk]``, ``v [b, H, dv]``, ``beta [b,
    H]``, ``S [b, H, dk, dv]`` -> ``(o [b, H, dv], S_new)``, float32. The
    kernel writes the state it is handed: on the TPU the caller donates."""
    from ...ops import pallas

    q, k, v, g, beta, S = (a.astype(F32) for a in (q, k, v, g, beta, S))
    route = step_route(state_shape=S.shape, pallas=pallas.is_available())
    _count_step_route(route)
    if route == "kernel":
        from ...ops.pallas.kda_step import kda_step_pallas

        return kda_step_pallas(q, k, v, g, beta, S)
    with jax.named_scope("kda_step_xla"):
        return _step_xla(q, k, v, g, beta, S)


def kda_scan_plain(q, k, v, g, beta, S0):
    """The recurrence as written, one position at a time."""
    q, k, v, g, beta, S0 = (a.astype(F32) for a in (q, k, v, g, beta, S0))

    def body(S, t):
        o, S = _step_xla(*t, S)
        return S, o

    S, o = jax.lax.scan(
        body, S0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), S


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HI)


def _forward_substitution(M):
    """``(I + M)^-1`` for strictly lower ``M [..., n, n]``, a row at a time:
    row ``i`` of ``X = (I + M)^-1 - I`` is ``-M_i - sum_{j<i} M_ij X_j`` over
    the rows above it, which are final by then. The batch lies on the lanes
    (``[n, n, batch]``), so a row is one fused multiply-add over whole
    vectors and nothing is updated in place."""
    n = M.shape[-1]
    Mt = jnp.moveaxis(M.reshape(-1, n, n), 0, -1)
    rows = [-Mt[0]]
    for i in range(1, n):
        rows.append(-Mt[i] - sum(Mt[i, j] * rows[j] for j in range(i)))
    X = jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(M.shape)
    return X + jnp.eye(n, dtype=M.dtype)


def _unit_lower_inverse(M):
    """``(I + M)^-1`` for strictly lower ``M [..., n, n]``: forward
    substitution on diagonal blocks of at most :data:`SUB`, merged pairwise
    (``[[X11, 0], [-X22 M21 X11, X22]]``); both halves in one batch."""
    n = M.shape[-1]
    if n <= SUB or n % 2:
        return _forward_substitution(M)
    h = n // 2
    X11, X22 = _unit_lower_inverse(
        jnp.stack([M[..., :h, :h], M[..., h:, h:]]))
    X21 = -_mm(_mm(X22, M[..., h:, :h]), X11)
    return jnp.concatenate(
        [jnp.concatenate([X11, jnp.zeros_like(X11)], -1),
         jnp.concatenate([X21, X22], -1)], -2)


def _decayed_lower(rows, cols, G, sub):
    """For each ``x`` of ``rows``: ``M[t, i] = sum_d x[t, d] cols[i, d]
    exp(G[t, d] - G[i, d])`` for ``i <= t``, 0 above the diagonal
    (``x, cols, G [..., C, d]`` -> ``[..., C, C]``). ``G`` does not rise
    along ``C``; no exponent taken here is above 0."""
    *lead, C, d = cols.shape
    ns = C // sub
    blk = lambda a: a.reshape(*lead, ns, sub, d)
    Gb, cb = blk(G), blk(cols)
    low = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    # inside a sub-block: pairwise
    E = jnp.exp(jnp.where(low, Gb[..., :, None, :] - Gb[..., None, :, :],
                          -jnp.inf))
    # between sub-blocks: relative to the row block's first position
    ref = Gb[..., :, :1, :]                                 # [.., ns, 1, d]
    down = jnp.exp(Gb - ref)                                # rows' factor
    outs = []
    for x in rows:
        xb = blk(x)
        diag = jnp.sum(xb[..., :, None, :] * cb[..., None, :, :] * E, -1)
        scaled = xb * down
        bands = []
        for a in range(ns):
            parts = []
            if a:
                left = cols[..., :a * sub, :] * jnp.exp(
                    ref[..., a, :, :] - G[..., :a * sub, :])
                parts.append(jnp.einsum("...td,...id->...ti",
                                        scaled[..., a, :, :], left,
                                        precision=_HI))
            parts.append(diag[..., a, :, :])
            if a < ns - 1:
                parts.append(jnp.zeros((*lead, sub, C - (a + 1) * sub),
                                       cols.dtype))
            bands.append(jnp.concatenate(parts, -1))
        outs.append(jnp.concatenate(bands, -2))
    return outs


def kda_scan_chunked(q, k, v, g, beta, S0, chunk=CHUNK):
    """Chunks of ``chunk`` positions (``L`` a multiple of it, or shorter
    than it): ``(o [b, L, H, dv], S_last)``."""
    q, k, v, g, beta, S0 = (a.astype(F32) for a in (q, k, v, g, beta, S0))
    b, L, H, dk = k.shape
    C = min(int(chunk), L)
    if L % C:
        raise ValueError(f"kda_scan_chunked: {L} positions are no multiple "
                         f"of the chunk of {C}")
    nc = L // C
    sub = SUB if C % SUB == 0 else C

    def chunks(a):  # [b, L, H, d] -> [b, nc, H, C, d]
        return jnp.moveaxis(a.reshape(b, nc, C, H, a.shape[-1]), 3, 2)

    with jax.named_scope("kda_chunk"):
        qc, kc, vc, gc = (chunks(a) for a in (q, k, v, g))
        bc = chunks(beta[..., None])                    # [b, nc, H, C, 1]
        G = jnp.cumsum(gc, axis=3)
        A, P = _decayed_lower((kc, qc), kc, G, sub)
        strict = jnp.tril(jnp.ones((C, C), bool), -1)
        # T = (I + tril(Diag(beta) A, -1))^-1 Diag(beta)
        T = _unit_lower_inverse(jnp.where(strict, bc * A, 0.0)) \
            * jnp.swapaxes(bc, -1, -2)
        decay = jnp.exp(G)
        U0 = _mm(T, vc)                                 # [b, nc, H, C, dv]
        W = _mm(T, kc * decay)                          # [b, nc, H, C, dk]
        last = G[..., -1:, :]                           # [b, nc, H, 1, dk]
        to_end = kc * jnp.exp(last - G)
        xs = tuple(jnp.moveaxis(a, 1, 0) for a in (
            U0, W, to_end, jnp.exp(last[..., 0, :]), qc * decay, P))

        def body(S, t):
            u0, w, ke, end, qd, p = t
            u = u0 - _mm(w, S)
            o = _mm(qd, S) + _mm(p, u)
            S = end[..., None] * S + jnp.einsum("bhcd,bhcv->bhdv", ke, u,
                                                precision=_HI)
            return S, o

        S, o = jax.lax.scan(body, S0, xs)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)       # [b, nc, C, H, dv]
    return o.reshape(b, L, H, o.shape[-1]), S
