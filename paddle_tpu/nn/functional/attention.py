"""Attention functionals.

Reference fused kernels: ``paddle/fluid/operators/fused/fused_attention_op.cu``
and ``fmha_ref.h``. Seven routes, chosen in ONE place, :func:`attention_route`,
from what the call can observe (shapes, the mask, dropout, the platform);
nothing a user sets picks a kernel:

* ``flash_packed`` / ``flash``: the Pallas flash-attention kernels
  (``paddle_tpu.ops.pallas.flash_attention_packed`` seq-major with no layout
  change, ``flash_attention`` layout-swapping) whenever shapes tile onto the
  MXU, at ``FLASH_MIN_SEQ_PROD`` and above;
* ``flash_cached`` / ``flash_decode``: cached (:class:`LengthMask`) serving
  calls on the TPU, the length-masked flash kernel for 128-aligned query
  blocks and the decode-shaped kernel for decode and verify;
* ``einsum_grouped``: a few query rows over a cache with grouped K/V heads;
* ``blockwise``: an online-softmax ``lax.scan`` over KV blocks
  (``_sdpa_blockwise``) that keeps the live logits at O(seq·block) instead of
  O(seq²) on every backend, from ``BLOCKWISE_MIN_KV`` keys up, for causal
  training and cached calls. It is what XLA:CPU runs, and what takes a cached
  query shape neither kernel does;
* ``einsum``: the XLA einsum path for everything else.

Routing is an EXPLICIT capability check, never a silent ``except`` fallback:
if a kernel is selected and fails, the error propagates.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ...framework import random as rnd
from ...ops.dispatch import op

#: additive-mask floor shared with serving.kv_cache.MASK_MIN
NEG_INF = -1e30


class LengthMask:
    """Compact validity descriptor for cached (length-masked) attention.

    Key slot ``j`` attends to query row ``i`` of batch ``b`` iff
    ``j <= q_pos[b, i]`` and, when ``kv_len`` is given, ``j < kv_len[b]``.
    ``q_pos`` is int32 ``[batch, q]`` (absolute position of each query row in
    the cache); ``kv_len`` is int32 ``[batch]`` (exclusive bound of rows ever
    written). The serving engine hands this to
    ``scaled_dot_product_attention`` instead of a dense ``[b, 1, q, max_len]``
    additive mask: the blockwise/Pallas paths consume the lengths directly and
    the einsum fallback expands the mask on the fly in the compute dtype.

    ``window`` (a static int; None: no lower bound, the meaning above) makes
    it a band: additionally ``j > q_pos[b, i] - window``, the query's own
    position and the ``window - 1`` before it. The blockwise scan and the
    cached flash kernel then visit only the key blocks that meet the band;
    the kernel sizes its sweep for query blocks whose rows stand at
    consecutive positions (a prompt, a chunk), which is what it is handed.
    """

    __slots__ = ("q_pos", "kv_len", "window")

    def __init__(self, q_pos, kv_len=None, window=None):
        self.q_pos = jnp.asarray(q_pos, jnp.int32)
        self.kv_len = None if kv_len is None else jnp.asarray(kv_len,
                                                              jnp.int32)
        if window is not None and int(window) < 1:
            raise ValueError(f"window must be at least 1, got {window}")
        self.window = None if window is None else int(window)

    def valid(self, sk):
        """Boolean ``[b, 1, q, sk]`` validity (broadcasts over heads)."""
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, sk), 3)
        ok = col <= self.q_pos[:, None, :, None]
        if self.kv_len is not None:
            ok = ok & (col < self.kv_len[:, None, None, None])
        if self.window is not None:
            ok = ok & (col > self.q_pos[:, None, :, None] - self.window)
        return ok

    def additive(self, sk, dtype, mask_min=-1e9):
        """Dense additive mask materialized on the fly in ``dtype`` — the
        short-sequence fallback; never an fp32 constant the compiler could
        fold and hold in HBM."""
        return jnp.where(self.valid(sk), jnp.asarray(0.0, dtype),
                         jnp.asarray(mask_min, dtype))


def _pick_block(n, pref):
    """Largest divisor of ``n`` that is <= ``pref`` (no padding: padding a
    KV cache block would copy the cache)."""
    for c in range(min(int(pref), n), 0, -1):
        if n % c == 0:
            return c
    return 1


# ---------------------------------------------------------------------------
# blockwise online-softmax scan (runs on every backend, incl. XLA:CPU)
# ---------------------------------------------------------------------------

def _bw_fwd(q, k, v, q_pos, kv_len, scale, block_k):
    """Forward scan over KV blocks. Carry: running (max, denom, acc) per
    query row; the only O(block)-wide temporary is the ``[b, h, sq,
    block_k]`` score tile of the current block.

    Written for backends without Pallas (XLA:CPU, tier-1) and for query
    shapes no kernel takes. It is not a decode path for the TPU: K and V are
    copied to float32 and re-laid out block-major before the scan, and every
    block is visited whatever the lengths say (168 of a 195 ms GPT-2 large
    decode step on the v5e before ``flash_sdpa_decode`` took that route)."""
    f32 = jnp.float32
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nb = sk // block_k
    qf = jnp.swapaxes(q, 1, 2).astype(f32) * scale            # [b,h,sq,d]
    ks = jnp.moveaxis(
        jnp.swapaxes(k, 1, 2).astype(f32).reshape(b, h, nb, block_k, d), 2, 0)
    vs = jnp.moveaxis(
        jnp.swapaxes(v, 1, 2).astype(f32).reshape(b, h, nb, block_k, d), 2, 0)
    base = jnp.arange(nb, dtype=jnp.int32) * block_k
    qpos_e = q_pos[:, None, :, None]
    klen_e = None if kv_len is None else kv_len[:, None, None, None]

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, b0 = xs
        s_ = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)
        col = b0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, block_k), 3)
        ok = col <= qpos_e
        if klen_e is not None:
            ok = ok & (col < klen_e)
        s_ = jnp.where(ok, s_, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
        # masked entries must contribute 0 even when the whole row is masked
        # so far (m_new == NEG_INF would make exp(s - m_new) = 1)
        p = jnp.where(ok, jnp.exp(s_ - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((b, h, sq), NEG_INF, f32)
    l0 = jnp.zeros((b, h, sq), f32)
    a0 = jnp.zeros((b, h, sq, d), f32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (ks, vs, base))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype), lse


def _bw_bwd(q, k, v, q_pos, kv_len, out, lse, g, scale, block_q, block_k):
    """FlashAttention-2 recurrence: dq scans K blocks, dk/dv scan Q blocks;
    every score tile is recomputed from the saved logsumexp so nothing
    O(sq·sk) is ever live."""
    f32 = jnp.float32
    b, sq, h, d = q.shape
    sk = k.shape[1]
    qf = jnp.swapaxes(q, 1, 2).astype(f32)
    kf = jnp.swapaxes(k, 1, 2).astype(f32)
    vf = jnp.swapaxes(v, 1, 2).astype(f32)
    gf = jnp.swapaxes(g, 1, 2).astype(f32)
    of = jnp.swapaxes(out, 1, 2).astype(f32)
    delta = jnp.sum(of * gf, axis=-1)                         # [b,h,sq]
    qpos_e = q_pos[:, None, :, None]
    klen_e = None if kv_len is None else kv_len[:, None, None, None]

    nbk = sk // block_k
    ks = jnp.moveaxis(kf.reshape(b, h, nbk, block_k, d), 2, 0)
    vs = jnp.moveaxis(vf.reshape(b, h, nbk, block_k, d), 2, 0)
    basek = jnp.arange(nbk, dtype=jnp.int32) * block_k

    def dq_body(dq, xs):
        kb, vb, b0 = xs
        s_ = jnp.einsum("bhqd,bhkd->bhqk", qf, kb) * scale
        col = b0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, block_k), 3)
        ok = col <= qpos_e
        if klen_e is not None:
            ok = ok & (col < klen_e)
        p = jnp.where(ok, jnp.exp(s_ - lse[..., None]), 0.0)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vb)
        ds = p * (dp - delta[..., None])
        return dq + jnp.einsum("bhqk,bhkd->bhqd", ds, kb) * scale, None

    dq, _ = jax.lax.scan(dq_body, jnp.zeros((b, h, sq, d), f32),
                         (ks, vs, basek))

    nbq = sq // block_q
    qs = jnp.moveaxis(qf.reshape(b, h, nbq, block_q, d), 2, 0)
    gs = jnp.moveaxis(gf.reshape(b, h, nbq, block_q, d), 2, 0)
    ls = jnp.moveaxis(lse.reshape(b, h, nbq, block_q), 2, 0)
    dls = jnp.moveaxis(delta.reshape(b, h, nbq, block_q), 2, 0)
    pqs = jnp.moveaxis(q_pos.reshape(b, nbq, block_q), 1, 0)
    colk = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, sk), 3)

    def dkv_body(carry, xs):
        dk, dv = carry
        qb, gb, lb, db, pq = xs
        s_ = jnp.einsum("bhqd,bhkd->bhqk", qb, kf) * scale
        ok = colk <= pq[:, None, :, None]
        if klen_e is not None:
            ok = ok & (colk < klen_e)
        p = jnp.where(ok, jnp.exp(s_ - lb[..., None]), 0.0)
        dv = dv + jnp.einsum("bhqk,bhqd->bhkd", p, gb)
        dp = jnp.einsum("bhqd,bhkd->bhqk", gb, vf)
        ds = p * (dp - db[..., None])
        dk = dk + jnp.einsum("bhqk,bhqd->bhkd", ds, qb) * scale
        return (dk, dv), None

    z = jnp.zeros((b, h, sk, d), f32)
    (dk, dv), _ = jax.lax.scan(dkv_body, (z, z), (qs, gs, ls, dls, pqs))

    def back(x, dt):
        return jnp.swapaxes(x, 1, 2).astype(dt)

    return back(dq, q.dtype), back(dk, k.dtype), back(dv, v.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _blockwise(q, k, v, q_pos, kv_len, scale, block_q, block_k):
    out, _ = _bw_fwd(q, k, v, q_pos, kv_len, scale, block_k)
    return out


def _blockwise_vjp_fwd(q, k, v, q_pos, kv_len, scale, block_q, block_k):
    # the custom vjp is mandatory, not an optimization: naive AD of the scan
    # would stack the per-block probability tiles into an O(sq·sk) residual
    out, lse = _bw_fwd(q, k, v, q_pos, kv_len, scale, block_k)
    return out, (q, k, v, q_pos, kv_len, out, lse)


def _blockwise_vjp_bwd(scale, block_q, block_k, res, g):
    q, k, v, q_pos, kv_len, out, lse = res
    dq, dk, dv = _bw_bwd(q, k, v, q_pos, kv_len, out, lse, g, scale,
                         block_q, block_k)
    zp = np.zeros(q_pos.shape, dtype=jax.dtypes.float0)
    zl = (None if kv_len is None
          else np.zeros(kv_len.shape, dtype=jax.dtypes.float0))
    return dq, dk, dv, zp, zl


_blockwise.defvjp(_blockwise_vjp_fwd, _blockwise_vjp_bwd)


def _bw_fwd_banded(q, k, v, q_pos, kv_len, window, scale, block_q, block_k):
    """The scan under a band (``LengthMask.window``): query blocks outside,
    key blocks inside, and a key block that no row of the query block can
    see is passed over (``lax.cond`` on the block's positions, so any
    ``q_pos`` is served right and a prompt's blocks visit the band alone).
    Forward only; serving holds no gradients."""
    f32 = jnp.float32
    b, sq, h, d = q.shape
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k

    def blocks(x, n, size):  # [b, s, h, d] -> [n, b, h, size, d], float32
        return jnp.moveaxis(
            jnp.swapaxes(x, 1, 2).astype(f32).reshape(b, h, n, size, d), 2, 0)

    qs = blocks(q, nq, block_q) * scale
    ks, vs = blocks(k, nk, block_k), blocks(v, nk, block_k)
    pos = jnp.moveaxis(q_pos.reshape(b, nq, block_q), 1, 0)  # [nq, b, bq]
    base = jnp.arange(nk, dtype=jnp.int32) * block_k
    klen_e = None if kv_len is None else kv_len[:, None, None, None]

    def q_block(xs):
        qb, pb = xs
        hi = jnp.max(pb)  # the last key a row of this block sees
        lo = jnp.min(jnp.where(pb >= 0, pb, hi)) - window + 1  # the first
        pe = pb[:, None, :, None]

        def visit(carry, kb, vb, b0):
            m, l, acc = carry
            s_ = jnp.einsum("bhqd,bhkd->bhqk", qb, kb)
            col = b0 + jax.lax.broadcasted_iota(jnp.int32,
                                                (1, 1, 1, block_k), 3)
            ok = (col <= pe) & (col > pe - window)
            if klen_e is not None:
                ok = ok & (col < klen_e)
            s_ = jnp.where(ok, s_, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
            p = jnp.where(ok, jnp.exp(s_ - m_new[..., None]), 0.0)
            alpha = jnp.exp(m - m_new)
            return (m_new, l * alpha + jnp.sum(p, axis=-1),
                    acc * alpha[..., None]
                    + jnp.einsum("bhqk,bhkd->bhqd", p, vb))

        def body(carry, ys):
            kb, vb, b0 = ys
            meets = (b0 <= hi) & (b0 + block_k > lo)
            return jax.lax.cond(meets, lambda c: visit(c, kb, vb, b0),
                                lambda c: c, carry), None

        init = (jnp.full((b, h, block_q), NEG_INF, f32),
                jnp.zeros((b, h, block_q), f32),
                jnp.zeros((b, h, block_q, d), f32))
        (_, l, acc), _ = jax.lax.scan(body, init, (ks, vs, base))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(q_block, (qs, pos))                    # [nq,b,h,bq,d]
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, sq, d)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


@op("blockwise_sdpa")
def _sdpa_blockwise(q, k, v, q_pos, kv_len=None, scale=None, block_q=0,
                    block_k=0, window=None):
    """Blockwise online-softmax attention (q,k,v in paddle (b,s,h,d)
    layout). ``q_pos``/``kv_len``/``window`` follow :class:`LengthMask`
    semantics."""
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if window is not None:
        return _bw_fwd_banded(q, k, v, q_pos, kv_len, window, s, block_q,
                              block_k)
    return _blockwise(q, k, v, q_pos, kv_len, s, block_q, block_k)


#: query rows of a decode-shaped call (decode 1; verify spec_k + 1): the
#: calls whose route ``attn.decode_route`` counts
DECODE_ROWS = 8


def _count_decode_route(route):
    """Counter ``attn.decode_route.<route>``: bumped when a decode-shaped
    cached call is TRACED (the route is a property of the compiled step,
    not of a tick), so a model that fell off the kernel says so."""
    from ...profiler import telemetry

    if telemetry.enabled():
        telemetry.get_telemetry().inc(f"attn.decode_route.{route}")


def prefill_band(route):
    """What a windowed cached call of more than :data:`DECODE_ROWS` query
    rows (a prefill bucket's window layer) got: ``banded`` where the route
    passes over key blocks outside the band (the cached flash kernel, the
    blockwise scan), ``dense`` where every key is scored and the band is a
    mask (the einsum routes: buckets under ``BLOCKWISE_MIN_KV``, no
    Pallas and few keys). Pure; table in ``tests/test_attention_window.py``."""
    return "banded" if route in ("flash_cached", "blockwise") else "dense"


def _count_prefill_band(route):
    """Counter ``attn.prefill_band.<banded|dense>``, bumped when such a call
    is TRACED, once a layer."""
    from ...profiler import telemetry

    if telemetry.enabled():
        telemetry.get_telemetry().inc(
            f"attn.prefill_band.{prefill_band(route)}")


def _repeat_kv_heads(key, value, heads):
    """Each K/V head once per query head of its group (uncached calls and
    prefill buckets: a few MB; never the cache)."""
    from ... import ops

    rep = heads // key.shape[2]
    return (ops.repeat_interleave(key, rep, axis=2),
            ops.repeat_interleave(value, rep, axis=2))


@op("sdpa_grouped_decode")
def _sdpa_grouped_decode(q, k, v, q_pos, kv_len=None, scale=None,
                         window=None):
    """A few query rows over a cache whose K/V heads each serve a GROUP of
    query heads: one einsum per product with the group as a batch
    dimension, the cache read in its own dtype and never repeated per
    query head; scores and softmax in float32."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(b, sq, hk, h // hk, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32) * s
    # [b, 1, 1, q, sk]
    ok = LengthMask(q_pos, kv_len, window).valid(sk)[:, :, None]
    probs = jax.nn.softmax(jnp.where(ok, logits, NEG_INF), axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


# Thresholds the routes turn on, each beside where it was measured. Constants,
# not options: a threshold that moves, moves a cell (ROADMAP W7 asks for a
# cell on the other side of each).
#
# Measured crossover on the v5e (GPT-2 124M, d=64): below sq*sk = 1024^2 XLA's
# fused einsum attention wins; at 1024^2+ the Pallas kernel with 1024-wide
# blocks is faster (s=1024 end-to-end: 102.6k vs 88.0k tok/s) and keeps memory
# flat at long context.
FLASH_MIN_SEQ_PROD = 1024 * 1024
# Key slots from which a call takes the blockwise scan (or, on the TPU, the
# cached kernels that stand before it): below it the fused einsum is faster
# and its score matrix is small anyway. Set with the scan (PR 15), on XLA:CPU;
# never fitted on the chip.
BLOCKWISE_MIN_KV = 1024
# Blocks of the blockwise scan (the largest divisor of the sequence at or
# under each is used); PR 15's long-context runs, XLA:CPU.
BLOCKWISE_BLOCK_Q = 512
BLOCKWISE_BLOCK_K = 512


def attention_route(*, batch, sq, sk, heads, kv_heads, head_dim, kv_itemsize,
                    cached, causal, mask_shape, mask_trainable, dropout,
                    pallas, interpret, window=None):
    """The one place a route is chosen. Shape facts and the two things the
    platform tells (``pallas.is_available()``, ``pallas.interpret_requested()``)
    come in as arguments and no global state is read, so a test can ask what
    the chip compiles. ``cached`` says the mask is a :class:`LengthMask` (and
    ``mask_shape`` is None); ``dropout`` that attention dropout is active;
    ``window`` is the mask's (``LengthMask.window``): every cached route
    honours it but the decode-shaped kernel, which is then not chosen.

    Returns one of ``flash_packed``, ``flash``, ``flash_cached``,
    ``flash_decode``, ``einsum_grouped``, ``blockwise``, ``einsum``."""
    from ...ops.pallas import flash_attention_packed as packed
    from ...ops.pallas.flash_attention import supports, supports_cached
    from ...ops.pallas.flash_decode import supports_decode

    # neither the scan nor the cached kernels have an in-kernel PRNG
    long_kv = not dropout and sk >= BLOCKWISE_MIN_KV
    if cached:
        # With Pallas: the decode-shaped kernel for a few query rows (decode,
        # verify), the length-masked flash kernel for 128-aligned query
        # blocks (long prefill buckets, prefill chunks). The blockwise scan
        # is what is left: every backend without Pallas (XLA:CPU, tier-1) and
        # query shapes neither kernel takes. Below the threshold (or under
        # attention dropout): dense on-the-fly mask.
        if kv_heads != heads and sq <= DECODE_ROWS and not dropout:
            return "einsum_grouped"
        # every route below takes grouped K/V heads repeated per query head
        if not long_kv:
            return "einsum"
        if pallas and window is None and supports_decode(
                sq, sk, heads, head_dim, kv_itemsize):
            return "flash_decode"
        if pallas and supports_cached(sq, sk, head_dim):
            return "flash_cached"
        return "blockwise"
    # The flash kernels: TPU (or interpret-mode) backend, MXU-tileable
    # sequence lengths and, when a mask is given, a mask the kernel streams
    # exactly: trailing dims (sq, sk) with broadcastable batch/head dims.
    # Trainable biases are supported: the fused backward computes the real
    # dS-sum bias gradient (XLA-DCE'd when unused). Attention dropout runs
    # in-kernel via the TPU hardware PRNG: compiled-TPU only (no interpret
    # lowering) and incompatible with a trainable bias (the XLA dbias
    # recompute cannot regenerate the in-kernel mask).
    flash = not (dropout and (interpret or mask_trainable))
    if sq * sk < FLASH_MIN_SEQ_PROD and not interpret:
        flash = False
    if mask_shape is not None:
        ms = tuple(mask_shape)
        if len(ms) == 4:
            if (ms[2:] != (sq, sk) or ms[0] not in (1, batch)
                    or ms[1] not in (1, heads)):
                flash = False
        elif ms != (sq, sk):
            flash = False
    if flash and pallas and supports(sq, sk, head_dim):
        # the seq-major packed kernel (zero layout transposes: the
        # (b,s,h,d)->(b,s,h*d) reshape is free) whenever the head dim packs
        # into 128-lane groups and the mask is shared-2-D/absent;
        # per-batch/per-head or trainable biases take the layout-swapping one
        shared_mask = mask_shape is None or (len(ms) == 2
                                             and not mask_trainable)
        if shared_mask and packed.supports(sq, sk, heads, heads * head_dim):
            return "flash_packed"
        return "flash"
    if mask_shape is None and causal and long_kv:
        # long causal training without Pallas (e.g. XLA:CPU): blockwise scan
        # instead of the O(seq²) einsum score matrix
        return "blockwise"
    return "einsum"


@op("flash_sdpa")
def _sdpa_flash(q, k, v, mask=None, dropout_seed=None, causal=False,
                scale=None, mask_trainable=False, dropout_p=0.0,
                packed=False):
    """q,k,v: (batch, seq, heads, head_dim) — paddle layout. ``packed``: the
    seq-major packed kernel (route ``flash_packed``) in place of the
    layout-swapping one (route ``flash``)."""
    if packed:
        from ...ops.pallas.flash_attention_packed import flash_attention_packed

        b, sq, h, d = q.shape
        sk = k.shape[1]
        out = flash_attention_packed(
            q.reshape(b, sq, h * d), k.reshape(b, sk, h * d),
            v.reshape(b, sk, h * d), h, bias=mask, causal=causal,
            scale=scale, dropout_p=dropout_p, dropout_seed=dropout_seed)
        return out.reshape(b, sq, h, d)
    from ...ops.pallas.flash_attention import flash_attention as fa

    return fa(q, k, v, bias=mask, causal=causal, scale=scale,
              bias_grad=mask_trainable,
              dropout_p=dropout_p, dropout_seed=dropout_seed)


@op("flash_sdpa_cached")
def _sdpa_flash_cached(q, k, v, q_pos, kv_len=None, scale=None, window=None):
    """Pallas length-masked (cached-attention) kernel — inference path; the
    per-tile validity comes from the streamed positions, never a dense
    bias. Under a ``window`` its banded form, which sweeps the key blocks
    that meet each query block's band and no others."""
    from ...ops.pallas.flash_attention import flash_attention_cached

    return flash_attention_cached(q, k, v, q_pos, kv_len, scale=scale,
                                  window=window)


@op("flash_sdpa_decode")
def _sdpa_flash_decode(q, k, v, q_pos, kv_len=None, scale=None):
    """Pallas decode-shaped kernel: a few query rows per cache row (decode,
    speculative verify), reading the cache once, in its own dtype and
    layout, up to the live length — inference path."""
    from ...ops.pallas.flash_decode import flash_attention_decode

    return flash_attention_decode(q, k, v, q_pos, kv_len, scale=scale)


@op("sdpa")
def _sdpa_raw(q, k, v, mask=None, dropout_mask=None, causal=False, scale=None,
              dropout_p=0.0):
    """XLA einsum path (small/odd shapes, or attention dropout active).

    ``dropout_mask`` is a keep-mask drawn by the caller (so the op stays a
    pure function of its inputs and remains jit-traceable).
    """
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * s
    if causal:
        # iota compare, not jnp.tril of a ones constant: the latter const-
        # folds into an fp32 [s, s] executable constant charged against HBM
        # (O(seq²) bytes at 32k — the hbm-const-folded finding)
        ql, kl = logits.shape[-2], logits.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (ql, kl), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (ql, kl), 1)
        logits = jnp.where(col - row <= kl - ql, logits,
                           jnp.asarray(NEG_INF, logits.dtype))
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_mask is not None:
        probs = probs * dropout_mask.astype(probs.dtype) / (1.0 - dropout_p)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _sdpa(query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False,
          training=True, scale=None):
    from ...ops import pallas

    b, sq, h, d = query.shape
    sk = key.shape[1]
    cached = isinstance(attn_mask, LengthMask)
    window = attn_mask.window if cached else None
    trainable = (not cached and attn_mask is not None
                 and getattr(attn_mask, "stop_gradient", True) is False)
    active_p = dropout_p if training else 0.0
    route = attention_route(
        batch=b, sq=sq, sk=sk, heads=h, kv_heads=key.shape[2], head_dim=d,
        kv_itemsize=key.dtype.itemsize, cached=cached, causal=is_causal,
        mask_shape=(None if cached or attn_mask is None
                    else tuple(attn_mask.shape)),
        mask_trainable=trainable, dropout=active_p > 0.0,
        pallas=pallas.is_available(), interpret=pallas.interpret_requested(),
        window=window)
    if cached and sq <= DECODE_ROWS:
        _count_decode_route(route)
    elif window is not None:
        _count_prefill_band(route)
    if route == "einsum_grouped":
        return _sdpa_grouped_decode(query, key, value, attn_mask.q_pos,
                                    attn_mask.kv_len, scale=scale,
                                    window=window)
    if key.shape[2] != h:
        key, value = _repeat_kv_heads(key, value, h)
    if route in ("flash_packed", "flash"):
        seed = None
        if active_p > 0.0:
            # two 32-bit words of a fresh key seed the in-kernel PRNG
            seed = jax.lax.bitcast_convert_type(
                jax.random.bits(rnd.next_key(), (2,), jnp.uint32), jnp.int32
            )
        return _sdpa_flash(query, key, value, attn_mask, seed,
                           causal=is_causal, scale=scale,
                           mask_trainable=trainable, dropout_p=active_p,
                           packed=route == "flash_packed")
    if route == "einsum":
        mask = attn_mask.additive(sk, query.dtype) if cached else attn_mask
        dropout_mask = None
        if active_p > 0.0:
            dropout_mask = jax.random.bernoulli(
                rnd.next_key(), 1.0 - active_p, (b, h, sq, sk))
        return _sdpa_raw(query, key, value, mask, dropout_mask,
                         causal=is_causal and not cached, scale=scale,
                         dropout_p=active_p)
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    if cached:
        q_pos, kv_len = attn_mask.q_pos, attn_mask.kv_len
    else:  # causal, bottom-right aligned
        q_pos = jnp.broadcast_to(
            jnp.arange(sk - sq, sk, dtype=jnp.int32)[None, :], (b, sq))
        kv_len = None
    if route == "flash_decode":
        return _sdpa_flash_decode(query, key, value, q_pos, kv_len, scale=s)
    if route == "flash_cached":
        return _sdpa_flash_cached(query, key, value, q_pos, kv_len, scale=s,
                                  window=window)
    if route == "blockwise":
        return _sdpa_blockwise(query, key, value, q_pos, kv_len, scale=s,
                               block_q=_pick_block(sq, BLOCKWISE_BLOCK_Q),
                               block_k=_pick_block(sk, BLOCKWISE_BLOCK_K),
                               window=window)
    raise ValueError(f"attention_route returned {route!r}")


def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False,
    training=True, name=None
):
    return _sdpa(query, key, value, attn_mask, dropout_p, is_causal, training)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    out = _sdpa(query, key, value, None, dropout, causal, training)
    return out, None
