"""Plain GPT-2: forward, loss, gradients and AdamW in float32 ``jax.numpy``.

The yardstick that decides ``correct``. It follows the published model
(pre-LN blocks, ``gelu_new``, learned positions, head tied to the token
embedding) and the optimizer the configuration states (AdamW, decoupled
decay on every leaf, bias-corrected). No kernels, no cache, no batching
tricks: attention is the full score matrix with a causal mask. Matrix
products run at ``highest`` precision (on a TPU float32 is otherwise
multiplied in bfloat16 passes). It imports nothing of ``paddle_tpu`` and is
given nothing the program made: the weights come from ``weights.make`` and
the same seed. Rows are taken in blocks so that it fits beside nothing else.

``lowp="fp8"`` is the control, not a reference: every linear layer and the
head multiply operands rounded to float8 (e4m3, scaled per tensor), the
step below bfloat16 that a later PR might be tempted to take.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

F32 = jnp.float32


def _fp8(a):
    """Round to float8 (e4m3) under a per-tensor scale; the gradient passes
    straight through (it is not rounded)."""
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    q = (a * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale
    return a + jax.lax.stop_gradient(q - a)


def _mm(a, b, lowp):
    if lowp == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision="highest")


def _ln(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _layer(x, lw, n_head, eps, lowp):
    b, s, h = x.shape
    d = h // n_head
    y = _ln(x, lw["ln1_g"], lw["ln1_b"], eps)
    qkv = _mm(y, lw["qkv_w"], lowp) + lw["qkv_b"]
    q, k, v = (qkv[..., i * h:(i + 1) * h].reshape(b, s, n_head, d)
               for i in range(3))
    scores = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        precision="highest") / np.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bnqk,bknd->bqnd", probs, v,
                     precision="highest").reshape(b, s, h)
    x = x + _mm(att, lw["out_w"], lowp) + lw["out_b"]
    y = _ln(x, lw["ln2_g"], lw["ln2_b"], eps)
    y = _gelu_new(_mm(y, lw["up_w"], lowp) + lw["up_b"])
    return x + _mm(y, lw["down_w"], lowp) + lw["down_b"]


def logits_fn(w, ids, n_head, eps, lowp=None):
    """``[rows, s]`` token ids -> ``[rows, s, vocab]`` float32 logits."""
    s = ids.shape[1]
    x = w["wte"][ids] + w["wpe"][:s]
    layers = {k: w[k] for k in W.LAYER_LEAVES}
    body = jax.checkpoint(
        lambda x, lw: (_layer(x, lw, n_head, eps, lowp), None))
    x, _ = jax.lax.scan(body, x, layers)
    x = _ln(x, w["lnf_g"], w["lnf_b"], eps)
    return _mm(x, w["wte"].T, lowp)


def _loss_sum(w, ids, labels, n_head, eps, lowp):
    lg = logits_fn(w, ids, n_head, eps, lowp)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], -1)[..., 0]
    return jnp.sum(lse - picked)


def upcast(stacked):
    return {k: v.astype(F32) for k, v in stacked.items()}


def _leaf_norms(tree):
    """Per program leaf: a stacked layer leaf gives one norm per layer, the
    fused query-key-value leaves one per layer and part."""
    out = {}
    for k, v in tree.items():
        if k in W.SPLIT3:
            v = v.reshape(v.shape[:-1] + (3, v.shape[-1] // 3))
            axes = tuple(a for a in range(1, v.ndim) if a != v.ndim - 2)
        else:
            axes = tuple(range(1, v.ndim)) if k in W.LAYER_LEAVES else None
        out[k] = jnp.sqrt(jnp.sum(jnp.square(v), axis=axes))
    return out


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9),
                   donate_argnums=(0, 1, 2))
def _train_step(w, m, v, t, ids, labels, n_head, eps, hp, lowp):
    """One AdamW step over ``ids [blocks, rows, s]``; the mean loss, the
    per-leaf gradient norms and the new state."""
    lr, b1, b2, adam_eps, wd = hp
    n_tok = ids.shape[0] * ids.shape[1] * ids.shape[2]

    def block(acc, xy):
        loss, g = jax.value_and_grad(_loss_sum)(
            w, xy[0], xy[1], n_head, eps, lowp)
        return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, w))
    (loss, g), _ = jax.lax.scan(block, zero, (ids, labels))
    g = jax.tree.map(lambda x: x / n_tok, g)
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, g)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    w = jax.tree.map(
        lambda w, m, v: w * (1 - lr * wd)
        - lr * (m / c1) / (jnp.sqrt(v / c2) + adam_eps), w, m, v)
    return w, m, v, loss / n_tok, _leaf_norms(g)


@jax.jit
def _change_norms(w, w0):
    return _leaf_norms(jax.tree.map(jnp.subtract, w, w0))


def train(seed, sizes, dtype, batches, hp, block_rows, lowp=None,
          fault=None):
    """Follow the first ``len(batches)`` steps. Returns the losses, the
    first step's per-leaf gradient norms and the per-leaf norms of the
    parameters' change after the last, by the program's parameter names.
    ``fault="half_batch"`` plants a fault for the control runs: half of
    each batch left out, the mean taken over the rest."""
    with jax.default_matmul_precision("highest"):
        w = upcast(W.make(seed, sizes, dtype))
        m = jax.tree.map(jnp.zeros_like, w)
        v = jax.tree.map(jnp.zeros_like, w)
        losses, grad_norms = [], None
        hp = (hp["learning_rate"], hp["beta1"], hp["beta2"], hp["epsilon"],
              hp["weight_decay"])
        for t, (ids, labels) in enumerate(batches, 1):
            if fault == "half_batch":
                ids, labels = ids[:len(ids) // 2], labels[:len(ids) // 2]
            rows, s = ids.shape
            blk = (rows // block_rows, block_rows, s)
            w, m, v, loss, gn = _train_step(
                w, m, v, jnp.float32(t), jnp.asarray(ids).reshape(blk),
                jnp.asarray(labels).reshape(blk), sizes["n_head"],
                sizes["layer_norm_epsilon"], hp, lowp)
            losses.append(float(loss))
            if t == 1:
                grad_norms = W.per_leaf(gn)
        change = W.per_leaf(_change_norms(
            w, upcast(W.make(seed, sizes, dtype))))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _served_gaps(w, ids, served, valid, n_head, eps, lowp):
    """Per position: how far the served token's logit lies below the best
    one, by the float32 logits; and, for the control, the same gap for the
    token the lower precision puts first."""
    lg = logits_fn(w, ids, n_head, eps)
    best = jnp.max(lg, -1)
    gap = best - jnp.take_along_axis(lg, served[..., None], -1)[..., 0]
    out = [jnp.where(valid, gap, 0.0)]
    if lowp:
        low = jnp.argmax(logits_fn(w, ids, n_head, eps, lowp), -1)
        gap_low = best - jnp.take_along_axis(lg, low[..., None], -1)[..., 0]
        out.append(jnp.where(valid, gap_low, 0.0))
    return out


def served_gaps(seed, sizes, dtype, requests, pad_to, lowp=None):
    """``requests``: ``(prompt, tokens)`` pairs as served (greedy). One
    forward pass over each prompt with its served tokens, padded to
    ``pad_to``; returns per request the widest gap over its served tokens
    (and the control's, if ``lowp``)."""
    with jax.default_matmul_precision("highest"):
        w = upcast(W.make(seed, sizes, dtype))
        rows = []
        for prompt, toks in requests:
            seq = list(prompt) + list(toks)
            n, k = len(prompt), len(toks)
            ids = np.zeros((1, pad_to), np.int32)
            ids[0, :len(seq) - 1] = seq[:-1]
            served = np.zeros((1, pad_to), np.int32)
            valid = np.zeros((1, pad_to), bool)
            served[0, n - 1:n - 1 + k] = toks  # position p predicts p+1
            valid[0, n - 1:n - 1 + k] = True
            out = _served_gaps(w, ids, served, valid, sizes["n_head"],
                               sizes["layer_norm_epsilon"], lowp)
            rows.append([float(jnp.max(o)) for o in out])
    return rows
