"""Fused ops for the memory-bound tails of transformer training.

Reference analogues: ``paddle/fluid/operators/fused/fused_softmax_mask.cu.h``
and ``paddle/phi/kernels/gpu/cross_entropy_kernel.cu`` (their answer to the
softmax/CE bandwidth problem). TPU-native redesign: the LM head matmul and the
softmax cross-entropy are fused into ONE chunked op with a custom VJP, so the
full ``[tokens, vocab]`` logits tensor is never materialized in HBM — neither
in forward nor in backward. Each chunk's logits live only as a fused-scan
temporary; the MXU does the matmuls, fp32 statistics ride in registers.

For GPT-2 124M at b16xs1024 the un-fused path writes+reads a 3.3 GB fp32
logits tensor twice per step; this op removes all of that traffic.

Measured on v5e (GPT-2 124M, b16 s1024, V=50304): the op is VPU-EXP-BOUND:
~824M f32 exps/step set a ~8-9 ms floor that no implementation can dodge. A
Pallas version edged this scan forward (14.5 vs 15.7 ms, blocks 1024x1024) and
lost forward + backward (41 vs 37 ms): its backward recomputed the logits in
BOTH the dx and the dW kernel. It is gone; a better one needs a new backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from .dispatch import op

__all__ = ["fused_linear_cross_entropy"]


def _pick_chunk(tokens: int) -> int:
    # largest power-of-two chunk <= 2048 dividing the padded token count.
    # Swept on v5e (GPT-2 124M, V=50304, 16k tokens): ISOLATED fwd+bwd
    # prefers 4096/8192 (35.7/35.4 ms vs 39.2 at 2048 — fewer dW-carry
    # trips), but END-TO-END the larger transient logits block loses
    # ~4.5k tok/s to HBM pressure against the resident model state —
    # 2048 (~400 MB transient) is the full-step optimum.
    for c in (2048, 1024, 512, 256, 128):
        if tokens >= c:
            return c
    return tokens


def _chunked(x, chunk):
    n = x.shape[0]
    pad = (-n) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)], 0)
    return x.reshape((x.shape[0] // chunk, chunk) + x.shape[1:])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _flce(h, w, b, labels, ignore_index, chunk):
    losses, _ = _flce_fwd(h, w, b, labels, ignore_index, chunk)
    return losses


def _flce_fwd(h, w, b, labels, ignore_index, chunk):
    tokens = h.shape[0]
    chunk = chunk or _pick_chunk(tokens)
    y = labels.astype(jnp.int32)
    safe = jnp.where(y == ignore_index, 0, y)
    h_b = _chunked(h, chunk)

    def body(_, h_c):
        logits = jnp.dot(h_c, w.T, preferred_element_type=jnp.float32) + b  # [C,V]
        m = jnp.max(logits, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[:, None]), axis=-1))
        return None, lse

    _, lse_b = lax.scan(body, None, h_b)
    # the label logit never needs the [C, V] block: it is a row gather of W
    # plus a row-dot — h_i . W[y_i] + b[y_i]. Computing it in the scan as a
    # one-hot select+reduce re-read the full f32 logits chunk (~400 MB x
    # nchunks of pure HBM traffic, profiled at ~4.4 ms/step on v5e).
    picked = jnp.sum(
        h.astype(jnp.float32) * jnp.take(w, safe, axis=0).astype(jnp.float32),
        axis=-1,
    )
    if b.ndim != 0:
        picked = picked + jnp.take(b, safe).astype(jnp.float32)
    losses = lse_b.reshape(-1)[:tokens] - picked
    losses = jnp.where(y == ignore_index, 0.0, losses)
    return losses, (h, w, b, safe, y == ignore_index, lse_b)


def _flce_bwd(ignore_index, chunk, res, g):
    h, w, b, safe, ignored, lse_b = res
    tokens = h.shape[0]
    chunk = chunk or _pick_chunk(tokens)
    g = jnp.where(ignored, 0.0, g.astype(jnp.float32))

    h_b = _chunked(h, chunk)
    y_b = _chunked(safe, chunk)
    g_b = _chunked(g, chunk)

    def body(acc, inp):
        dw_acc, db_acc = acc
        h_c, y_c, g_c, lse_c = inp
        logits = jnp.dot(h_c, w.T, preferred_element_type=jnp.float32) + b
        # softmax from the saved forward lse: single fused pass, no max/sum
        # re-reduction; one-hot via iota compare keeps this scatter-free
        eq = (lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == y_c[:, None]).astype(jnp.float32)
        dl = ((jnp.exp(logits - lse_c[:, None]) - eq)
              * g_c[:, None]).astype(w.dtype)              # [C, V] bf16
        dh_c = jnp.dot(dl, w)                              # [C, H]
        dw_acc = dw_acc + jnp.dot(dl.T, h_c, preferred_element_type=jnp.float32)
        if b.ndim == 0:
            # bias-free path: the placeholder's cotangent is never consumed —
            # skip the O(chunk*vocab) reduction entirely
            pass
        else:
            db_acc = db_acc + jnp.sum(dl.astype(jnp.float32), axis=0)
        return (dw_acc, db_acc), dh_c

    dw0 = jnp.zeros(w.shape, jnp.float32)
    db0 = jnp.zeros(b.shape, jnp.float32)
    (dw, db), dh_b = lax.scan(body, (dw0, db0), (h_b, y_b, g_b, lse_b))
    dh = dh_b.reshape(-1, h.shape[-1])[:tokens].astype(h.dtype)
    return dh, dw.astype(w.dtype), db.astype(b.dtype), None


_flce.defvjp(_flce_fwd, _flce_bwd)


@op("fused_linear_cross_entropy")
def _flce_op(hidden, weight, labels, bias=None, ignore_index=-100,
             reduction="mean", chunk=0):
    tokens = 1
    for d in hidden.shape[:-1]:
        tokens *= d
    h2 = hidden.reshape(tokens, hidden.shape[-1])
    y = labels.reshape(tokens)
    # bias-free callers pay nothing: a scalar 0 broadcasts into the chunk
    # logits and its (discarded) gradient is one extra scalar reduction
    b = jnp.zeros((), jnp.float32) if bias is None else bias.astype(jnp.float32)
    losses = _flce(h2, weight, b, y, ignore_index, chunk)
    if reduction == "none":
        return losses.reshape(labels.shape)
    valid = jnp.sum((y != ignore_index).astype(jnp.float32))
    total = jnp.sum(losses)
    if reduction == "sum":
        return total
    return total / jnp.maximum(valid, 1.0)


def fused_linear_cross_entropy(hidden, weight, labels, bias=None,
                               ignore_index=-100, reduction="mean", chunk=0,
                               name=None):
    """``cross_entropy(hidden @ weight.T + bias, labels)`` without
    materializing logits.

    Args:
        hidden: ``[..., hidden_size]`` activations (bf16/f32).
        weight: ``[vocab, hidden_size]`` LM head / tied embedding weight.
        labels: integer class ids, shape ``hidden.shape[:-1]``.
        bias: optional ``[vocab]`` LM-head bias (ERNIE/BERT-style heads).
        ignore_index: label value excluded from the loss and the mean.
        reduction: ``"mean" | "sum" | "none"``.
        chunk: token-chunk size (0 = auto).
    """
    # one scope for the head matmul and the loss: a profile's op metadata
    # groups them as the step's "lm_head_loss" part
    with jax.named_scope("lm_head_loss"):
        if bias is None:
            return _flce_op(hidden, weight, labels,
                            ignore_index=ignore_index,
                            reduction=reduction, chunk=int(chunk))
        return _flce_op(hidden, weight, labels, bias,
                        ignore_index=ignore_index,
                        reduction=reduction, chunk=int(chunk))
