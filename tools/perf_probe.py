"""Decompose the GPT train-step time on the real chip.

Every probe measures k calls issued back-to-back on the donated-state
chain with one fetch at the end, so the host readback is paid once, not k
times.

Run:  python tools/perf_probe.py
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def timeit_batch(step, batches, k=6):
    """Issue k calls back-to-back; fence via the LAST output only (the
    donated state chain means it already depends on every prior step)."""
    outs = [step(*b) for b in batches[:2]]          # warmup/compile
    np.asarray(outs[-1]._value)
    t0 = time.perf_counter()
    outs = [step(*b) for b in batches[2:2 + k]]
    np.asarray(outs[-1]._value)
    dt = (time.perf_counter() - t0) / k
    assert all(np.isfinite(np.asarray(o._value)).all() for o in outs)
    return dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=1024)
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    on_tpu = jax.default_backend() != "cpu"
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=args.layers,
                    num_heads=12, max_position_embeddings=1024,
                    hidden_dropout=0.0, attention_dropout=0.0)
    batch, seq = args.batch, args.seq
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
        for name, sub in model.named_sublayers():
            if type(sub).__name__ == "LayerNorm":
                sub.to(dtype="float32")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=on_tpu)
    rng = np.random.RandomState(time.time_ns() % (2**31))
    tok = batch * seq
    k = 6
    data = [Tensor(rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
            for _ in range(2 + k)]

    def fwd_only(ids):
        return model.gpt(ids).astype("float32").sum()

    def fwd_loss_fused(ids, labels):
        return model.loss(ids, labels)

    def fwd_loss_unfused(ids, labels):
        logits = model(ids)
        return F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]).astype("float32"),
            labels.reshape([-1, 1])).mean()

    def grad_fused(ids, labels):
        # return a grad-dependent scalar so XLA cannot DCE the backward
        loss = model.loss(ids, labels)
        loss.backward()
        gsum = None
        for p in model.parameters():
            if p.grad is not None:
                s = p.grad.astype("float32").sum()
                gsum = s if gsum is None else gsum + s
        opt.clear_grad()
        return loss + gsum

    def opt_only(ids, labels):
        # grads of a cheap surrogate so step() cost dominates
        loss = (model.gpt.embeddings.word_embeddings.weight.astype("float32").sum())
        for p in model.parameters():
            p._grad = Tensor(p._value * 0 + 1e-6)
        opt.step()
        opt.clear_grad()
        return loss

    def full_step(ids, labels):
        loss = model.loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    probes = [
        ("fwd body only (no head)", CompiledStep(fwd_only, stateful=[model]),
         [(d,) for d in data]),
        ("fwd + fused head+CE", CompiledStep(fwd_loss_fused, stateful=[model]),
         [(d, d) for d in data]),
        ("fwd + unfused head+CE", CompiledStep(fwd_loss_unfused, stateful=[model]),
         [(d, d) for d in data]),
        ("fwd+bwd fused", CompiledStep(grad_fused, stateful=[model, opt]),
         [(d, d) for d in data]),
        ("optimizer only", CompiledStep(opt_only, stateful=[model, opt]),
         [(d, d) for d in data]),
        ("full step (fused)", CompiledStep(full_step, stateful=[model, opt]),
         [(d, d) for d in data]),
    ]
    for name, step, b in probes:
        t = timeit_batch(step, b, k=k)
        print(f"{name:28s} {t * 1e3:8.2f} ms   {tok / t:10.0f} tok/s", flush=True)


if __name__ == "__main__":
    main()
