"""A/B experiments for the GPT train-step on the real chip: attention kernel
choice, layernorm dtype, and a same-shape pure-GEMM ceiling.

Run:  python tools/perf_experiments.py
"""
from __future__ import annotations

import time

import numpy as np


def timeit_batch(step, batches, k=6):
    outs = [step(*b) for b in batches[:2]]
    np.asarray(outs[-1]._value) if hasattr(outs[-1], "_value") else None
    t0 = time.perf_counter()
    outs = [step(*b) for b in batches[2:2 + k]]
    last = outs[-1]
    np.asarray(last._value if hasattr(last, "_value") else last)
    return (time.perf_counter() - t0) / k


def main():
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    batch, seq = 16, 1024
    tok = batch * seq
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_position_embeddings=1024,
                    hidden_dropout=0.0, attention_dropout=0.0)

    rng = np.random.RandomState(0)
    k = 6
    data = [
        (Tensor(rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)),) * 2
        for _ in range(2 + k)
    ]

    def build(ln_fp32=True):
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        model.to(dtype="bfloat16")
        if ln_fp32:
            for name, sub in model.named_sublayers():
                if type(sub).__name__ == "LayerNorm":
                    sub.to(dtype="float32")
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters(),
                                     multi_precision=True)

        def full_step(ids, labels):
            loss = model.loss(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return CompiledStep(full_step, stateful=[model, opt],
                            donate_state=True)

    # 1) baseline
    t = timeit_batch(build(), data, k)
    print(f"baseline (flash, ln fp32)      {t*1e3:8.2f} ms  {tok/t:9.0f} tok/s", flush=True)

    # 2) XLA attention instead of Pallas flash
    paddle.set_flags({"disable_flash_attention": True})
    try:
        t = timeit_batch(build(), data, k)
        print(f"xla attention (no flash)       {t*1e3:8.2f} ms  {tok/t:9.0f} tok/s", flush=True)
    finally:
        paddle.set_flags({"disable_flash_attention": False})

    # 3) all-bf16 layernorm
    t = timeit_batch(build(ln_fp32=False), data, k)
    print(f"flash, ln bf16                 {t*1e3:8.2f} ms  {tok/t:9.0f} tok/s", flush=True)

    # 4) pure-GEMM ceiling with the step's dominant shapes (fwd+bwd pattern:
    # each fwd matmul has two bwd partners of the same flop count)
    h = cfg.hidden_size
    x = jnp.asarray(rng.randn(tok, h), jnp.bfloat16)
    ws = {
        "qkv": jnp.asarray(rng.randn(h, 3 * h), jnp.bfloat16),
        "proj": jnp.asarray(rng.randn(h, h), jnp.bfloat16),
        "up": jnp.asarray(rng.randn(h, 4 * h), jnp.bfloat16),
        "down": jnp.asarray(rng.randn(4 * h, h), jnp.bfloat16),
        "head": jnp.asarray(rng.randn(h, cfg.vocab_size), jnp.bfloat16),
    }

    x4 = jnp.asarray(rng.randn(tok, 4 * h), jnp.bfloat16)

    @jax.jit
    def gemm_chain(x, x4):
        acc = jnp.zeros((), jnp.float32)
        for _ in range(cfg.num_layers):
            for wname in ("qkv", "proj", "up", "down"):
                w = ws[wname]
                inp = x if w.shape[0] == h else x4
                for _rep in range(3):  # fwd + 2 bwd-equivalent flops
                    z = jnp.dot(inp, w)
                    acc = acc + z.astype(jnp.float32).sum() * 1e-9
        for _rep in range(3):
            z = jnp.dot(x, ws["head"])
            acc = acc + z.astype(jnp.float32).sum() * 1e-9
        return acc

    outs = [gemm_chain(x + i, x4 + i) for i in range(2)]
    np.asarray(outs[-1])
    t0 = time.perf_counter()
    outs = [gemm_chain(x + 2 + i, x4 + 2 + i) for i in range(k)]
    np.asarray(outs[-1])
    t = (time.perf_counter() - t0) / k
    flops = 3 * (cfg.num_layers * (2 * tok * h * 3 * h + 2 * tok * h * h
                                   + 2 * tok * h * 4 * h + 2 * tok * 4 * h * h)
                 + 2 * tok * h * cfg.vocab_size)
    print(f"pure GEMM chain (same shapes)  {t*1e3:8.2f} ms  "
          f"{flops/t/1e12:6.1f} TF/s achieved", flush=True)


if __name__ == "__main__":
    main()
