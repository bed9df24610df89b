"""Sweep the fused linear+CE chunk size on the real chip (the two lax.scan
loops were 21% of device step time in the profile — bigger chunks mean
fewer scan trips and bigger MXU matmuls, at the cost of a larger transient
logits block). Run: python tools/chunk_sweep.py
"""
from __future__ import annotations

import time

import numpy as np


def main():
    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.ops import fused

    batch, seq = 16, 1024
    tok = batch * seq
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_position_embeddings=1024,
                    hidden_dropout=0.0, attention_dropout=0.0)
    rng = np.random.RandomState(0)
    k = 6
    data = [Tensor(rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64))
            for _ in range(2 + k)]

    for chunk in (1024, 2048, 4096, 8192, 16384):
        fused._FORCE_CHUNK = chunk
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
        model.to(dtype="bfloat16")
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

        step = CompiledStep(full_step, stateful=[model, opt], donate_state=True)
        outs = [step(d, d) for d in data[:2]]
        np.asarray(outs[-1]._value)
        t0 = time.perf_counter()
        outs = [step(d, d) for d in data[2:]]
        np.asarray(outs[-1]._value)
        t = (time.perf_counter() - t0) / k
        print(f"chunk={chunk:<6} {t*1e3:8.2f} ms  {tok/t:9.0f} tok/s", flush=True)
    fused._FORCE_CHUNK = None


if __name__ == "__main__":
    main()
