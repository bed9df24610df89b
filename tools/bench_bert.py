"""BERT-large MLM train-step throughput on one TPU chip (BASELINE.md
config 3).

Prints ONE JSON line per sequence length and (on TPU) writes
``BERT_r05.json`` at the repo root with both entries.

Recipe: BERT-large (340M, 24L/1024H/16 heads), bf16 compute with fp32
layernorms and fp32 master weights, dense bidirectional attention through
the packed seq-major flash kernel (no padding mask — throughput regime),
MLM loss via the fused linear+cross-entropy head (the [tokens, vocab]
logits never materialize). Reference capability: the fleet BERT configs
(``reference/python/paddle/fluid/tests/unittests/test_bert*``) and the
BERT-large tokens/sec/chip metric demanded by BASELINE.md.

Usage: python tools/bench_bert.py
       [--seq 128 512] [--batch N] [--iters N] [--no-artifact]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from bench_common import (  # noqa: E402
    compiled_flops,
    device_peak,
    measure_steps,
    telemetry_block,
)

# measured per-chip optima on v5e (b256@s128 and b64@s512 OOM against the
# AdamW fp32-master/moment state of the 340M model; s512: b48 42.4k > b32
# 40.6k tok/s)
DEFAULT_BATCH = {128: 128, 512: 48}


def _run_one(seq, batch=None, iters=None):
    import jax

    backend = jax.default_backend()
    on_tpu = backend not in ("cpu",)

    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import BertConfig, BertForPretraining, bert_large

    if on_tpu:
        cfg = bert_large()
        cfg.hidden_dropout = 0.0
        cfg.attention_dropout = 0.0
        batch = batch or DEFAULT_BATCH.get(seq, max(1, 32768 // seq))
        iters = iters or 10
    else:  # smoke-scale for CPU verification runs
        cfg = BertConfig(vocab_size=512, hidden_size=128, num_layers=2,
                         num_heads=2, intermediate_size=256,
                         max_position_embeddings=max(seq, 64),
                         hidden_dropout=0.0, attention_dropout=0.0)
        batch = batch or 4
        iters = iters or 3

    paddle.seed(0)
    model = BertForPretraining(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
        for _, sub in model.named_sublayers():
            if type(sub).__name__ == "LayerNorm":
                sub.to(dtype="float32")
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        multi_precision=on_tpu,
    )

    def train_step(ids, labels):
        loss = model.loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = CompiledStep(train_step, stateful=[model, opt], donate_state=True)

    rng = np.random.RandomState(0)
    batches = []
    for _ in range(3 + iters):
        ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        # MLM regime: loss on ~15% of positions, rest ignore_index
        labels = np.where(rng.rand(batch, seq) < 0.15,
                          rng.randint(0, cfg.vocab_size, (batch, seq)),
                          -100).astype(np.int64)
        batches.append((Tensor(ids), Tensor(labels)))

    total, _ = measure_steps(step, batches, iters)
    tokens_per_sec = batch * seq * iters / total
    telemetry = telemetry_block(total, iters)

    kind, peak = device_peak()
    flops = compiled_flops(step, batches)
    hfu = (flops * tokens_per_sec / (batch * seq) / peak) \
        if (flops and peak) else None
    # analytic: 6*N_matmul + 12*L*H*s flops/token (encoder blocks + tied MLM
    # head + transform), same convention as bench.py
    h_, l_, v_, i_ = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                      cfg.intermediate_size)
    n_matmul = l_ * (4 * h_ * h_ + 2 * h_ * i_) + h_ * h_ + v_ * h_
    flops_per_token = 6 * n_matmul + 12 * l_ * h_ * seq
    mfu = tokens_per_sec * flops_per_token / peak if peak else None

    return {
        "metric": f"bert-large MLM train throughput ({backend})" if on_tpu
                  else f"bert-smoke MLM train throughput ({backend})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "seq": seq,
        "batch": batch,
        "device_kind": kind,
        "step_flops": flops,
        "hw_flops_util": round(hfu, 4) if hfu else None,
        "mfu": round(mfu, 4) if mfu else None,
        "telemetry": telemetry,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, nargs="+", default=[128, 512])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--no-artifact", action="store_true")
    a = ap.parse_args()

    import jax

    from paddle_tpu.framework.compile_cache import enable_compile_cache

    enable_compile_cache()
    results = []
    for seq in a.seq:
        results.append(_run_one(seq, a.batch, a.iters))
        print(json.dumps(results[-1]))
        jax.clear_caches()
    on_tpu = jax.default_backend() not in ("cpu",)
    if on_tpu and not a.no_artifact:
        with open("BERT_r05.json", "w") as f:
            json.dump({"results": results}, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
