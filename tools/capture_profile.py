"""Capture an XPlane device profile of the bench train step and print the
top device ops by self time (parsed from the trace.json.gz the jax profiler
writes). Run: python tools/capture_profile.py
"""
from __future__ import annotations

import glob
import gzip
import json
import tempfile
import time

import numpy as np


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--model", default="gpt", choices=["gpt", "bert", "resnet"])
    a = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    batch, seq = a.batch, a.seq
    paddle.seed(0)
    if a.model == "resnet":
        import paddle_tpu.nn.functional as F
        from paddle_tpu.vision.models import resnet50

        paddle.incubate.autotune.set_config({"layout": {"enable": True}})
        cfg = None
        model = resnet50(num_classes=1000)

        class _M:
            def loss(self, x, y):
                logits = model(x)
                return F.cross_entropy(logits.astype("float32"), y,
                                       reduction="mean")

            to = model.to
            named_sublayers = model.named_sublayers
            parameters = model.parameters

        model_wrap = _M()
    elif a.model == "bert":
        from paddle_tpu.models import BertForPretraining, bert_large

        cfg = bert_large()
        cfg.hidden_dropout = 0.0
        cfg.attention_dropout = 0.0
        model = BertForPretraining(cfg)
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=a.seq,
                        hidden_dropout=0.0, attention_dropout=0.0)
        model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    for name, sub in model.named_sublayers():
        if (type(sub).__name__ == "LayerNorm"
                or type(sub).__name__.startswith("BatchNorm")):
            sub.to(dtype="float32")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)

    loss_model = model_wrap if a.model == "resnet" else model

    def full_step(ids, labels):
        loss = loss_model.loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = CompiledStep(full_step, stateful=[model, opt], donate_state=True)
    rng = np.random.RandomState(0)
    if a.model == "resnet":
        import jax.numpy as jnp

        data = [(Tensor(jnp.asarray(rng.randn(batch, 3, 224, 224)
                                    .astype(np.float32)).astype("bfloat16")),
                 Tensor(rng.randint(0, 1000, (batch, 1)).astype(np.int64)))
                for _ in range(8)]
    else:
        data = [Tensor(rng.randint(0, cfg.vocab_size,
                                   (batch, seq)).astype(np.int64))
                for _ in range(8)]
    def _args(d):
        return d if isinstance(d, tuple) else (d, d)

    for i in range(3):
        np.asarray(step(*_args(data[i]))._value)

    d = tempfile.mkdtemp(prefix="xplane_")
    with jax.profiler.trace(d):
        outs = [step(*_args(data[3 + i])) for i in range(4)]
        np.asarray(outs[-1]._value)

    time.sleep(2)
    files = glob.glob(f"{d}/**/*.trace.json.gz", recursive=True)
    print("trace files:", files)
    if not files:
        return
    with gzip.open(files[0], "rt") as f:
        trace = json.load(f)
    events = [e for e in trace.get("traceEvents", [])
              if e.get("ph") == "X" and e.get("dur")]
    # The trace mixes host python lanes, module-level wrappers, and the
    # flat XLA-op device lane — summing everything double-counts nested
    # parents and mixes host time into the denominator. Aggregate ONLY
    # within the (pid, tid) lane that holds the XLA fusion events; that
    # lane is flat, so totals there are true self times.
    lanes = {}
    for e in events:
        lanes.setdefault((e.get("pid"), e.get("tid")), []).append(e)
    xla_lane = None
    for key, evs in lanes.items():
        if any(e.get("name", "").startswith("fusion") for e in evs):
            if xla_lane is None or (sum(x["dur"] for x in evs)
                                    > sum(x["dur"] for x in lanes[xla_lane])):
                xla_lane = key
    if xla_lane is None:
        print("no XLA op lane found in trace")
        return
    agg = {}
    for e in lanes[xla_lane]:
        name = e.get("name", "")
        agg.setdefault(name, [0, 0.0])
        agg[name][0] += 1
        agg[name][1] += e["dur"]
    total = sum(v[1] for v in agg.values())
    rows = sorted(agg.items(), key=lambda kv: -kv[1][1])[:40]
    print(f"device-op lane {xla_lane}: total {total/1e3:.1f} ms")
    print(f"{'name':<72} {'calls':>6} {'total_us':>12} {'%':>6}")
    for name, (cnt, dur) in rows:
        print(f"{name[:72]:<72} {cnt:>6} {dur:>12.0f} {100 * dur / total:>5.1f}%")


if __name__ == "__main__":
    main()
