"""ResNet-50 train-step throughput on one TPU chip (BASELINE.md configs 2/4).

Prints ONE JSON line {"metric", "value", "unit", ...} and (on TPU) writes
``RESNET_r05.json`` at the repo root.

Recipe: ImageNet-shape synthetic data (224x224), bf16 compute with fp32
batch-norm statistics, NHWC convolutions via layout autotune (the TPU conv
units natively consume channels-last; XLA folds the interior transposes of
back-to-back convs), SGD+momentum. Reference capability: the fleet ResNet
configs under ``reference/python/paddle/fluid/tests/unittests/collective/``
and the op-perf gate in ``tools/ci_op_benchmark.sh``.

Usage: python tools/bench_resnet.py
       [--batch N] [--iters N] [--no-artifact]
"""
from __future__ import annotations

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from bench_common import (  # noqa: E402
    compiled_flops,
    device_peak,
    emit,
    measure_steps,
    telemetry_block,
)


def _run(batch=None, iters=None, artifact=True):
    import jax

    backend = jax.default_backend()
    on_tpu = backend not in ("cpu",)

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.vision.models import resnet50

    if on_tpu:
        batch = batch or 128
        size, classes = 224, 1000
        iters = iters or 10
    else:  # smoke-scale for CPU verification runs
        batch = batch or 4
        size, classes = 32, 10
        iters = iters or 3

    paddle.seed(0)
    paddle.incubate.autotune.set_config({"layout": {"enable": True}})
    model = resnet50(num_classes=classes)
    if on_tpu:
        model.to(dtype="bfloat16")
        # batch-norm statistics/affine stay fp32 for numerical stability
        # (same policy as the GPT bench's fp32 layernorms)
        for _, sub in model.named_sublayers():
            if type(sub).__name__.startswith("BatchNorm"):
                sub.to(dtype="float32")
    opt = paddle.optimizer.Momentum(
        learning_rate=0.1, momentum=0.9, parameters=model.parameters(),
        weight_decay=1e-4, use_nesterov=False,
        multi_precision=on_tpu,
    )

    def train_step(images, labels):
        logits = model(images)
        loss = F.cross_entropy(logits.astype("float32"),
                               labels, reduction="mean")
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = CompiledStep(train_step, stateful=[model, opt], donate_state=True)

    rng = np.random.RandomState(0)
    dtype = np.float32
    batches = []
    for _ in range(3 + iters):
        img = rng.randn(batch, 3, size, size).astype(dtype)
        lab = rng.randint(0, classes, (batch, 1)).astype(np.int64)
        batches.append((Tensor(jax.numpy.asarray(img).astype(
            "bfloat16" if on_tpu else "float32")), Tensor(lab)))

    total, _ = measure_steps(step, batches, iters)
    images_per_sec = batch * iters / total
    telemetry = telemetry_block(total, iters)

    kind, peak = device_peak()
    flops = compiled_flops(step, batches)
    hfu = (flops * images_per_sec / batch / peak) if (flops and peak) else None
    # analytic model FLOPs: ResNet-50 fwd = 4.09 GMACs @224^2 (8.18 GFLOPs in
    # mul+add counting); train step ~= 3x fwd
    mfu_analytic = (3 * 2 * 4.089e9 * images_per_sec / peak) if peak else None

    emit({
        "metric": f"resnet50 train throughput ({backend})",
        "value": round(images_per_sec, 1),
        "unit": "images/sec/chip",
        "batch": batch,
        "image_size": size,
        "device_kind": kind,
        "step_flops": flops,
        "hw_flops_util": round(hfu, 4) if hfu else None,
        "mfu_analytic": round(mfu_analytic, 4) if mfu_analytic else None,
        "telemetry": telemetry,
    }, artifact="RESNET_r05.json" if (on_tpu and artifact) else None)
    return images_per_sec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--no-artifact", action="store_true")
    a = ap.parse_args()
    from paddle_tpu.framework.compile_cache import enable_compile_cache

    enable_compile_cache()
    _run(a.batch, a.iters, artifact=not a.no_artifact)


if __name__ == "__main__":
    main()
