"""Hardware-gated throughput regression tests for the three benchmark
models (BASELINE.md configs; VERDICT round-5 item 1).

Run: PADDLE_TPU_HW_TESTS=1 python -m pytest \
       tests/test_model_benchmarks_tpu.py -q

Thresholds sit ~12% under the committed round-5 artifacts (RESNET_r05.json,
BERT_r05.json, LONGCTX_r05.json) to absorb run-to-run noise
while still catching real regressions (the reference gates op perf the
same relative way — tools/ci_op_benchmark.sh)."""
import os
import sys

import pytest

pytestmark = pytest.mark.skipif(
    not os.environ.get("PADDLE_TPU_HW_TESTS"),
    reason="hardware benchmark tests need PADDLE_TPU_HW_TESTS=1 + a TPU")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


@pytest.fixture(autouse=True)
def _require_tpu():
    import jax

    if jax.default_backend() == "cpu":
        pytest.skip("no TPU backend")
    # conftest pins matmul precision to HIGHEST for CPU finite-difference
    # parity; on TPU that forces multi-pass fp32-emulated matmuls (and
    # Mosaic rejects the pass-split dots inside the pallas kernels) —
    # throughput must be measured at the hardware's native bf16 precision,
    # exactly like the standalone bench tools
    prev = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "default")
    yield
    jax.config.update("jax_default_matmul_precision",
                      prev if prev is not None else "highest")
    jax.clear_caches()


def test_resnet50_throughput_floor():
    from bench_resnet import _run

    # ResNet steps are short (~53 ms): the final fence needs >=12 steps to
    # amortize below the floor's noise margin (4 iters measured 20% low on
    # a healthy chip)
    ips = _run(batch=128, iters=12, artifact=False)
    assert ips >= 1900, f"ResNet-50 {ips:.0f} img/s below floor (r05: 2166)"


def test_bert_large_seq128_throughput_floor():
    from bench_bert import _run_one

    res = _run_one(128, iters=4)
    tps = res["value"]
    assert tps >= 49000, f"BERT-large {tps:.0f} tok/s below floor (r05: 55993)"


def test_gpt_long_context_throughput_floor():
    """s=8192 flagship long-context: guards the flash-attention long-seq
    path (block routing + multi-tile online softmax)."""
    import time

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    batch, seq = 4, 8192
    cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_position_embeddings=seq,
                    hidden_dropout=0.0, attention_dropout=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    for _, sub in model.named_sublayers():
        if type(sub).__name__ == "LayerNorm":
            sub.to(dtype="float32")
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters(),
                                 multi_precision=True)

    def train_step(ids, labels):
        loss = model.loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = CompiledStep(train_step, stateful=[model, opt], donate_state=True)
    rng = np.random.RandomState(0)
    data = [Tensor(rng.randint(0, cfg.vocab_size, (batch, seq))
                   .astype(np.int64)) for _ in range(6)]
    for i in range(2):
        np.asarray(step(data[i], data[i])._value)
    t0 = time.perf_counter()
    outs = [step(b, b) for b in data[2:]]
    np.asarray(outs[-1]._value)
    toks = batch * seq * 4 / (time.perf_counter() - t0)
    assert toks >= 53000, f"GPT s=8192 {toks:.0f} tok/s below floor (r05: ~60k)"
