"""Driver benchmark: flagship GPT train-step throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
The reference publishes no in-tree numbers (BASELINE.md) — vs_baseline
compares against the previous round's BENCH_r*.json when present, else 1.0.

Measurement protocol (warmup/donated-state chain/fence-on-last-loss) and
the chip-peak table live in tools/bench_common.py, shared with the
ResNet-50 and BERT-large benchmarks. Batches are HOST numpy arrays staged
through io.DeviceLoader (double-buffered async host→device prefetch) and
the step donates its input buffers (CompiledStep donate_inputs=True) — the
measured number includes the production input pipeline, with transfer
overlapped and batch HBM recycled into the step's temporaries.

``--dp N --zero`` switches to the comm-optimized data-parallel benchmark
(distributed/sharding/zero.py): the smoke GPT under a pure-dp mesh with
the ZeRO sharded weight update, reporting tokens/sec, comm_fraction,
per-replica optimizer-state bytes vs the replicated-Adam baseline, and
(with ``--parity``) the loss-parity check the CI gate asserts — exact for
ZeRO alone, rtol-gated for ``--int8`` (quantized param all-gather with
error feedback). On hosts without ``N`` devices the dp mesh is virtualized
over XLA:CPU (``xla_force_host_platform_device_count``).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "tools"))
from bench_common import (  # noqa: E402
    device_peak,
    measure_steps,
    telemetry_block,
)

#: int8 + error feedback loss-parity gate (max relative deviation from the
#: replicated-Adam curve over the smoke run)
INT8_PARITY_RTOL = 2e-2

#: fp32 ZeRO is exact in math (sharding constraints move data, never
#: values) and typically bitwise — but XLA:CPU's thread-pool reduction
#: scheduling can reorder an all-reduce between compiles, wiggling the
#: last ulp. Gate at last-ulp scale; the emitted doc still records the
#: per-run ``bitwise`` flag.
FP32_PARITY_RTOL = 1e-5


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dp", type=int, default=None,
                    help="data-parallel ways; enables the multichip bench")
    ap.add_argument("--zero", action="store_true",
                    help="ZeRO sharded weight update over the dp axis")
    ap.add_argument("--int8", action="store_true",
                    help="int8 + error-feedback param all-gather")
    ap.add_argument("--parity", action="store_true",
                    help="assert loss parity vs the replicated-Adam "
                         "baseline (bitwise for fp32 ZeRO, rtol for int8)")
    ap.add_argument("--artifact", default=None,
                    help="also write the result JSON to this path")
    args = ap.parse_args(argv)
    # the dp mesh needs the devices BEFORE jax is imported (below)
    if args.dp is not None and os.environ.get("PADDLE_TPU_HW_TESTS") != "1":
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={args.dp}")
    from paddle_tpu.framework.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.dp is None:
        _run()
    else:
        _run_zero(args)


def _run():
    import jax

    backend = jax.default_backend()
    on_tpu = backend not in ("cpu",)

    import paddle_tpu as paddle
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    # GPT-2 small (124M); bf16 compute + fp32 master weights on TPU.
    # batch 24 is the measured per-chip MFU optimum on v5e (b16: 119.0k,
    # b24: 120.1k, b32: 110.3k tok/s — bigger batches start losing to HBM
    # pressure against the fused-CE transient)
    if on_tpu:
        cfg = GPTConfig(
            vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
            max_position_embeddings=1024, hidden_dropout=0.0, attention_dropout=0.0,
        )
        batch, seq = 24, 1024
    else:  # smoke-scale for CPU runs
        cfg = GPTConfig(
            vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
            max_position_embeddings=128, hidden_dropout=0.0, attention_dropout=0.0,
        )
        batch, seq = 4, 64

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
        # keep layernorms fp32 for stability
        for name, sub in model.named_sublayers():
            if type(sub).__name__ == "LayerNorm":
                sub.to(dtype="float32")
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(), multi_precision=on_tpu
    )

    def train_step(ids, labels):
        # fused LM-head matmul + softmax-CE: the [b*s, vocab] logits tensor
        # never materializes in HBM (ops/fused.py)
        loss = model.loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # donate_inputs: every batch below is a single-use staged array, so its
    # HBM is recycled into the step's temporaries (attacks the "b32 loses
    # to HBM pressure" ceiling at larger batch sizes)
    step = CompiledStep(train_step, stateful=[model, opt], donate_state=True,
                        donate_inputs=True)

    iters = 10 if on_tpu else 5
    rng = np.random.RandomState(0)
    batches = []
    for _ in range(3 + iters):
        # host numpy, staged by measure_steps' DeviceLoader; labels are a
        # separate buffer (ids are donated — no aliased donation)
        a = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        batches.append((a, a.copy()))

    total, _ = measure_steps(step, batches, iters)
    tokens_per_sec = batch * seq * iters / total
    # phase attribution for the perf trajectory: steps/s, data-wait
    # fraction, compile/recompile counts, DeviceLoader prefetch stats
    telemetry = telemetry_block(total, iters)

    # Achieved MFU against the chip's bf16 peak by device_kind. Preferred
    # FLOP count: XLA's own cost analysis of the compiled step (harvested
    # by profiler.devprof at first compile — includes remat recompute, the
    # honest hardware-utilization number). Fallback: the standard
    # 6*N_matmul + 12*L*H*s flops/token convention (fwd+bwd; matmul params
    # = decoder blocks + tied head, embedding lookups excluded).
    from paddle_tpu.profiler import devprof

    kind, peak = device_peak()
    rep = devprof.get_report("train_step") or devprof.last_report()
    mfu = mfu_source = None
    # mfu only when the chip's bf16 peak is known — never a guessed peak
    if peak:
        if rep is not None and rep.flops:
            mfu = (rep.flops * iters / total) / peak
            mfu_source = "xla_cost_analysis"
        else:
            h_, l_, v_, s_ = (cfg.hidden_size, cfg.num_layers,
                              cfg.vocab_size, seq)
            n_matmul = l_ * 12 * h_ * h_ + v_ * h_
            flops_per_token = 6 * n_matmul + 12 * l_ * h_ * s_
            mfu = tokens_per_sec * flops_per_token / peak
            mfu_source = "analytic"

    prev = 0.0
    for f in sorted(glob.glob("BENCH_r*.json")):
        try:
            d = json.load(open(f))
            # the driver wraps our line under "parsed" in BENCH_r*.json
            if isinstance(d.get("parsed"), dict):
                d = d["parsed"]
            if d.get("unit") == "tokens/sec/chip":
                prev = float(d.get("value", 0.0))
        except Exception:
            pass
    vs = tokens_per_sec / prev if prev > 0 else 1.0

    print(json.dumps({
        "metric": f"gpt2-124M train throughput ({backend})" if on_tpu
                  else f"gpt-smoke train throughput ({backend})",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(vs, 3),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_source": mfu_source,
        "device_kind": kind,
        "telemetry": telemetry,
    }))


def _acc_bytes(opt):
    """Per-replica optimizer-state bytes: local shard sizes when sharded."""
    total = 0
    for store in opt._accumulators.values():
        for v in store.values():
            if hasattr(v, "sharding") and hasattr(v.sharding, "shard_shape"):
                shape = v.sharding.shard_shape(v.shape)
            else:
                shape = v.shape
            total += int(np.prod(shape)) * v.dtype.itemsize
    return total


def _run_zero(args):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    backend = jax.default_backend()
    if jax.device_count() < args.dp:
        raise SystemExit(f"--dp {args.dp} needs {args.dp} devices; "
                         f"found {jax.device_count()} ({backend})")

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.sharding import ShardedOptimizer
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.utils import unique_name

    cfg = GPTConfig(
        vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
        max_position_embeddings=128, hidden_dropout=0.0,
        attention_dropout=0.0,
    )
    batch, seq, iters, k_parity = 4 * args.dp, 64, 5, 5
    mesh = build_mesh({"dp": args.dp})
    quantize = "int8" if args.int8 else None

    def build(zero):
        with unique_name.guard():
            paddle.seed(0)
            model = GPTForCausalLM(cfg)
        rep = NamedSharding(mesh, P())
        for p in model.parameters():
            p._value = jax.device_put(p._value, rep)
        opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                     parameters=model.parameters())
        stepper = (ShardedOptimizer(opt, axis="dp", mesh=mesh,
                                    quantize=quantize) if zero else opt)

        def train_step(ids, labels):
            loss = model.loss(ids, labels)
            loss.backward()
            stepper.step()
            stepper.clear_grad()
            return loss

        train_step.__name__ = ("zero_train_step" if zero
                               else "dp_train_step")
        # stateful threads the INNER optimizer: the wrapper holds no
        # arrays of its own (ef residuals live in the inner accumulators)
        step = CompiledStep(train_step, stateful=[model, opt],
                            donate_state=True)
        return step, opt

    def batches_for(rng, n):
        sh = NamedSharding(mesh, P("dp", None))
        out = []
        for _ in range(n):
            a = rng.randint(0, cfg.vocab_size, (batch, seq))
            ids = jax.device_put(np.asarray(a, np.int32), sh)
            out.append((Tensor(ids), Tensor(ids.copy())))
        return out

    # SHARED between the baseline and ZeRO runs — parity needs identical
    # data streams
    data_seed = 0

    # -- replicated-Adam baseline (parity reference + comm/state baseline)
    base_step, base_opt = build(zero=False)
    base_parity = [float(np.asarray(base_step(*b)._value))
                   for b in batches_for(np.random.RandomState(data_seed),
                                        k_parity)]
    sample = batches_for(np.random.RandomState(data_seed + 2), 1)[0]
    # the step compiled during the parity loop (telemetry off) — harvest
    # the device ground truth explicitly so telemetry_block's comm stats
    # (comm_fraction, comm.bytes.dp) have a report to fall back on
    base_step.device_report(*sample)
    base_total, _ = measure_steps(
        base_step, batches_for(np.random.RandomState(data_seed + 1),
                               3 + iters), iters, prefetch=0)
    base_tok = batch * seq * iters / base_total
    base_telemetry = telemetry_block(base_total, iters)
    base_state = _acc_bytes(base_opt)

    # -- ZeRO run
    zero_step, zero_opt = build(zero=True)
    zero_parity = [float(np.asarray(zero_step(*b)._value))
                   for b in batches_for(np.random.RandomState(data_seed),
                                        k_parity)]
    zero_step.device_report(*sample)
    zero_total, _ = measure_steps(
        zero_step, batches_for(np.random.RandomState(data_seed + 1),
                               3 + iters), iters, prefetch=0)
    zero_tok = batch * seq * iters / zero_total
    zero_telemetry = telemetry_block(zero_total, iters)
    zero_state = _acc_bytes(zero_opt)

    max_abs = max(abs(a - b) for a, b in zip(base_parity, zero_parity))
    max_rel = max(abs(a - b) / max(abs(a), 1e-12)
                  for a, b in zip(base_parity, zero_parity))
    bitwise = base_parity == zero_parity
    parity = {
        "steps": k_parity,
        "bitwise": bitwise,
        "max_abs": max_abs,
        "max_rel": max_rel,
        "gate": (f"rtol<{FP32_PARITY_RTOL}" if quantize is None
                 else f"rtol<{INT8_PARITY_RTOL}"),
    }
    if args.parity:
        if quantize is None:
            assert max_rel < FP32_PARITY_RTOL, (
                f"fp32 ZeRO parity drift {max_rel:.3e} exceeds "
                f"{FP32_PARITY_RTOL} vs replicated Adam: "
                f"base={base_parity} zero={zero_parity}")
        else:
            assert max_rel < INT8_PARITY_RTOL, (
                f"int8+EF parity drift {max_rel:.3e} exceeds "
                f"{INT8_PARITY_RTOL}: base={base_parity} "
                f"zero={zero_parity}")

    doc = {
        "metric": f"gpt-smoke zero-dp{args.dp}"
                  f"{'-int8' if args.int8 else ''} train throughput "
                  f"({backend})",
        "value": round(zero_tok, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": round(zero_tok / base_tok, 3) if base_tok else 1.0,
        "dp": args.dp,
        "zero": True,
        "int8": bool(args.int8),
        "parity": parity,
        "state_bytes": {
            "replicated": base_state,
            "sharded": zero_state,
            "ratio": round(base_state / zero_state, 3) if zero_state
                     else None,
        },
        "baseline": {
            "value": round(base_tok, 1),
            "comm_fraction": base_telemetry.get("comm_fraction"),
            "comm_bytes_by_axis": base_telemetry.get("comm_bytes_by_axis"),
        },
        "telemetry": zero_telemetry,
    }
    line = json.dumps(doc)
    print(line)
    if args.artifact:
        with open(args.artifact, "w") as fh:
            fh.write(line + "\n")


if __name__ == "__main__":
    main()
