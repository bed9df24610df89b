"""Long-context training demonstration on one chip.

Trains the flagship GPT at growing sequence lengths. Attention memory
stays O(s·d): the Pallas flash kernel (1024x1024 tiles) on TPU, the
blockwise online-softmax KV scan (ISSUE 15) everywhere else — never the
O(s²) einsum score matrix. The multi-chip extension is ring attention
over the `sep` axis (distributed/meta_parallel/sequence_parallel.py),
dryrun-validated on the virtual mesh; this tool shows the single-chip
long-seq numbers the ring composes from.

Every row also carries the PREDICTED HBM peak of the train step
(``analysis.analyze_memory`` — abstract trace, the upper-bound model the
mem-lint crosscheck gates) next to the einsum path's predicted peak on
the same shapes: the static series is honest on CPU, where the 16k/32k
rows never execute. ``--predict-only`` (the default off-TPU) skips
execution entirely; ``--remat BYTES|auto`` runs the selective-remat
autopilot first; ``--capacity BYTES`` turns the run into a gate — every
blockwise row must fit the budget (exit 1 otherwise), and rows where the
einsum peak blows it are marked.

Run: python tools/long_context_bench.py [--seqs 2048,...,32768]
Writes LONGCTX_r15.json at the repo root (TPU measured run, or a
--predict-only static run).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2048,4096,8192,16384,32768")
    # per-seq batch optima measured on v5e (r5): s2048 b16 > b12/b8;
    # s4096 b6 > b4/b8; s8192 b4 > b2/b3/b6. 16k/32k run at FIXED batch 2:
    # the r15 acceptance is context growth at constant batch, not a
    # tokens-per-batch trade
    ap.add_argument("--tokens-per-batch", type=int, default=0)
    ap.add_argument("--no-artifact", action="store_true")
    ap.add_argument("--predict-only", action="store_true", default=None,
                    help="static analysis only, no device execution "
                         "(default on non-TPU backends)")
    ap.add_argument("--remat", default=None,
                    help='selective-remat autopilot budget: "auto" '
                         "(device HBM capacity) or bytes")
    ap.add_argument("--capacity", type=float, default=None,
                    help="HBM budget in bytes: every blockwise row must "
                         "fit (exit 1 otherwise); einsum rows that blow "
                         "it are marked")
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    from paddle_tpu import analysis
    from paddle_tpu.framework.compile_cache import enable_compile_cache
    from paddle_tpu.framework.flags import set_flags
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    enable_compile_cache()
    on_tpu = jax.default_backend() != "cpu"
    predict_only = (not on_tpu if args.predict_only is None
                    else args.predict_only)
    remat = args.remat
    if remat not in (None, "auto"):
        remat = float(remat)
    results = []
    over_capacity = False
    MEASURED_BATCH = {2048: 16, 4096: 6, 8192: 4, 16384: 2, 32768: 2}
    for seq in [int(s) for s in args.seqs.split(",")]:
        if args.tokens_per_batch:
            batch = max(1, args.tokens_per_batch // seq)
        else:
            batch = MEASURED_BATCH.get(seq, max(1, 32768 // seq))
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=seq,
                        hidden_dropout=0.0, attention_dropout=0.0)
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

        def train_step(ids, labels):
            loss = model.loss(ids, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        def make_step():
            return CompiledStep(train_step, stateful=[model, opt],
                                donate_state=True)

        rng = np.random.RandomState(0)  # fixed: numbers must reproduce
        example = Tensor(rng.randint(0, cfg.vocab_size,
                                     (batch, seq)).astype(np.int64))

        # the static series: predicted peak for the blockwise step and
        # for the einsum path on the SAME shapes (abstract trace only)
        set_flags({"disable_blockwise_attention": True})
        peak_einsum = analysis.analyze_memory(
            make_step(), example, example).peak_bytes
        set_flags({"disable_blockwise_attention": False})
        remat_report = None
        if remat is not None:
            remat_report = analysis.auto_remat(
                model, remat, make_step, (example, example),
                name=f"longctx_{seq}")
            peak_pred = remat_report.peak_after
        else:
            peak_pred = analysis.analyze_memory(
                make_step(), example, example).peak_bytes

        fits = None
        if args.capacity is not None:
            fits = peak_pred <= args.capacity
            over_capacity |= not fits

        row = {"seq": seq, "batch": batch,
               "hbm_peak_bytes": float(peak_pred),
               "hbm_peak_bytes_einsum": float(peak_einsum),
               "predicted_only": predict_only}
        if remat_report is not None:
            row["remat_blocks"] = remat_report.blocks_wrapped
        if fits is not None:
            row["fits_capacity"] = bool(fits)
            row["einsum_fits_capacity"] = bool(
                peak_einsum <= args.capacity)
        cap_note = ""
        if fits is not None:
            cap_note = (" fits-capacity" if fits else " OVER-CAPACITY") \
                + ("" if peak_einsum <= args.capacity
                   else " (einsum blows it)")

        if predict_only:
            print(f"seq={seq:6d} batch={batch:3d}: predicted peak "
                  f"{peak_pred / 2**30:7.2f} GiB (einsum "
                  f"{peak_einsum / 2**30:7.2f} GiB, "
                  f"{peak_einsum / peak_pred:.2f}x){cap_note}", flush=True)
            results.append(row)
            jax.clear_caches()
            continue

        step = make_step()
        n = 6
        batches = [Tensor(rng.randint(0, cfg.vocab_size,
                                      (batch, seq)).astype(np.int64))
                   for _ in range(2 + n)]
        for i in range(2):
            np.asarray(step(batches[i], batches[i])._value)
        t0 = time.perf_counter()
        outs = [step(b, b) for b in batches[2:]]
        last = float(np.asarray(outs[-1]._value))
        dt = (time.perf_counter() - t0) / n
        toks = batch * seq / dt
        # attention share grows with s: flops/token = 6*N_mat + 12*L*H*s;
        # MFU only against a KNOWN chip peak (tools/bench_common.py policy)
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from bench_common import device_peak

        n_mat = cfg.num_layers * 12 * cfg.hidden_size ** 2 \
            + cfg.vocab_size * cfg.hidden_size
        fpt = 6 * n_mat + 12 * cfg.num_layers * cfg.hidden_size * seq
        _, peak = device_peak()
        mfu = toks * fpt / peak if (on_tpu and peak) else float("nan")
        assert np.isfinite(last)
        print(f"seq={seq:6d} batch={batch:3d}: {dt * 1e3:8.1f} ms/step "
              f"{toks:9.0f} tok/s  mfu={mfu:.3f}  loss={last:.3f}"
              f"{cap_note}", flush=True)
        row.update({"ms_per_step": round(dt * 1e3, 1),
                    "tokens_per_sec": round(toks, 1),
                    "mfu": round(mfu, 4) if np.isfinite(mfu) else None})
        results.append(row)
        jax.clear_caches()
    if (on_tpu or predict_only) and not args.no_artifact:
        with open("LONGCTX_r15.json", "w") as f:
            json.dump({"results": results,
                       "predict_only": predict_only,
                       "remat": args.remat,
                       "capacity": args.capacity}, f, indent=1)
            f.write("\n")
    if over_capacity:
        print("FAIL: a blockwise row exceeded --capacity", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
