#!/usr/bin/env python
"""Serving benchmark: continuous batching vs batch-of-1 sequential decode.

Serves a GPT config from ``paddle_tpu.models`` through the
``paddle_tpu.serving`` tier (static-shape KV cache, prefill/decode split,
slot-based continuous batching) and reports:

* aggregate tokens/sec for (a) SEQUENTIAL serving — one request at a
  time through a batch-1 engine, the no-batching baseline — and (b)
  CONTINUOUS batching at ``--concurrency`` slots, plus the speedup;
* user-perceived p50/p95 request latency (arrival → last token, so the
  sequential baseline pays its queue wait — that is the point);
* decode-batch occupancy and requests-in-flight from telemetry;
* the O(1)-decode proof: telemetry compile counters (decode must compile
  EXACTLY once; prefill once per length bucket; the speculative verify
  and chunked-prefill steps exactly once each) and a static graph-lint
  of the decode step at two consecutive positions (zero shape-churn /
  kv-cache findings).

Serving speed v2 (ISSUE 13): the continuous engine runs with
speculative decoding (``--spec-k``, n-gram prompt-lookup drafts verified
in one ``[batch, k+1]`` forward — output stays byte-identical to greedy)
and chunked prefill (``--prefill-chunk``) ON by default; pass 0 to
disable either. ``--prompt-len-sweep`` appends TTFT-vs-prompt-length
rows to the artifact so the flat-TTFT claim is a tracked series, and the
telemetry block carries ``serve.spec_acceptance_rate`` plus the
``recompile_whitelist`` marker that lets bench_sentinel hard-gate
``recompile_count`` as an 'equal' contract metric.

Long-context raw speed (ISSUE 15): ``--long-prompt`` switches to the
long-prompt leg — 4x max_len and prefill buckets, every prompt in the
top bucket — so prefill, chunked prefill, and decode all route through
the blockwise cached attention path (length-masked KV-block scan / the
Pallas flash cached kernel on TPU) instead of the dense additive mask.
All the contract assertions below still apply verbatim: the blockwise
route must stay O(1)-decode, recompile-free, and byte-identical greedy.

Emits one JSON line and (with ``--artifact``) a SERVE_r*.json. ``--smoke``
runs a tiny CPU config and hard-asserts the telemetry contract — wired
into ``tools/run_tests.sh`` as a CI gate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pctl(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs else 0.0


def build_model(smoke, long_prompt=False):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    if smoke:
        cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                        num_heads=2,
                        max_position_embeddings=256 if long_prompt else 64,
                        hidden_dropout=0.0, attention_dropout=0.0)
    else:
        # GPT-2 small (124M) — the same flagship config bench.py trains
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_position_embeddings=1024,
                        hidden_dropout=0.0, attention_dropout=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return cfg, model


def make_requests(cfg, n, max_new, buckets, seed, long_prompt=False):
    from paddle_tpu.serving import Request

    rng = np.random.RandomState(seed)
    if long_prompt:
        # every prompt lands in the top bucket: prefill runs at blockwise
        # lengths instead of the short-prompt regime
        lo, hi = buckets[-1] // 2 + 1, buckets[-1]
    else:
        lo, hi = 4, max(5, buckets[-1] // 2)
    return [Request(prompt=rng.randint(0, cfg.vocab_size,
                                       int(rng.randint(lo, hi))).tolist(),
                    max_new_tokens=max_new)
            for _ in range(n)]


def run_sequential(model, requests, max_len, buckets):
    """Batch-of-1 serial decode: every request waits for its predecessors
    (user-perceived latency includes that wait — all requests 'arrive' at
    t0). Run OUTSIDE the telemetry window so the continuous engine's
    compile counters stay clean (both steps share their step names)."""
    from paddle_tpu.serving import GenerationEngine

    eng = GenerationEngine(model, max_batch=1, max_len=max_len,
                           prefill_buckets=buckets)
    # warm every executable (one per bucket + decode) outside the timer
    for b in buckets:
        eng.generate([1] * min(b, max_len - 2), max_new_tokens=2)
    t0 = time.perf_counter()
    lat, tokens = [], 0
    for req in requests:
        out = eng.generate(req.prompt, max_new_tokens=req.max_new_tokens,
                           eos_id=req.eos_id)
        tokens += len(out)
        lat.append(time.perf_counter() - t0)  # includes queue wait
    wall = time.perf_counter() - t0
    return {
        "wall_s": round(wall, 4),
        "tokens": tokens,
        "tokens_per_sec": round(tokens / wall, 2) if wall else None,
        "p50_latency_s": round(_pctl(lat, 50), 4),
        "p95_latency_s": round(_pctl(lat, 95), 4),
    }


def warm_engine(eng, buckets, max_len, concurrency):
    """Compile every serving executable outside the timers: one prefill
    per bucket, the decode step, and (when built) the chunked-prefill and
    speculative-verify steps. Compiles still land in telemetry."""
    for b in buckets:
        eng.prefill(0, [1] * min(b, max_len - 2))
    eng.decode_once(np.zeros(concurrency, np.int32))
    if eng.prefill_chunk:
        warm = [1] * (eng.prefill_chunk + 1)  # exactly two chunks
        off, tok = 0, None
        while tok is None:
            tok = eng.prefill_chunk_step(0, warm, off)
            off += eng.prefill_chunk
    if eng.spec_k:
        # lengths are NOT advanced by a verify, so this leaves no state
        eng.verify_once(np.zeros((concurrency, eng.spec_k + 1), np.int32))


def run_continuous(model, requests, max_len, buckets, concurrency,
                   spec_k=0, prefill_chunk=None):
    """Continuous batching under telemetry: compiles (during warmup) and
    the scheduler's serve.* stats all land in the registry."""
    from paddle_tpu.profiler import telemetry
    from paddle_tpu.serving import GenerationEngine, Scheduler

    telemetry.reset()
    # recompiling once per prefill bucket is the DESIGN here, not churn —
    # lift the per-step-name warning threshold above the bucket count
    telemetry.enable(recompile_warn_threshold=len(buckets) + 2)
    eng = GenerationEngine(model, max_batch=concurrency, max_len=max_len,
                           prefill_buckets=buckets, spec_k=spec_k,
                           prefill_chunk=prefill_chunk or None)
    warm_engine(eng, buckets, max_len, concurrency)

    sched = Scheduler(eng)
    t0 = time.perf_counter()
    submit_ns = time.perf_counter_ns()
    for req in requests:
        sched.submit(req)
        req.submit_ns = submit_ns  # common arrival instant, like sequential
    finished = sched.run()
    wall = time.perf_counter() - t0

    lat = [r.latency_s for r in finished if r.latency_s is not None]
    ttft = [r.ttft_s for r in finished if r.ttft_s is not None]
    tokens = sum(len(r.tokens) for r in finished)
    tm = telemetry.get_telemetry()
    stats = {
        "wall_s": round(wall, 4),
        "tokens": tokens,
        "tokens_per_sec": round(tokens / wall, 2) if wall else None,
        "p50_latency_s": round(_pctl(lat, 50), 4),
        "p95_latency_s": round(_pctl(lat, 95), 4),
        "p50_ttft_s": round(_pctl(ttft, 50), 4),
        "p95_ttft_s": round(_pctl(ttft, 95), 4),
        "batch_occupancy": round(sched.occupancy(), 4),
        "decode_steps": sched.decode_steps,
        # the drain retires the in-flight gauges (stale-gauge fix); a
        # fully-drained run reports 0 by construction
        "requests_in_flight": tm.gauges().get("serve.requests_in_flight",
                                              0.0),
    }
    # publish the bench headline back into the registry so the telemetry
    # block (and anything tailing the exporter) carries it
    tm.set_gauge("serve.tokens_per_s", stats["tokens_per_sec"] or 0.0)
    tm.set_gauge("serve.p95_latency_s", stats["p95_latency_s"])
    tm.set_gauge("serve.p50_latency_s", stats["p50_latency_s"])
    tm.set_gauge("serve.batch_occupancy", stats["batch_occupancy"])
    telemetry.disable()  # data stays readable for the block below
    return eng, sched, stats


def lint_decode(eng):
    """Static O(1) proof: lint the decode step against two CONSECUTIVE
    positions — with the static cache both signatures are identical, so
    shape-churn/kv-cache findings must be zero."""
    from paddle_tpu import analysis

    a1 = eng.example_decode_args([5] * min(2, eng.max_batch))
    a2 = eng.example_decode_args([6] * min(2, eng.max_batch))
    report = analysis.lint_step(eng.decode_step, *a1, extra_args=[a2])
    churn = [f for f in report
             if f.rule in ("retrace-shape-churn", "kv-cache-concat")]
    return {
        "findings": len(report),
        "shape_churn_findings": len(churn),
        "rules": sorted({f.rule for f in report}),
    }


def run_prompt_len_sweep(cfg, model, max_len, buckets, concurrency,
                         spec_k, prefill_chunk, seed, lengths=None):
    """TTFT vs prompt length, at queue pressure (2× concurrency, every
    prompt the same length L): with one-shot prefill the second wave's
    TTFT inherits every first-wave prefill whole, so p95 TTFT scales
    with L; chunked prefill amortizes each prompt into bounded per-tick
    chunks that ride along with decode. Rows land in the artifact so the
    claim is a tracked series; ``growth_ratio`` < 1 means p95 TTFT grew
    sub-linearly vs the prompt length itself."""
    from paddle_tpu.serving import GenerationEngine, Request, Scheduler

    eng = GenerationEngine(model, max_batch=concurrency, max_len=max_len,
                           prefill_buckets=buckets, spec_k=spec_k,
                           prefill_chunk=prefill_chunk or None)
    warm_engine(eng, buckets, max_len, concurrency)
    max_new = 8  # short decode budget: the sweep isolates TTFT
    lengths = [x for x in (lengths or (4, 8, 16, 24, 32))
               if x <= buckets[-1] and x + max_new <= max_len]
    rng = np.random.RandomState(seed)
    rows = []
    for L in lengths:
        reqs = [Request(prompt=rng.randint(0, cfg.vocab_size, L).tolist(),
                        max_new_tokens=max_new)
                for _ in range(2 * concurrency)]
        sched = Scheduler(eng)
        t0 = time.perf_counter_ns()
        for r in reqs:
            sched.submit(r)
            r.submit_ns = t0  # common arrival instant
        sched.run()
        ttft = [r.ttft_s for r in reqs if r.ttft_s is not None]
        rows.append({"prompt_len": int(L),
                     "requests": len(reqs),
                     "p50_ttft_s": round(_pctl(ttft, 50), 4),
                     "p95_ttft_s": round(_pctl(ttft, 95), 4)})
    lo, hi = rows[0], rows[-1]
    growth = None
    if lo["p95_ttft_s"] > 0 and hi["prompt_len"] > lo["prompt_len"]:
        growth = round((hi["p95_ttft_s"] / lo["p95_ttft_s"])
                       / (hi["prompt_len"] / lo["prompt_len"]), 4)
    return {"rows": rows, "growth_ratio": growth,
            "sub_linear": bool(growth is not None and growth < 1.0)}


def telemetry_serve_block():
    from paddle_tpu.profiler import telemetry

    s = telemetry.summary()
    block = {k: v for k, v in s["gauges"].items() if k.startswith("serve.")}
    block.update({k: v for k, v in s["counters"].items()
                  if k.startswith("serve.")})
    block["compiles"] = dict(s["compiles"])
    block["recompile_count"] = int(s["recompile_count"])
    tm = telemetry.get_telemetry()
    # the marker bench_sentinel keys on: recompile_count in THIS artifact
    # is declared-variant aware (per-bucket prefill compiles are design,
    # not churn), so the sentinel may 'equal'-gate it at 0
    block["recompile_whitelist"] = {
        k: int(v) for k, v in sorted(tm.declared_variants().items())}
    for name in ("serve.ttft_s", "serve.tpot_s", "serve.latency_s"):
        st = tm.get(name)
        if st and st.get("count"):
            block[name + ".mean"] = round(st["sum"] / st["count"], 6)
            # exact running sum plus the reservoir percentiles (the
            # sentinel and scrapers want rate-correct figures)
            block[name + ".sum"] = round(st["sum"], 6)
            block[name + ".p50"] = round(tm.stat(name, "p50"), 6)
            block[name + ".p95"] = round(tm.stat(name, "p95"), 6)
    return block


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU config + hard telemetry assertions "
                         "(the run_tests.sh CI gate)")
    ap.add_argument("--concurrency", type=int, default=8)
    ap.add_argument("--requests", type=int, default=None)
    ap.add_argument("--max-new-tokens", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spec-k", type=int, default=None,
                    help="speculative draft length (default 4; 0 disables)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill width (default 16, smoke 4; "
                         "0 disables)")
    ap.add_argument("--prompt-len-sweep", action="store_true",
                    help="append TTFT-vs-prompt-length rows to the "
                         "artifact (sub-linear growth is the contract)")
    ap.add_argument("--long-prompt", action="store_true",
                    help="long-prompt leg (ISSUE 15): 4x max_len and "
                         "buckets, every prompt in the top bucket, so "
                         "prefill/decode take the blockwise cached-"
                         "attention route instead of the dense mask")
    ap.add_argument("--artifact", default=None)
    ap.add_argument("--chaos", action="store_true",
                    help="also run tools/chaos_serve.py and embed its "
                         "verdict as the chaos_ok contract metric (the "
                         "bench_sentinel 'equal'-direction gate)")
    args = ap.parse_args(argv)

    from paddle_tpu.framework.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.smoke:
        args.concurrency = min(args.concurrency, 4)
    n_req = args.requests or 2 * args.concurrency
    max_new = args.max_new_tokens or (8 if args.smoke else 64)
    # serving speed v2 is the default path; 0 opts out of either feature
    spec_k = 4 if args.spec_k is None else max(0, args.spec_k)
    prefill_chunk = ((4 if args.smoke else 16) if args.prefill_chunk is None
                     else max(0, args.prefill_chunk))

    cfg, model = build_model(args.smoke, long_prompt=args.long_prompt)
    # size the cache to the workload: largest prompt (buckets[-1]/2) plus
    # the generation budget — decode attention + cache traffic scale with
    # max_len, so capacity beyond the worst case is pure per-step cost
    if args.long_prompt:
        # long-prompt leg: the KV lengths must cross the blockwise route.
        # The full config reaches the stock min-kv threshold (1024) on its
        # own; the smoke config is held small, so lower the threshold to
        # its bucket scale — same route, CPU-sized shapes
        max_len = 256 if args.smoke else cfg.max_position_embeddings
        buckets = (64, 128) if args.smoke else (256, 512)
        if args.smoke:
            from paddle_tpu.framework.flags import set_flags

            set_flags({"blockwise_attention_min_kv": 64})
    else:
        max_len = 64 if args.smoke else 32 + max_new
        buckets = (8, 16) if args.smoke else (16, 64)

    requests = make_requests(cfg, n_req, max_new, buckets, args.seed,
                             long_prompt=args.long_prompt)
    # identical prompts for both runs (Request objects are stateful):
    from paddle_tpu.serving import Request

    seq_requests = [Request(prompt=list(r.prompt),
                            max_new_tokens=r.max_new_tokens)
                    for r in requests]

    sequential = run_sequential(model, seq_requests, max_len, buckets)
    eng, sched, continuous = run_continuous(model, requests, max_len,
                                            buckets, args.concurrency,
                                            spec_k=spec_k,
                                            prefill_chunk=prefill_chunk)
    lint = lint_decode(eng)
    tblock = telemetry_serve_block()

    speedup = None
    if sequential["tokens_per_sec"] and continuous["tokens_per_sec"]:
        speedup = round(continuous["tokens_per_sec"]
                        / sequential["tokens_per_sec"], 3)

    result = {
        "metric": "serve_tokens_per_sec",
        "value": continuous["tokens_per_sec"],
        "unit": "tok/s",
        "speedup_vs_sequential": speedup,
        "config": {
            "model": "gpt2-smoke" if args.smoke else "gpt2-124M",
            "vocab_size": cfg.vocab_size, "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
            "max_len": max_len, "prefill_buckets": list(buckets),
            "concurrency": args.concurrency, "requests": n_req,
            "max_new_tokens": max_new,
            "spec_k": spec_k, "prefill_chunk": prefill_chunk,
            "long_prompt": bool(args.long_prompt),
        },
        "sequential": sequential,
        "continuous": continuous,
        "decode_lint": lint,
        "telemetry": tblock,
    }
    if args.prompt_len_sweep:
        # runs after the telemetry block is captured so the sweep's own
        # engine/compiles cannot perturb the contract counters above
        sweep_lengths = None
        if args.long_prompt:
            sweep_lengths = (buckets[0] // 2, buckets[0],
                             (buckets[0] + buckets[-1]) // 2, buckets[-1])
        sweep = run_prompt_len_sweep(cfg, model, max_len, buckets,
                                     args.concurrency, spec_k,
                                     prefill_chunk, args.seed,
                                     lengths=sweep_lengths)
        result["prompt_len_sweep"] = sweep
    chaos = None
    if args.chaos:
        # the chaos contract is config-independent, so the harness always
        # runs its own tiny deterministic config — cheap even when the
        # bench itself ran gpt2-124M
        import chaos_serve

        chaos = chaos_serve.run_chaos(seed=args.seed)
        result["chaos_ok"] = 1.0 if chaos["ok"] else 0.0
        result["chaos"] = {k: chaos[k] for k in
                           ("finish_reasons", "survivors", "slo_alerts",
                            "problems")}
    print(json.dumps(result))
    if args.artifact:
        with open(args.artifact, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")

    # CI contract (the satellite gate): the telemetry block must carry the
    # serving headline gauges, the decode step must have compiled exactly
    # once, and the static lint must see a shape-stable decode
    problems = []
    if "serve.tokens_per_s" not in tblock:
        problems.append("telemetry block missing serve.tokens_per_s")
    if "serve.p95_latency_s" not in tblock:
        problems.append("telemetry block missing serve.p95_latency_s")
    if tblock["compiles"].get("serve_decode") != 1:
        problems.append(f"decode compiled "
                        f"{tblock['compiles'].get('serve_decode')}x "
                        f"(want exactly 1)")
    if tblock["compiles"].get("serve_prefill", 0) > len(buckets):
        problems.append("prefill compiled more than once per bucket")
    if spec_k and tblock["compiles"].get("serve_verify") != 1:
        problems.append(f"verify compiled "
                        f"{tblock['compiles'].get('serve_verify')}x "
                        f"(want exactly 1)")
    if prefill_chunk and tblock["compiles"].get("serve_prefill_chunk") != 1:
        problems.append(f"chunked prefill compiled "
                        f"{tblock['compiles'].get('serve_prefill_chunk')}x "
                        f"(want exactly 1)")
    if tblock["recompile_count"] != 0:
        problems.append(f"recompile_count {tblock['recompile_count']} "
                        f"(every variant must be declared)")
    if spec_k and not tblock.get("serve.spec_ticks"):
        problems.append("speculation enabled but no speculative ticks ran")
    sweep = result.get("prompt_len_sweep")
    if sweep is not None and prefill_chunk and not sweep["sub_linear"]:
        problems.append(f"p95 TTFT grew super-linearly with prompt length "
                        f"(growth_ratio {sweep['growth_ratio']})")
    if lint["shape_churn_findings"]:
        problems.append(f"decode lint: {lint['shape_churn_findings']} "
                        f"shape-churn/kv-cache finding(s)")
    if chaos is not None and not chaos["ok"]:
        problems.append("chaos harness: " + "; ".join(chaos["problems"]))
    if problems:
        print("bench_serve FAILED: " + "; ".join(problems), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
