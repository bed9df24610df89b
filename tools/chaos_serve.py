#!/usr/bin/env python
"""Deterministic chaos harness for the serving tier (ISSUE 10 acceptance).

Floods a :class:`~paddle_tpu.serving.Scheduler` while injecting faults
through ``paddle_tpu.fault.inject`` and asserts the resilience contract:

* **full accounting** — every submitted request reaches EXACTLY ONE
  terminal ``finish_reason`` (``eos|length|timeout|shed|oom_evicted|
  error|drained``), and the ``serve.*`` telemetry counters agree with the
  per-request records;
* **no scheduler crash** — the injected OOM (``serve.decode``), transient
  prefill error (``serve.prefill``), draft fault (``serve.draft``),
  mid-verify faults (``serve.verify`` error + stall) and stall are
  absorbed by the degraded-decode / retry / plain-tick-fallback paths;
* **survivor parity** — the chaos pass serves with speculative decoding
  and chunked prefill ON while the clean reference runs the PLAIN greedy
  path (``Scheduler(speculative=False)``); every request that still
  finished normally (``eos``/``length``) must have produced the SAME
  token stream, token for token. That is the ISSUE-13 acceptance squared:
  spec output is byte-identical to greedy even while drafts drop,
  verifies fault mid-flight and neighbors get evicted around it;
* **overload pages** — an :class:`~paddle_tpu.profiler.slo.SLOMonitor`
  over the shipped ``SERVING_SLOS`` (driven on a synthetic clock, so burn
  windows are deterministic) must fire on the shed burst;
* **recovery** — after ``disarm_all()``, steady-state tokens/sec is back
  within 10% of the pre-chaos clean measurement (median of ``--reps``
  each).

The whole run is deterministic: seeded prompts, faults armed at fixed hit
counts, `retry_sleep` stubbed out, a deterministic largest-footprint OOM
victim, and submission order fixed — re-running produces the same event
log and the same survivor set.

Usage::

    python tools/chaos_serve.py --smoke       # CI gate (tiny CPU config)
    python tools/chaos_serve.py --json        # machine-readable result
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MAX_NEW = 8
CONCURRENCY = 4
MAX_QUEUE = 4
BUCKETS = (8, 16)
MAX_LEN = 64
SPEC_K = 4
PREFILL_CHUNK = 4


def build_engines(seed=0):
    """Tiny CPU GPT + TWO identically warmed engines over the same model:
    the chaos subject and a never-faulted CONTROL. The recovery check
    compares the two in interleaved passes, so slow host drift (thermal,
    another process) cancels instead of masquerading as a regression.
    Every executable — per-bucket prefill, decode, chunked prefill,
    speculative verify — is warmed up front; chaos must measure the
    steady state, not compiles."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import GenerationEngine

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, max_position_embeddings=MAX_LEN,
                    hidden_dropout=0.0, attention_dropout=0.0)
    paddle.seed(seed)
    model = GPTForCausalLM(cfg)
    engines = []
    for _ in range(2):
        eng = GenerationEngine(model, max_batch=CONCURRENCY,
                               max_len=MAX_LEN, prefill_buckets=BUCKETS,
                               spec_k=SPEC_K, prefill_chunk=PREFILL_CHUNK)
        for b in BUCKETS:
            eng.prefill(0, [1] * (b - 1))
        eng.decode_once(np.zeros(CONCURRENCY, np.int32))
        off, tok = 0, None
        warm = [1] * (PREFILL_CHUNK + 1)  # exactly two chunks
        while tok is None:
            tok = eng.prefill_chunk_step(0, warm, off)
            off += PREFILL_CHUNK
        # a verify does not advance lengths, so warming leaves no state
        eng.verify_once(np.zeros((CONCURRENCY, SPEC_K + 1), np.int32))
        engines.append(eng)
    return cfg, engines[0], engines[1]


def make_prompts(cfg, n, seed):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size,
                        int(rng.randint(4, BUCKETS[-1] // 2))).tolist()
            for _ in range(n)]


def _new_requests(prompts):
    from paddle_tpu.serving import Request

    return [Request(prompt=list(p), max_new_tokens=MAX_NEW) for p in prompts]


def run_clean(eng, prompts):
    """Reference pass: serve every prompt cleanly through the PLAIN
    greedy path (speculation forced off), return idx → tokens. The chaos
    pass then serves with speculation ON, so survivor parity doubles as
    the spec-vs-greedy byte-identity check under faults."""
    from paddle_tpu.serving import Scheduler

    sched = Scheduler(eng, speculative=False)
    reqs = _new_requests(prompts)
    for r in reqs:
        sched.submit(r)
    sched.run()
    return {i: list(r.tokens) for i, r in enumerate(reqs)}


def _tps_pass(eng, prompts):
    """One full serving pass → tokens/sec. Decodes 4× the chaos token
    budget so a pass is long enough (hundreds of decode ticks) for the
    10% recovery bar to sit above per-pass timing noise."""
    from paddle_tpu.serving import Request, Scheduler

    sched = Scheduler(eng)
    reqs = [Request(prompt=list(p), max_new_tokens=4 * MAX_NEW)
            for p in prompts]
    t0 = time.perf_counter()
    for r in reqs:
        sched.submit(r)
    fin = sched.run()
    wall = time.perf_counter() - t0
    return sum(len(r.tokens) for r in fin) / wall


def measure_pair(eng_a, eng_b, prompts, reps=3):
    """Best-of-``reps`` tokens/sec for two engines, passes INTERLEAVED
    (b, a, b, a, ...) so both sides sample the same host conditions.
    Best-of, not mean/median: host noise (GC, CPU frequency, another
    process) only ever SLOWS a pass, so the fastest pass is the cleanest
    steady-state estimate."""
    a_vals, b_vals = [], []
    for _ in range(reps):
        b_vals.append(_tps_pass(eng_b, prompts))
        a_vals.append(_tps_pass(eng_a, prompts))
    return max(a_vals), max(b_vals)


def run_chaos(seed=0, reps=3):
    """Clean → chaos → recovery. Returns a result dict with ``ok`` and the
    list of contract ``problems`` (empty on a green run)."""
    from paddle_tpu.fault import inject
    from paddle_tpu.profiler import telemetry, tracing
    from paddle_tpu.profiler.slo import SERVING_SLOS, SLOMonitor
    from paddle_tpu.serving import FINISH_REASONS, Request, Scheduler

    cfg, eng, control = build_engines(seed)
    prompts = make_prompts(cfg, 24, seed)

    # -- clean reference streams (survivor-parity baseline) ------------------
    clean_streams = run_clean(eng, prompts)

    problems = []
    counters = {}
    alerts = []
    reason_counts = {}
    survivors = 0
    try:
        # -- chaos pass ------------------------------------------------------
        inject.disarm_all()
        telemetry.reset()
        telemetry.enable(recompile_warn_threshold=len(BUCKETS) + 2)
        tracing.reset()
        tracing.enable()
        # synthetic clock (+1 s per check): SLO burn windows deterministic
        clk = {"now": 0.0}

        def clock():
            clk["now"] += 1.0
            return clk["now"]

        monitor = SLOMonitor(SERVING_SLOS, clock=clock,
                             sinks=[alerts.append])
        sched = Scheduler(eng, slo=monitor, slo_check_every=1,
                          max_queue=MAX_QUEUE,
                          retry_sleep=lambda s: None)
        # armed faults (fixed hit counts — fully replayable): a transient
        # prefill error the retry must absorb, a draft fault and two
        # mid-verify errors that must each fall back to a plain tick,
        # an OOM on one of those plain ticks (the third serve.decode hit)
        # that must evict exactly one victim, and a mid-verify stall (a
        # slow tick, not a dead one). With speculation healthy the
        # scheduler never decodes plain, so serve.decode hits are created
        # BY the draft/verify faults — the fallback chain under test.
        inject.arm("error", "serve.prefill", at=2)
        inject.arm("error", "serve.draft", at=2)
        inject.arm("error", "serve.verify", at=3)
        inject.arm("error", "serve.verify", at=5)
        inject.arm("oom", "serve.decode", at=3)
        inject.arm("stall", "serve.verify", at=7)

        chaos_reqs = _new_requests(prompts)
        # two requests with an already-expired deadline: deterministic
        # queue-wait timeouts at the first tick
        doomed = [Request(prompt=list(prompts[0]), max_new_tokens=MAX_NEW,
                          deadline_s=0.0) for _ in range(2)]
        submitted = list(doomed)
        for r in doomed:
            sched.submit(r)
        # flood in waves: each wave overflows the bounded queue (sheds burn
        # the serve.shed SLO between monitor checks), then the scheduler
        # ticks a few times before the next wave lands
        for lo in range(0, len(chaos_reqs), 8):
            for r in chaos_reqs[lo:lo + 8]:
                submitted.append(sched.submit(r))
            sched.step()
            sched.step()
        sched.run()
        sched.shutdown()
        inject.disarm_all()

        # -- contract checks -------------------------------------------------
        # exactly one terminal reason per submitted request
        fin = sched.finished
        if len(fin) != len(submitted):
            problems.append(f"accounting: {len(submitted)} submitted but "
                            f"{len(fin)} finished")
        if len({r.rid for r in fin}) != len(fin):
            problems.append("accounting: a request finished more than once")
        for r in submitted:
            if not r.finished or r.finish_reason not in FINISH_REASONS:
                problems.append(f"rid {r.rid}: no terminal finish_reason "
                                f"(got {r.finish_reason!r})")
                break
        for r in fin:
            reason_counts[r.finish_reason] = \
                reason_counts.get(r.finish_reason, 0) + 1
        # the injected faults must actually have produced their reasons
        for want in ("shed", "timeout", "oom_evicted"):
            if not reason_counts.get(want):
                problems.append(f"chaos produced no {want!r} termination")
        # telemetry counters must agree with the per-request records
        counters = {k: v for k, v in
                    telemetry.get_telemetry().counters().items()
                    if k.startswith("serve.")}
        for reason, counter in (("shed", "serve.shed"),
                                ("timeout", "serve.timeouts"),
                                ("oom_evicted", "serve.oom_evictions"),
                                ("drained", "serve.drained")):
            want = reason_counts.get(reason, 0)
            got = int(counters.get(counter, 0))
            if got != want:
                problems.append(f"{counter}={got} but {want} request(s) "
                                f"finished {reason!r}")
        if not counters.get("serve.degraded_steps"):
            problems.append("injected decode OOM did not count a "
                            "degraded step")
        # the speculative surface must have been exercised AND survived:
        # spec ticks ran, and both injected verify faults degraded to
        # plain ticks instead of killing the scheduler
        if not counters.get("serve.spec_ticks"):
            problems.append("chaos pass ran no speculative ticks")
        if int(counters.get("serve.spec_fallback_ticks", 0)) < 2:
            problems.append(
                f"expected both injected verify faults to force plain-"
                f"tick fallbacks, got serve.spec_fallback_ticks="
                f"{counters.get('serve.spec_fallback_ticks', 0)}")
        if not counters.get("serve.prefill_chunks"):
            problems.append("chaos pass never took the chunked-prefill "
                            "path")
        # abnormal terminations must be queryable as trace event spans
        span_names = {s.name for s in tracing.get_tracer().spans()}
        for want in ("shed", "timeout", "oom_evicted"):
            if want in reason_counts and want not in span_names:
                problems.append(f"no {want!r} trace event span recorded")
        # overload must page: the shed burst burns the serve.shed SLO
        if not any(a["metric"] == "serve.shed" for a in alerts):
            problems.append("SLO monitor never fired on the shed burst "
                            f"({len(alerts)} alert(s) total)")
        # survivor parity: normal finishers match the clean run exactly
        for i, r in enumerate(chaos_reqs):
            if r.finish_reason in ("eos", "length"):
                survivors += 1
                if r.tokens != clean_streams[i]:
                    problems.append(
                        f"survivor rid {r.rid} diverged from the clean "
                        f"run: {r.tokens[:4]}... vs "
                        f"{clean_streams[i][:4]}...")
        if survivors == 0:
            problems.append("chaos left no surviving request to check "
                            "parity against")
    except Exception as e:  # noqa: BLE001 — a crash IS the finding
        problems.append(f"scheduler crashed under chaos: {type(e).__name__}: "
                        f"{e}")
    finally:
        inject.disarm_all()
        telemetry.disable()
        tracing.disable()

    # -- recovery: post-chaos steady state within 10% of the clean control —
    # interleaved passes against the never-faulted engine, measured under
    # identical host conditions
    recovery_tps, clean_tps = measure_pair(eng, control, prompts, reps=reps)
    if recovery_tps < 0.9 * clean_tps:
        problems.append(f"post-chaos throughput {recovery_tps:.1f} tok/s "
                        f"recovered to less than 90% of the clean control "
                        f"{clean_tps:.1f} tok/s")

    return {
        "ok": not problems,
        "problems": problems,
        "submitted": 26,
        "finish_reasons": reason_counts,
        "survivors": survivors,
        "slo_alerts": len(alerts),
        "clean_tokens_per_sec": round(clean_tps, 2),
        "recovery_tokens_per_sec": round(recovery_tps, 2),
        "counters": {k: v for k, v in sorted(counters.items())},
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate (same deterministic run; nonzero exit on "
                         "any contract violation)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--reps", type=int, default=3,
                    help="throughput samples per median (clean + recovery)")
    ap.add_argument("--json", action="store_true",
                    help="print the result as one JSON object")
    args = ap.parse_args(argv)

    result = run_chaos(seed=args.seed, reps=args.reps)
    if args.json:
        print(json.dumps(result))
    else:
        status = "OK" if result["ok"] else "FAILED"
        print(f"chaos_serve {status}: {result['submitted']} submitted, "
              f"reasons {result['finish_reasons']}, "
              f"{result['survivors']} survivor(s) token-exact, "
              f"{result['slo_alerts']} SLO alert(s), clean "
              f"{result['clean_tokens_per_sec']} tok/s → recovery "
              f"{result['recovery_tokens_per_sec']} tok/s")
        for p in result["problems"]:
            print(f"  problem: {p}", file=sys.stderr)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
