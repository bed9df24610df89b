"""Serving cells: ``serving.Scheduler`` over ``GenerationEngine`` under an
open loop at a rate fixed in the cell (construction copied from
``chip_smoke.py``).

One process, one thread: each pass of the loop submits what is due, then
runs one ``Scheduler.step()`` and notes, on the host's clock, every token
the tick produced. Latencies count from the instant a request was *due*,
so a stalled tick or a late generator shows. After the close a cell with
``drain`` keeps ticking until every request due in the window has its first
token (a late answer is late, not wrong). Then a sample of the finished
requests, drawn from the seed and with the longest in it, is checked
against the reference: one float32 forward pass over each prompt with its
served tokens, and the widest gap by which a served (greedy) token's logit
lies below the reference's best.

Cell parameters: ``engine`` (max_batch, max_len), ``traffic`` (see
``harness/loadgen.py``), ``drain``, ``check_requests``, ``trace_lead_s``.
"""
from __future__ import annotations

import collections
import faulthandler
import gc
import time

import numpy as np

from harness import heap, loadgen, trace_reduce

DRAIN_LIMIT_S = 60.0

#: one ``Scheduler.step()`` as the loop saw it: the seconds it took, when it
#: began, the requests it admitted, the gaps between tokens it made
Tick = collections.namedtuple("Tick", "took_s began_s admitted gaps")


def build(ctx, hooks):
    from paddle_tpu.profiler import telemetry
    from paddle_tpu.serving import GenerationEngine

    telemetry.reset()
    model = ctx.model.build(ctx.sizes, ctx.seed)
    eng = GenerationEngine(model, max_batch=ctx.cell["engine"]["max_batch"],
                           max_len=ctx.cell["engine"]["max_len"])
    return hooks.get("wrap_engine", lambda e: e)(eng)


def _warm(eng, arrivals, rng, vocab):
    """Every shape this traffic uses: its prefill buckets, and decode."""
    from paddle_tpu.serving import Request, Scheduler
    from paddle_tpu.serving.kv_cache import pick_bucket

    longest = {}
    for a in arrivals:
        b = pick_bucket(len(a.prompt), eng.prefill_buckets)
        longest[b] = max(longest.get(b, 0), len(a.prompt))
    for _ in range(2):  # the second pass finds everything compiled
        sched = Scheduler(eng)
        for n in sorted(longest.values()):
            sched.submit(Request(prompt=rng.integers(0, vocab, n).tolist(),
                                 max_new_tokens=3))
        sched.run()
    return sorted(longest)


class Window:
    """The open loop and what it observes."""

    def __init__(self, eng, arrivals, stall_dump_s=None):
        from paddle_tpu.serving import Scheduler

        self.sched = Scheduler(eng)
        self.arrivals = arrivals
        #: ``tools.py --vary`` only: a tick that takes longer has every
        #: thread's Python stack written to stderr while it is stalled
        self.stall_dump_s = stall_dump_s
        self.reqs, self.sent_s = [], []
        self.due_s = {}     # rid -> when it was due
        self.seen = {}      # rid -> tokens seen
        self.last_t = {}    # rid -> time of its last token
        self.ttft_s = {}    # rid -> first token - due
        self.gaps_s = []
        self.done_t = {}    # rid -> time it finished
        self.tick_log = []  # a Tick for each
        self.work = dict.fromkeys(
            ("ticks", "decode_steps", "decode_positions",
             "decode_live_tokens", "n_positions", "n_keys", "n_outputs"), 0)

    def submit_due(self, now, t0):
        from paddle_tpu.serving import Request

        while len(self.reqs) < len(self.arrivals) \
                and self.arrivals[len(self.reqs)].due_s <= now:
            a = self.arrivals[len(self.reqs)]
            # the program's own serve.queue_wait counts from the same
            # instant as the benchmark's TTFT: when the request was due
            r = self.sched.submit(Request(
                prompt=a.prompt, max_new_tokens=a.max_new,
                due_ns=int((t0 + a.due_s) * 1e9)))
            self.due_s[r.rid] = a.due_s
            self.reqs.append(r)
            self.sent_s.append(time.perf_counter() - t0)
            self.seen[r.rid] = 0

    def in_system(self, t):
        """Requests sent by ``t`` and not finished by then."""
        return sum(1 for r, sent in zip(self.reqs, self.sent_s)
                   if sent <= t and self.done_t.get(r.rid, 1e30) > t)

    def busy(self):
        s = self.sched
        return bool(s.queue or s.active or s.prefilling)

    def tick(self, t0):
        s, w = self.sched, self.work
        steps_before = s.decode_steps
        began = time.perf_counter() - t0
        if self.stall_dump_s:
            faulthandler.dump_traceback_later(self.stall_dump_s)
        done = s.step()
        if self.stall_dump_s:
            faulthandler.cancel_dump_traceback_later()
        t = time.perf_counter() - t0
        admitted, gaps_before = 0, len(self.gaps_s)
        w["ticks"] += 1
        w["decode_steps"] += s.decode_steps - steps_before
        for r in list(s.active.values()) + done:
            n, k = len(r.tokens), self.seen.get(r.rid)
            if k is None or n == k:
                continue
            p = len(r.prompt)
            if k == 0:  # prefilled in this tick: p positions, one output
                admitted += 1
                self.ttft_s[r.rid] = t - self.due_s[r.rid]
                w["n_positions"] += p
                w["n_keys"] += p * (p + 1) // 2
                w["n_outputs"] += 1
            else:
                self.gaps_s.append(t - self.last_t[r.rid])
            # tokens that came in one tick with another: no gap between them
            self.gaps_s.extend([0.0] * (n - max(k, 1) - (1 if k else 0)))
            for j in range(max(k, 1) + 1, n + 1):  # decoded: j-th output
                w["n_positions"] += 1
                w["n_keys"] += p + j - 1
                w["n_outputs"] += 1
                w["decode_positions"] += 1
                w["decode_live_tokens"] += p + j - 1
            self.seen[r.rid] = n
            self.last_t[r.rid] = t
        for r in done:
            self.done_t[r.rid] = t
        self.tick_log.append(Tick(t - began, began, admitted,
                                  len(self.gaps_s) - gaps_before))
        return t

    def in_system_at_quarters(self):
        """Requests in the system at each quarter of the window and at its
        close: a cell above capacity holds a full engine at every one."""
        return [self.in_system(self.total_s * q) for q in (.25, .5, .75, 1)]

    def live_slots(self):
        """Slots that decoded, mean over the window's decode steps."""
        return self.at_close["decode_positions"] \
            / max(1, self.at_close["decode_steps"])

    def slowest_ticks(self, top):
        """Indices of the ``top`` slowest ticks, the slowest first."""
        return sorted(range(len(self.tick_log)),
                      key=lambda i: -self.tick_log[i].took_s)[:top]

    def slowest_ticks_s_at(self, top):
        return [(round(self.tick_log[i].took_s, 3),
                 round(self.tick_log[i].began_s, 2))
                for i in self.slowest_ticks(top)]

    def tick_kinds(self):
        """The window's ticks by how many requests each admitted (0, 1,
        2+): its share of the ticks and of the gaps between tokens (a tick
        makes one gap for each request that was live before it), and its
        time. ``itl_p95_ms`` is a percentile over those gaps, so it sits
        where the cumulative share of gaps, by tick time, passes 95%."""
        ticks = [t for t in self.tick_log if t.began_s < self.closed_s]
        n_gaps = max(1, sum(t.gaps for t in ticks))
        out = {}
        for most, kind in enumerate(("0", "1", "2+")):
            mine = [t for t in ticks if min(t.admitted, 2) == most]
            if not mine:
                continue
            ms = np.array([t.took_s for t in mine]) * 1e3
            out[kind] = {
                "share_of_ticks": 100.0 * len(mine) / len(ticks),
                "share_of_gaps": 100.0 * sum(t.gaps for t in mine) / n_gaps,
                "ms_p50": float(np.median(ms)), "ms_mean": float(ms.mean()),
                "ms_p95": float(np.quantile(ms, 0.95))}
        return out


def _slowest_ticks_phases(tm, win, top=3):
    """The program's own record of the window's slowest ticks (those the
    ring still holds: the last 1,024): which phase the time went to. For
    whoever has to explain a wide run; no metric reads it."""
    mine = {r.index: r for r in tm.steps(kind="serve.tick",
                                         owner=win.sched.sched_id)}
    return [{"tick": i, "at_s": round(win.tick_log[i].began_s, 2),
             "ms": {k: round(v * 1e3, 2) for k, v in mine[i].phases.items()
                    if v >= 5e-4}}
            for i in win.slowest_ticks(top) if i in mine]


def _p95(xs):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(np.ceil(0.95 * len(xs))) - 1)]


def measure(ctx, eng, arrivals, length, lead=0.0, drain=False,
            stall_dump_s=None):
    """One window of ``lead + length`` seconds; with ``ctx.trace`` the
    profiler runs over the last ``length`` of it."""
    import jax

    span = jax.profiler.TraceAnnotation
    win = Window(eng, arrivals, stall_dump_s)
    total = lead + length
    tracing = None
    at_trace = None
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if ctx.trace and tracing is None and now >= lead:
            trace_reduce.start(ctx.trace_dir)
            tracing = span("bench:window")
            tracing.__enter__()
            at_trace = dict(win.work)
            t_on = time.perf_counter() - t0
        if now >= total:
            break
        with span("bench:submit"):
            win.submit_due(now, t0)
        if win.busy():
            with span("bench:sched_step"):
                win.tick(t0)
        else:
            nxt = arrivals[len(win.reqs)].due_s if len(win.reqs) < len(
                arrivals) else total
            with span("bench:idle_no_request"):
                time.sleep(max(0.0, min(nxt, total)
                               - (time.perf_counter() - t0)))
    closed = time.perf_counter() - t0
    # what the window itself saw: ticks after the close (the drain) add to
    # neither the tokens nor the gaps
    win.at_close = dict(win.work, requests=len(win.reqs),
                        gaps=len(win.gaps_s))
    win.queued_at_window_close = len(win.sched.queue)
    if tracing is not None:
        tracing.__exit__(None, None, None)
        win.traced_work = {k: v - at_trace[k] for k, v in win.work.items()}
        win.traced_s = closed - t_on
    win.submit_due(total, t0)  # whatever was due in the last instants
    if drain:
        while any(r.rid not in win.ttft_s and not r.finished
                  for r in win.reqs) \
                and time.perf_counter() - t0 < total + DRAIN_LIMIT_S:
            win.tick(t0)
    if tracing is not None:
        # after the drain: stopping the profiler takes seconds, which the
        # last requests' first tokens would otherwise wait for
        jax.profiler.stop_trace()
    win.closed_s = closed
    win.total_s = total
    return win


def _sample(win, seed, k):
    """Finished requests to check: the longest, and others from the seed."""
    done = [r for r in win.reqs if r.finish_reason in ("length", "eos")
            and r.tokens]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    rng = np.random.default_rng([int(seed), 3])
    rest = [done[i] for i in rng.permutation(len(done) - 1)[:k - 1]]
    return [done[-1]] + rest


def run(ctx, hooks=None):
    from paddle_tpu.profiler import telemetry

    hooks = hooks or {}
    telemetry.enable()
    sizes, cell = ctx.sizes, ctx.cell
    eng = build(ctx, hooks)
    t_built = time.perf_counter()
    lead = min(cell.get("trace_lead_s", 0.0), ctx.seconds) if ctx.trace \
        else 0.0
    length = ctx.trace_seconds if ctx.trace else ctx.seconds
    arrivals = loadgen.schedule(ctx.traffic, ctx.seed, lead + length,
                                sizes["vocab_size"])
    buckets = _warm(eng, arrivals, np.random.default_rng([ctx.seed, 4]),
                    sizes["vocab_size"])
    tm = telemetry.get_telemetry()
    compiles_before = dict(tm.compile_counts())
    ctx.log(f"set-up: build+weights {t_built - ctx.t_import:.1f}s, warm-up "
            f"{time.perf_counter() - t_built:.1f}s; buckets {buckets}; "
            f"compiles {compiles_before}")
    heap.settle()  # no full collection inside the window
    setup_s = time.perf_counter() - ctx.t_start
    with heap.Pauses() as pauses:
        win = measure(ctx, eng, arrivals, length, lead,
                      drain=cell.get("drain", False))

    counters = tm.counters()
    compiled_inside = dict(tm.compile_counts()) != compiles_before \
        or tm.recompile_count != 0
    bad = [n for n in ("serve.errors", "serve.oom_evictions",
                       "serve.degraded_steps", "serve.timeouts", "serve.shed")
           if counters.get(n)]
    failed = sum(1 for r in win.reqs if r.finished
                 and r.finish_reason not in ("length", "eos"))
    failed += sum(1 for r in win.reqs if r.finished
                  and len(r.tokens) != r.max_new_tokens)
    if bad or compiled_inside:
        ctx.log(f"counters {bad}, compiled inside the window: "
                f"{compiled_inside}")
        failed = max(failed, 1)
    inside = [r for r in win.reqs if win.done_t.get(r.rid, 1e30)
              <= win.closed_s]
    worst = DRAIN_LIMIT_S
    ttft = [win.ttft_s.get(r.rid, worst) for r in win.reqs]
    at_close = win.at_close
    gaps = win.gaps_s[:at_close["gaps"]]
    # requests that fell due during the last tick are sent after the close
    # (in a traced run after the profiler has stopped): late by design
    late_mean, late_max = loadgen.lateness_ms(
        [a.due_s for a in arrivals[:at_close["requests"]]],
        win.sent_s[:at_close["requests"]])
    values = {
        "setup_s": setup_s,
        "serve_tokens_per_s": at_close["n_outputs"] / win.closed_s,
        "ttft_p95_ms": _p95(ttft) * 1e3 if ttft else None,
        "itl_p95_ms": _p95(gaps) * 1e3 if gaps else None,
        "ttft_mean_ms": float(np.mean(ttft)) * 1e3 if ttft else None,
    }
    facts = {"requests_due": len(win.reqs), "finished_inside": len(inside),
             "cache_slots": cell["engine"]["max_batch"]
             * cell["engine"]["max_len"],
             "tokens_of_finished_per_s": sum(len(r.tokens) for r in inside)
             / win.closed_s,
             "ttft_p95_ms": _p95(ttft) * 1e3 if ttft else None,
             "ttft_p90_ms": float(np.quantile(ttft, 0.9)) * 1e3
             if ttft else None,
             "itl_p50_ms": float(np.median(gaps)) * 1e3 if gaps else None,
             "closed_s": win.closed_s,
             "backlog_at_close": len(win.reqs) - len(
                 [r for r in win.reqs if r.rid in win.done_t]),
             "queue_at_close": len(win.sched.queue),
             "queued_at_window_close": win.queued_at_window_close,
             "in_system_quarter_half_3quarter_close":
             win.in_system_at_quarters(),
             "tick_kinds": win.tick_kinds(),
             "live_slots_per_decode_step": win.live_slots(),
             "itl_samples": len(gaps), "ticks": at_close["ticks"],
             "generator_late_ms_mean": late_mean,
             "generator_late_ms_max": late_max, "work": at_close,
             "traced_work": getattr(win, "traced_work", None),
             "ttft_p50_ms": float(np.median(ttft)) * 1e3 if ttft else None,
             "slowest_ticks_s_at": win.slowest_ticks_s_at(4),
             "slowest_ticks_phases": _slowest_ticks_phases(tm, win),
             **pauses.facts()}
    ctx.log(f"window: {facts}")
    sample = [(list(r.prompt), list(r.tokens))
              for r in _sample(win, ctx.seed, cell["check_requests"])]
    peak = ctx.memory_peak()
    sched = win.sched
    del win, sched, eng
    gc.collect()

    t_ref = time.perf_counter()
    compared = {}
    ok = bool(sample)
    if sample:
        rows = ctx.model.reference.served_gaps(
            ctx.seed, sizes, sizes["dtype"], sample,
            cell["engine"]["max_len"], lowp="fp8" if ctx.control else None)
        gap = max(r[0] for r in rows)
        if ctx.control:  # the gap of the token the lower precision puts first
            compared["fp8.served_logit_gap"] = {
                "value": max(r[1] for r in rows), "limit": cell["limits"][
                    "served_logit_gap"]}
            ctx.log(f"control fp8.served_logit_gap: "
                    f"{max(r[1] for r in rows)} per request "
                    f"{[r[1] for r in rows]}; program's per request "
                    f"{[r[0] for r in rows]}")
        limit = cell["limits"]["served_logit_gap"]
        compared["served_logit_gap"] = {
            "value": gap, "limit": limit, "requests": len(sample),
            "tokens": sum(len(t) for _, t in sample)}
        ok = bool(np.isfinite(gap) and gap <= limit)
    else:
        compared["served_logit_gap"] = {"value": None, "limit": cell[
            "limits"]["served_logit_gap"], "requests": 0}
    ctx.log(f"reference: {time.perf_counter() - t_ref:.1f}s over "
            f"{len(sample)} requests")
    return {"values": values, "facts": facts,
            "attempted": max(1, facts["requests_due"]),
            "failed": failed, "correct": ok and not failed,
            "compared": compared, "memory_peak_bytes": peak}
