"""Tooling around ``run.py`` for whoever defines a cell or sets a limit.
None of it gives a result, and the driver never calls it.

    python benchmark/tools.py --workload <cell> [--seconds s] [--trace 1] ...

- ``--seeds a,b,c``: one process, one line per seed (set-up shared): what
  sound runs read, before a limit is set.
- ``--control``: the control (the reference a precision lower, fp8, in the
  program's place; for training also a planted fault), for the upper
  reading of a limit.
- ``--vary FILE``: serving. ``FILE`` holds a JSON list of objects, each laid
  over the cell's ``traffic`` (nested objects merged); one set-up, a window
  each, the system emptied in between. ``[{"rate_per_s": 1.0}, ...]`` is the
  sweep from which the knee is read; other keys show what a choice of the
  traffic moves; ``seed`` and ``drain`` in an object stand for the run's.
  ``knee_sweep.json`` is the sweep and the window far above capacity that
  the serving cells' rates were set from (PERF.md section 6). Windows open
  on an unsettled heap unless ``--settle`` is given (the state ``run.py``
  measures in), and a tick over a second writes every thread's Python
  stack to stderr (``harness/heap.py``).
- ``--keep-trace FILE`` / ``--describe-trace FILE`` with ``--trace 1``: the
  reduced trace trimmed to a fixture (``tests/data``); a page of text about
  the raw profiler trace, for choosing a reader's pattern by hand.
"""
from __future__ import annotations

import collections
import gzip
import json
import os
import sys

import run as bench


def trimmed(trace, max_ops=4000, name_chars=160):
    """A small copy for a recorded fixture: the first ``max_ops`` ops of
    each line inside the window (names cut to what the patterns read), the
    window cut to them."""
    lo, hi = trace["window"]
    devices, end = {}, lo
    for name, d in trace["devices"].items():
        ops = [[e[0][:name_chars], e[1], e[2]]
               for e in d["ops"] if e[1] >= lo][:max_ops]
        end = max(end, max((e[1] + e[2] for e in ops), default=lo))
        devices[name] = {"ops": ops}
    for name, d in trace["devices"].items():
        devices[name]["modules"] = [e for e in d["modules"]
                                    if lo <= e[1] < end]
    return {"window": [lo, end],
            "spans": [s for s in trace["spans"] if lo <= s[1] < end],
            "devices": devices}


def dump(trace, path):
    """Write the plain form as JSON (gzipped if the path ends in ``.gz``)."""
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with (gzip.open if path.endswith(".gz") else open)(path, "wt") as fh:
        json.dump(trace, fh)


def describe(path, top=40):
    """A page of text about a raw xplane file, for reading one by hand:
    planes, lines, each line's heaviest event names with their stats."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            total, first = collections.Counter(), {}
            n = 0
            for e in line.events:
                n += 1
                total[e.name] += e.duration_ns
                first.setdefault(e.name, e)
            out.append(f"  LINE {line.name!r}: {n} events, "
                       f"{len(total)} names")
            for name, ns in total.most_common(top):
                e = first[name]
                stats = {k: (str(v)[:120]) for k, v in list(e.stats)[:12]}
                out.append(f"    {ns / 1e6:10.3f} ms  {name[:100]}  "
                           f"start={e.start_ns} {stats}")
    return "\n".join(out)


def overlay(base, over):
    out = dict(base)
    for k, v in over.items():
        out[k] = overlay(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def vary(args, variants):
    """One set-up, a window for each variant of the cell's traffic."""
    import numpy as np

    from drivers import serve
    from harness import heap, loadgen

    ctx = bench.make_ctx(args, args.seed)
    eng = serve.build(ctx, {})
    vocab = ctx.sizes["vocab_size"]
    plan = []
    for v in variants:
        traffic = overlay(ctx.traffic, v)
        seed = traffic.pop("seed", ctx.seed)
        drain = traffic.pop("drain", ctx.cell.get("drain", False))
        plan.append((v, drain, loadgen.schedule(traffic, seed, ctx.seconds,
                                                vocab)))
    serve._warm(eng, [a for *_, arr in plan for a in arr],
                np.random.default_rng([ctx.seed, 4]), vocab)
    rows = []
    for variant, drain, arrivals in plan:
        if args.settle:
            heap.settle()
        with heap.Pauses() as pauses:
            # the watchdog belongs to the hunt for stalls on an unsettled
            # heap: it walks the main thread's frames from another thread,
            # and a sweep of settled windows died in such a walk (PR 29)
            win = serve.measure(ctx, eng, arrivals, ctx.seconds, drain=drain,
                                stall_dump_s=None if args.settle else 1.0)
        ttft = [win.ttft_s.get(r.rid, serve.DRAIN_LIMIT_S)
                for r in win.reqs]
        gaps = win.gaps_s[:win.at_close["gaps"]]
        rows.append({
            "variant": variant, "due": len(win.reqs),
            "tokens_per_s": win.at_close["n_outputs"] / win.closed_s,
            "in_system_quarter_half_3quarter_close":
            win.in_system_at_quarters(),
            "queue_at_close": len(win.sched.queue),
            "queued_at_window_close": win.queued_at_window_close,
            "mean_output_len_offered": float(np.mean(
                [a.max_new for a in arrivals])),
            "tick_kinds": win.tick_kinds(),
            "ttft_p50_ms": float(np.median(ttft)) * 1e3,
            "ttft_mean_ms": float(np.mean(ttft)) * 1e3,
            "ttft_p95_ms": serve._p95(ttft) * 1e3,
            "itl_p50_ms": float(np.median(gaps)) * 1e3,
            "itl_p95_ms": serve._p95(gaps) * 1e3,
            "live_tokens_per_decode_step": win.at_close["decode_live_tokens"]
            / max(1, win.at_close["decode_steps"]),
            "live_slots_per_decode_step": win.live_slots(),
            "ticks": win.at_close["ticks"],
            "slowest_ticks_s_at": win.slowest_ticks_s_at(3),
            **pauses.facts()})
        bench.log(f"vary: {json.dumps(rows[-1])}")
        win.sched.drain()  # empty the system before the next variant
    return rows


def main():
    ap = bench.parser()
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--vary", default="")
    ap.add_argument("--settle", action="store_true")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--keep-ops", type=int, default=4000)
    ap.add_argument("--describe-trace", default="")
    args = ap.parse_args()

    def on_trace(trace, xplane):
        if args.describe_trace:
            with open(args.describe_trace, "w") as fh:
                fh.write(describe(xplane))
        if args.keep_trace:
            dump(trimmed(trace, args.keep_ops), args.keep_trace)

    with bench.program(args.rehearse):
        if args.vary:
            with open(args.vary) as fh:
                print(json.dumps({"vary": vary(args, json.load(fh))}))
            return 0
        for seed in [int(s) for s in args.seeds.split(",") if s] \
                or [args.seed]:
            print(bench.run_once(args, seed, control=args.control,
                                 on_trace=on_trace), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
