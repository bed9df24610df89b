"""Per-layer metrics read from the reduced profiler trace and the
benchmark's own spans in it. A reader that finds nothing returns None."""
from harness import trace_reduce as tr


def fill(pattern, sizes):
    """``<key>`` in a pattern stands for that size of the configuration."""
    for k, v in sizes.items():
        pattern = pattern.replace(f"<{k}>", str(v))
    return pattern


def idle_share(out, ctx):
    t = out["trace"]
    return 100.0 * (1.0 - tr.busy_s(t) / tr.window_s(t))


def span_share(out, ctx, span):
    """Host time inside the named benchmark span, as a share of the window."""
    t = out["trace"]
    return 100.0 * tr.span_s(t, span) / tr.window_s(t)


def op_share(out, ctx, pattern, line="ops"):
    """Device time of the matching ops (or programs), as a share of busy."""
    t = out["trace"]
    hit = tr.matched_s(t, fill(pattern, ctx.sizes), line)
    return None if hit is None else 100.0 * hit / tr.busy_s(t)


def rarer_programs_share(out, ctx, pattern):
    """Device time of every matching program but the one that ran most
    often (in a serving window: the prefill buckets beside the decode
    step), as a share of busy."""
    t = out["trace"]
    progs = sorted(tr.programs(t, pattern).values())
    if len(progs) < 2:
        return None
    return 100.0 * sum(s for _, s in progs[:-1]) / tr.busy_s(t)


def host_ms_per_span(out, ctx, span):
    """Mean host time of the named span less the device's busy time in it."""
    host, inside, n = tr.busy_inside_s(out["trace"], span)
    return None if n == 0 else 1e3 * (host - inside) / n
