"""Per-layer metrics of the Nemotron-H cells: shares of the chip's peaks
with work from ``harness/work_nemotron_h.py``, and what the program's tick
records counted. Tick records are picked BY INDEX (the window's scheduler is
the owner of the newest record; its traced ticks are the last
``traced_work.ticks`` before ``work.ticks``), never by a clock. A reader
that finds nothing to read (a program without these kernels or counters)
returns None."""
from harness import trace_reduce as tr, work, work_nemotron_h as W


def traced_counts(out):
    """Sums of the traced ticks' ``counts`` (``{}`` where the program files
    none), and how many of those ticks ran a decode step."""
    try:
        from paddle_tpu.profiler import telemetry
        ticks = telemetry.get_telemetry().steps(kind="serve.tick")
    except Exception:  # noqa: BLE001 - a program without tick records
        return {}, 0
    facts = out["facts"]
    if not ticks or not facts.get("traced_work"):
        return {}, 0
    hi = facts["work"]["ticks"]
    lo = hi - facts["traced_work"]["ticks"]
    total, decodes = {}, 0
    for t in ticks:
        counts = getattr(t, "counts", None)
        if t.owner != ticks[-1].owner or not lo <= t.index < hi \
                or not counts:
            continue
        decodes += "serve.state_live_slots" in counts
        for k, v in counts.items():
            total[k] = total.get(k, 0) + v
    return total, decodes


def _both(counts, name):
    """A count over decode steps and prefills together (None: not filed)."""
    if name not in counts and name + ".prefill" not in counts:
        return None
    return counts.get(name, 0) + counts.get(name + ".prefill", 0)


def mfu(out, ctx):
    """Model FLOPs of the traced window over its length and the peak."""
    pairs = _both(traced_counts(out)[0], "moe.pairs_on_held")
    w = out["facts"]["traced_work"]
    if pairs is None or not w:
        return None
    peak = work.peaks(out["device_kind"])["flops_per_s"]
    flops = W.serve_flops(ctx.sizes, w["n_positions"], w["n_keys"],
                          w["n_outputs"], pairs)
    return 100.0 * flops / tr.window_s(out["trace"]) / ctx.chips / peak


def _bound(out, flops, nbytes):
    pk = work.peaks(out["device_kind"])
    return max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])


def decode_step(out, ctx, pattern):
    """The decode steps of the window against the larger of their byte and
    FLOP bounds: every weight read once a step (of the routed experts'
    those that were hit), every slot's state read and written, the live
    keys and values; the decoded positions' products. The decode step is
    the matching program that ran most often."""
    progs = tr.programs(out["trace"], pattern)
    w = out["facts"]["traced_work"]
    counts, decodes = traced_counts(out)
    if not progs or not w["decode_steps"] or not decodes:
        return None
    hit = max(progs.values())[1]
    slots = ctx.cell["engine"]["max_batch"]
    nbytes = w["decode_steps"] * W.decode_step_fixed_bytes(ctx.sizes, slots) \
        + counts.get("moe.experts_hit", 0) * W.expert_bytes(ctx.sizes) \
        + w["decode_live_tokens"] * W.kv_bytes_per_token(ctx.sizes)
    flops = W.serve_flops(ctx.sizes, w["decode_positions"],
                          w["decode_live_tokens"], w["decode_positions"],
                          counts.get("moe.pairs_on_held", 0))
    return 100.0 * _bound(out, flops, nbytes) / hit


def moe_grouped(out, ctx, pattern):
    """The grouped expert products (up and down, every expert block, decode
    steps and prefills) against the larger of their bounds: the matrices of
    the experts that were hit, once a step; two multiply-adds a pair."""
    hit = tr.matched_s(out["trace"], pattern)
    counts, _ = traced_counts(out)
    pairs, experts = (_both(counts, "moe.pairs_on_held"),
                      _both(counts, "moe.experts_hit"))
    if hit is None or pairs is None or experts is None:
        return None
    return 100.0 * _bound(out, pairs * W.expert_pair_flops(ctx.sizes),
                          experts * W.expert_bytes(ctx.sizes)) / hit


def ssm_step(out, ctx, pattern):
    """The one-step state updates of the decode steps against the bytes of
    every slot's state, read and written."""
    hit = tr.matched_s(out["trace"], pattern)
    w = out["facts"]["traced_work"]
    if hit is None or not w["decode_steps"]:
        return None
    slots = ctx.cell["engine"]["max_batch"]
    nbytes = w["decode_steps"] * W.ssm_step_bytes(ctx.sizes, slots)
    return 100.0 * _bound(out, 0.0, nbytes) / hit


def expert_load(out, ctx):
    """Rows of the busiest held expert over the mean rows of a held expert,
    summed over the expert blocks and the traced ticks' steps (1: even)."""
    counts, _ = traced_counts(out)
    pairs = _both(counts, "moe.pairs_on_held")
    if not pairs:
        return None
    return _both(counts, "moe.busiest_expert_rows") * ctx.sizes[
        "n_routed_experts"] / pairs


def state_live(out, ctx):
    """Slots whose recurrent state belongs to a request, mean over the
    traced decode steps, over the slots the engine holds state for."""
    counts, decodes = traced_counts(out)
    if not decodes:
        return None
    return 100.0 * counts["serve.state_live_slots"] / decodes \
        / ctx.cell["engine"]["max_batch"]
