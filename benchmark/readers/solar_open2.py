"""Per-layer metrics of the Solar Open 2 cells: shares of the chip's peaks
with work from ``harness/work_solar_open2.py``, and what the program's tick
records counted (picked by index, as ``readers/nemotron_h.py`` picks them:
its helpers are shared). A reader that finds nothing to read (a program
without these kernels or counters) returns None."""
from harness import trace_reduce as tr, work, work_solar_open2 as W
from readers.nemotron_h import (_both, _bound, expert_load,  # noqa: F401
                                state_live, traced_counts)


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


def decode_step(out, ctx, pattern):
    """The decode steps of the window against the larger of their byte and
    FLOP bounds: every weight read once a step (of the routed experts'
    those that were hit), every slot's state read and written, the LIVE
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
    """The grouped expert products (gated first layer and down, every
    layer, decode steps and prefills) against the larger of their bounds:
    the three matrices of the experts that were hit, once a step; three
    multiply-adds a pair."""
    hit = tr.matched_s(out["trace"], pattern)
    counts, _ = traced_counts(out)
    pairs, experts = (_both(counts, "moe.pairs_on_held"),
                      _both(counts, "moe.experts_hit"))
    if hit is None or pairs is None or experts is None:
        return None
    return 100.0 * _bound(out, pairs * W.expert_pair_flops(ctx.sizes),
                          experts * W.expert_bytes(ctx.sizes)) / hit


def kda_step(out, ctx, pattern):
    """The one-step delta-rule updates of the decode steps against the
    bytes of every slot's state, read and written."""
    hit = tr.matched_s(out["trace"], pattern)
    w = out["facts"]["traced_work"]
    if hit is None or not w["decode_steps"]:
        return None
    slots = ctx.cell["engine"]["max_batch"]
    nbytes = w["decode_steps"] * W.kda_step_bytes(ctx.sizes, slots)
    return 100.0 * _bound(out, 0.0, nbytes) / hit
