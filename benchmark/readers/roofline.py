"""Shares of the chip's peaks: work from ``harness/work.py`` (shapes only)
over time from the trace and peaks from ``harness/peaks.json``."""
from harness import trace_reduce as tr, work
from readers.trace import fill


def mfu(out, ctx, kind):
    """Model FLOPs of the traced window over its length and the peak."""
    peak = work.peaks(out["device_kind"])["flops_per_s"]
    if kind == "train":
        f = out["facts"]
        flops = f["tokens"] * work.train_flops_per_token(ctx.sizes, f["seq"])
    else:
        w = out["facts"]["traced_work"]
        flops = work.serve_flops(ctx.sizes, w["n_positions"], w["n_keys"],
                                 w["n_outputs"])
    return 100.0 * flops / tr.window_s(out["trace"]) / ctx.chips / peak


def flash_train(out, ctx, pattern):
    """Packed flash attention, forward and backward, against the larger of
    its FLOP and byte bounds."""
    hit = tr.matched_s(out["trace"], fill(pattern, ctx.sizes))
    if hit is None:
        return None
    pk = work.peaks(out["device_kind"])
    f = out["facts"]
    flops, nbytes = work.flash_train_work(
        ctx.sizes, f["batch"] // ctx.chips, f["seq"])
    bound = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * f["steps"] * bound / hit


def decode_step(out, ctx, pattern):
    """The decode steps of the window against the larger of their byte and
    FLOP bounds: every weight read once a step plus the live keys and
    values of the active slots; the decoded positions' products. Every
    ``CompiledStep`` is jitted under one function name today, so the decode
    step is told from the prefill buckets as the matching program that ran
    most often."""
    progs = tr.programs(out["trace"], pattern)
    w = out["facts"]["traced_work"]
    if not progs or not w["decode_steps"]:
        return None
    hit = max(progs.values())[1]
    pk = work.peaks(out["device_kind"])
    nbytes = w["decode_steps"] * work.decode_step_bytes(ctx.sizes, 0) \
        + w["decode_live_tokens"] * work.kv_bytes_per_token(ctx.sizes)
    flops = work.serve_flops(ctx.sizes, w["decode_positions"],
                             w["decode_live_tokens"], w["decode_positions"])
    bound = max(nbytes / pk["hbm_bytes_per_s"], flops / pk["flops_per_s"])
    return 100.0 * bound / hit
