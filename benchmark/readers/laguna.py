"""Per-layer metrics of the Laguna cells: shares of the chip's peaks with
work from ``harness/work_laguna.py``, and what the program's tick records
counted (picked by index by ``readers/nemotron_h.py``'s helpers, which are
shared). A reader that finds nothing to read (a
program without these kernels or counters) returns None."""
from harness import trace_reduce as tr, work, work_laguna as W
from readers.nemotron_h import _both, _bound, traced_counts
from readers.trace import fill

PREFILL_BAND = "serve.ring_live_rows.prefill.b"


def _counts(out):
    """Sums of the traced ticks' ``counts`` and the traced window's decode
    steps (the driver's count); ``({}, 0)`` for a program that counts no
    ring rows."""
    counts, _ = traced_counts(out)
    if "serve.ring_live_rows" not in counts:
        return {}, 0
    return counts, out["facts"]["traced_work"]["decode_steps"]


def _prefill_band_keys(counts, min_bucket=0):
    """Keys the prefills' window-layer queries saw (one layer's), of the
    buckets from ``min_bucket`` up."""
    return sum(v for k, v in counts.items() if k.startswith(PREFILL_BAND)
               and int(k[len(PREFILL_BAND):]) >= min_bucket)


def mfu(out, ctx):
    """Model FLOPs of the traced window over its length and the peak; a
    window layer's scores count its band, not the causal triangle."""
    counts, _ = _counts(out)
    pairs = _both(counts, "moe.pairs_on_held")
    w = out["facts"]["traced_work"]
    if pairs is None or not w:
        return None
    peak = work.peaks(out["device_kind"])["flops_per_s"]
    band = counts["serve.ring_live_rows"] + _prefill_band_keys(counts)
    flops = W.serve_flops(ctx.sizes, w["n_positions"], w["n_keys"], band,
                          w["n_outputs"], pairs)
    return 100.0 * flops / tr.window_s(out["trace"]) / ctx.chips / peak


def decode_step(out, ctx, pattern):
    """The decode steps of the window against the larger of their byte and
    FLOP bounds: every weight read once a step (of the routed experts'
    those that were hit), the LIVE keys and values of the full layers and
    of the rings; the decoded positions' products. The decode step is the
    matching program that ran most often."""
    progs = tr.programs(out["trace"], pattern)
    w = out["facts"]["traced_work"]
    counts, decodes = _counts(out)
    if not progs or not decodes or not w["decode_steps"]:
        return None
    hit = max(progs.values())[1]
    ring = counts["serve.ring_live_rows"]
    nbytes = w["decode_steps"] * W.decode_step_fixed_bytes(ctx.sizes) \
        + counts.get("moe.experts_hit", 0) * W.expert_bytes(ctx.sizes) \
        + W.live_kv_bytes(ctx.sizes, w["decode_live_tokens"], ring)
    flops = W.serve_flops(ctx.sizes, w["decode_positions"],
                          w["decode_live_tokens"], ring,
                          w["decode_positions"],
                          counts.get("moe.pairs_on_held", 0))
    return 100.0 * _bound(out, flops, nbytes) / hit


def moe_grouped(out, ctx, pattern):
    """The grouped expert products (gated first layer and down, every
    sparse layer, decode steps and prefills) against the larger of their
    bounds: the three matrices of the experts that were hit, once a step;
    three multiply-adds a pair."""
    hit = tr.matched_s(out["trace"], pattern)
    counts, _ = _counts(out)
    pairs, experts = (_both(counts, "moe.pairs_on_held"),
                      _both(counts, "moe.experts_hit"))
    if hit is None or pairs is None or experts is None:
        return None
    return 100.0 * _bound(out, pairs * W.expert_pair_flops(ctx.sizes),
                          experts * W.expert_bytes(ctx.sizes)) / hit


def expert_load(out, ctx):
    """Rows of the busiest held expert over the mean rows of a held expert,
    summed over the sparse layers and the traced ticks' steps (1: even)."""
    counts, _ = _counts(out)
    pairs = _both(counts, "moe.pairs_on_held")
    if not pairs:
        return None
    return _both(counts, "moe.busiest_expert_rows") \
        * ctx.sizes["num_experts"] / pairs


def ring_live(out, ctx):
    """Rows of a ring that hold a live request's keys, mean over the traced
    decode steps, over the rows the engine reserves for one
    (``max_batch`` x ``sliding_window``)."""
    counts, decodes = _counts(out)
    if not decodes:
        return None
    return 100.0 * counts["serve.ring_live_rows"] / decodes \
        / (ctx.cell["engine"]["max_batch"] * ctx.sizes["sliding_window"])


def attn_decode(out, ctx, pattern):
    """The decode steps' attention in every layer (the score and the value
    products and the softmax between: the ops whose name carries a decode
    step's score tensor, ``pattern``) against the bytes of the LIVE rows,
    full and ring, at the HBM's peak: what a route that reads the live rows
    alone would approach."""
    hit = tr.matched_s(out["trace"], fill(pattern, ctx.sizes))
    counts, decodes = _counts(out)
    w = out["facts"]["traced_work"]
    if hit is None or not decodes:
        return None
    ring = counts["serve.ring_live_rows"]
    return 100.0 * _bound(
        out, W.attn_flops(ctx.sizes, w["decode_live_tokens"], ring),
        W.live_kv_bytes(ctx.sizes, w["decode_live_tokens"], ring)) / hit


def flash_band(out, ctx, pattern, min_bucket):
    """The banded flash kernel's calls (the prefills' window layers, in the
    buckets from ``min_bucket`` up, where the program takes it) against the
    larger of its bounds, with the BAND's FLOPs: a kernel that visited the
    causal triangle would read low. Bytes: q and the output once, k and v
    of the K/V heads once."""
    hit = tr.matched_s(out["trace"], pattern)
    counts, _ = _counts(out)
    keys = _prefill_band_keys(counts, min_bucket)
    if hit is None or not keys:
        return None
    sizes = ctx.sizes
    # a prompt of n > window positions has window (window + 1) / 2 + (n -
    # window) window band keys: keys / window is under its n positions
    positions = keys / sizes["sliding_window"]
    nbytes = sum(
        positions * 2 * sizes["head_dim"]
        * (2 * heads + 2 * sizes["num_key_value_heads"])
        for kind, heads, _ in W.layers(sizes) if kind == "S")
    return 100.0 * _bound(out, W.band_flops(sizes, keys), nbytes) / hit
