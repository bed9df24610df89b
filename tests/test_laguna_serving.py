"""Laguna through ``serving.GenerationEngine`` and ``Scheduler``: the cache
of two kinds the model declares (full-length rows and rings), prefill in a
padded bucket then decoding through both against the reference's one full
forward pass on LOGITS (prompts under, at and past the window, decoding that
wraps the ring twice, a slot reused after a longer request), the mask each
layer kind is handed, what the tick records and which routes were traced."""
import jax
import numpy as np
import pytest

from paddle_tpu.profiler import telemetry
from paddle_tpu.serving import GenerationEngine, Request, Scheduler

import laguna_tiny as tiny
from logit_spy import LogitSpy

MAX_LEN = 128
W = tiny.WINDOW_ROWS  # 8


@pytest.fixture(scope="module")
def served():
    cfg = tiny.tiny_config()
    model, named = tiny.build(cfg)
    return cfg, model, named


@pytest.fixture(scope="module")
def spied(served):
    """An engine whose every step tells ``seen`` the logits it computed."""
    seen = []
    return GenerationEngine(LogitSpy(served[1], seen), max_batch=3,
                            max_len=MAX_LEN), seen


@pytest.fixture(scope="module")
def engine(served):
    return GenerationEngine(served[1], max_batch=3, max_len=MAX_LEN)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, tiny.VOCAB, n).tolist()


def _served_gap(named, cfg, prompt, tokens):
    """How far each served token's logit lies under the reference's best."""
    seq = list(prompt) + list(tokens)
    lg = np.asarray(tiny.reference_logits(named, cfg, seq[:-1]))
    at = np.arange(len(prompt) - 1, len(seq) - 1)
    return float(np.max(lg[at].max(-1) - lg[at, seq[len(prompt):]]))


def _logits_through_the_cache(eng, seen, slot, seq, n):
    """Prefill ``seq[:n]`` into ``slot``, then feed ``seq[n:]`` a token a
    decode step: the logits of every position as the steps computed them."""
    del seen[:]
    eng.prefill(slot, seq[:n])
    jax.effects_barrier()
    got = [seen[-1][0, :n]]
    for t in range(n, len(seq)):
        feed = np.zeros((eng.max_batch,), np.int32)
        feed[slot] = seq[t]
        eng.decode_once(feed)
        jax.effects_barrier()
        got.append(seen[-1][slot])
    return np.concatenate(got)


# prompts of < W, = W, > W, > 2W (and past the original context of 16, into
# YaRN's scaled range); 20 decode steps wrap the ring of 8 twice
@pytest.mark.parametrize("n, slot", [(5, 0), (W, 1), (13, 2), (21, 0),
                                     (47, 1)])
def test_logits_at_every_served_position(served, spied, n, slot):
    cfg, _, named = served
    eng, seen = spied
    seq = _prompt(n, n + 20)
    want = np.asarray(tiny.reference_logits(named, cfg, seq))
    got = _logits_through_the_cache(eng, seen, slot, seq, n)
    np.testing.assert_allclose(got, want, atol=3e-4)
    eng.release_slot(slot)


def test_a_slot_reused_after_a_longer_request(served, spied):
    # the ring still holds the long request's rows past the short one's
    # length, and the full-length rows its tail: neither may be seen
    cfg, _, named = served
    eng, seen = spied
    long = _prompt(70, 60)
    _logits_through_the_cache(eng, seen, 2, long, 40)
    eng.release_slot(2)
    short = _prompt(71, 3 + 2 * W + 3)
    want = np.asarray(tiny.reference_logits(named, cfg, short))
    got = _logits_through_the_cache(eng, seen, 2, short, 3)
    np.testing.assert_allclose(got, want, atol=3e-4)
    eng.release_slot(2)


def test_each_layer_kind_is_handed_its_own_mask(served):
    # a full layer sees the step's mask, a ring layer one of its own: in
    # prefill the band over the bucket, in decode the ring's valid rows
    _, model, _ = served
    seen = []

    class Spy:
        def __init__(self):
            self.cfg, self.cache_spec = model.cfg, model.cache_spec

        def eval(self):
            model.eval()

        def __call__(self, ids, position_ids=None, attn_mask=None,
                     cache=None):
            own = [getattr(v, "mask", None) for v in cache[::2]]
            seen.append((attn_mask, own, [v.k.shape[1] for v in cache[::2]]))
            return model(ids, position_ids=position_ids,
                         attn_mask=attn_mask, cache=cache)

    eng = GenerationEngine(Spy(), max_batch=2, max_len=64)
    eng.generate(_prompt(5, 11), max_new_tokens=2)
    prefill = next(s for s in seen if s[0].kv_len is not None)
    decode = next(s for s in seen if s[0].kv_len is None)
    for step_mask, own, rows in (prefill, decode):
        assert step_mask.window is None
        assert rows == [64, W, W, W, 64]
        assert own[0] is None and own[4] is None      # the step's mask
        assert own[1] is own[2] is own[3] is not None  # one a KIND
    assert prefill[1][1].window == W and prefill[1][1].kv_len is not None
    assert decode[1][1].window is None  # a plain mask over the ring's rows


def test_generate_serves_the_references_greedy_tokens(served, engine):
    cfg, _, named = served
    prompt = _prompt(2, 19)
    tokens = engine.generate(prompt, max_new_tokens=44)
    assert len(tokens) == 44 and len(set(tokens)) > 8  # no single token
    assert _served_gap(named, cfg, prompt, tokens) < 1e-3


def test_scheduler_serves_requests_together_and_counts_rows(
        served, engine, counting):
    # three requests of different lengths decoded together (one under the
    # window, two past it), then a fourth into a slot they left
    cfg, model, named = served
    sched = Scheduler(engine)
    reqs = [sched.submit(Request(prompt=_prompt(10 + i, n),
                                 max_new_tokens=m))
            for i, (n, m) in enumerate([(5, 9), (23, 17), (30, 6)])]
    sched.run()
    later = [sched.submit(Request(prompt=_prompt(20, 3), max_new_tokens=12))]
    sched.run()
    assert {r.slot for r in later} <= {0, 1, 2}
    for r in reqs + later:
        assert len(r.tokens) == r.max_new_tokens
        assert _served_gap(named, cfg, r.prompt, r.tokens) < 1e-3
    fresh = GenerationEngine(model, max_batch=1, max_len=MAX_LEN)
    for r in later:
        assert fresh.generate(r.prompt, max_new_tokens=12) == r.tokens
    ticks = telemetry.get_telemetry().steps(kind="serve.tick",
                                            owner=sched.sched_id)
    decodes = [t.counts for t in ticks
               if t.counts and "serve.decode_live_slots" in t.counts]
    # the rows each decode step's attention read: a live slot's length (its
    # new row included) in a full-length layer, at most the window in a ring
    want_kv = want_ring = 0
    for r in reqs + later:
        for j in range(2, r.max_new_tokens + 1):   # the j-th output's step
            want_kv += len(r.prompt) + j - 1
            want_ring += min(len(r.prompt) + j - 1, W)
    assert sum(c["serve.kv_live_rows"] for c in decodes) == want_kv
    assert sum(c["serve.ring_live_rows"] for c in decodes) == want_ring
    assert all(c["serve.ring_live_rows"]
               <= W * c["serve.decode_live_slots"] for c in decodes)
    # four expert sublayers count every routed token; the dense one none
    both = lambda c, name: c.get(name, 0) + c.get(name + ".prefill", 0)
    routed = sum(both(t.counts, "moe.tokens_routed") for t in ticks
                 if t.counts)
    assert routed == 4 * sum(len(r.prompt) + len(r.tokens) - 1
                             for r in reqs + later)
    assert not engine._live.any()  # every slot released


def test_steps_count_their_routes_once_a_layer(served, counting):
    # a fresh engine traces its steps here: two full-length layers and three
    # rings a decode step, the grouped einsum and the row write in all five;
    # the prompt's bucket is too short for a route that skips blocks
    eng = GenerationEngine(served[1], max_batch=2, max_len=64)
    eng.generate(_prompt(4, 9), max_new_tokens=3)
    c = telemetry.get_telemetry().counters()
    traces = c["attn.cache_route.full"] // 2  # a step is traced > once
    assert traces >= 1 and c["attn.cache_route.full"] == 2 * traces
    assert c["attn.cache_route.ring"] == 3 * traces
    assert c["kv.row_write_route.dus"] == 5 * traces
    assert c["attn.decode_route.einsum_grouped"] == 5 * traces
    assert c["attn.prefill_band.dense"] % 3 == 0
    assert "attn.prefill_band.banded" not in c


def test_a_long_bucket_takes_the_scan_under_the_band(served, counting,
                                                     monkeypatch):
    # lower the threshold so that a tiny prompt's bucket takes the blockwise
    # scan: the window layers count as banded, and serve the same tokens
    from paddle_tpu.nn.functional import attention as A

    cfg, model, named = served
    monkeypatch.setattr(A, "BLOCKWISE_MIN_KV", 32)
    monkeypatch.setattr(A, "BLOCKWISE_BLOCK_Q", 16)
    monkeypatch.setattr(A, "BLOCKWISE_BLOCK_K", 8)
    eng = GenerationEngine(model, max_batch=1, max_len=64)
    prompt = _prompt(6, 37)
    tokens = eng.generate(prompt, max_new_tokens=5)
    assert _served_gap(named, cfg, prompt, tokens) < 1e-3
    c = telemetry.get_telemetry().counters()
    assert c["attn.prefill_band.banded"] % 3 == 0
    assert "attn.prefill_band.dense" not in c
