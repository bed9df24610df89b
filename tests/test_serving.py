"""Inference serving tier (ISSUE 6): static-shape KV-cache decode +
continuous batching.

Contracts under test:
  * cached-vs-uncached greedy parity — 32 tokens of greedy decode through
    the static KV cache produce the SAME token ids as the uncached full
    forward, for bucket-boundary and mid-bucket prompt lengths;
  * O(1) decode — telemetry compile counters over a 64+-token generation:
    decode compiles EXACTLY once, prefill once per length bucket;
  * static lint — the decode step at two consecutive positions carries
    zero shape-churn/kv-cache findings, while the legacy grow-by-concat
    gpt cache path is flagged by the `kv-cache-concat` rule;
  * continuous batching — admit/evict determinism under a seeded arrival
    stream, per-request output parity with single-request generate, and
    dense-batch occupancy accounting.
"""
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models import GPTConfig, GPTDecoderLayer, GPTForCausalLM
from paddle_tpu.profiler import telemetry
from paddle_tpu.serving import (
    GenerationEngine,
    KVCache,
    Request,
    Scheduler,
    default_buckets,
    pick_bucket,
)
from paddle_tpu.utils import unique_name


@pytest.fixture
def _no_persistent_compile_cache():
    """Parity tests compare a cached-decode executable against a fresh
    eager path: executables round-tripped through the persistent XLA:CPU
    compile cache are not bit-identical to in-process compiles on this
    stack (see tests/test_fault_tolerance.py and the conftest warm-cache
    hazard note — the eager BERT path comes back corrupted on a warm
    cache), so these tests compile everything in-process."""
    import jax

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _gpt_cfg(max_pos=128):
    return GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                     num_heads=2, max_position_embeddings=max_pos,
                     hidden_dropout=0.0, attention_dropout=0.0)


def _gpt(seed=0, max_pos=128):
    with unique_name.guard():
        paddle.seed(seed)
        model = GPTForCausalLM(_gpt_cfg(max_pos))
    model.eval()
    return model


def _greedy_uncached(model, prompt, got):
    """The uncached reference for a cached run that produced ``got``, from
    ONE full forward over ``prompt + got[:-1]``. The model is causal: the
    row at position ``len(prompt) - 1 + j`` of that forward is the row a
    forward over the first ``len(prompt) + j`` ids alone would end on, so
    its argmax is greedy token ``j`` as long as the tokens before ``j``
    matched, and the first token that does not match fails the comparison.
    One eager shape to compile per op, where a forward over each growing
    prefix compiles one per generated token."""
    ids = list(prompt) + list(got[:-1])
    logits = model(Tensor(np.asarray(ids, np.int64)[None, :]))
    return np.asarray(logits._value)[0, len(prompt) - 1:].argmax(-1).tolist()


# ---------------------------------------------------------------------------
# bucketing + cache plumbing
# ---------------------------------------------------------------------------
def test_bucket_helpers():
    assert default_buckets(64) == (16, 32, 64)
    assert default_buckets(100) == (16, 32, 64, 100)
    assert pick_bucket(1, (8, 16)) == 8
    assert pick_bucket(8, (8, 16)) == 8   # boundary stays in its bucket
    assert pick_bucket(9, (8, 16)) == 16
    with pytest.raises(ValueError, match="largest prefill bucket"):
        pick_bucket(17, (8, 16))


def test_kv_cache_alloc_layout():
    c = KVCache.alloc(num_layers=3, batch=2, max_len=16, num_heads=4,
                      head_dim=8)
    assert c.num_layers == 3 and c.batch == 2 and c.max_len == 16
    assert c.num_heads == 4 and c.head_dim == 8
    assert c.ks[0].shape == (2, 16, 4, 8)
    assert c.lengths.dtype.name == "int32"
    # 3 layers x (K+V) x 2*16*4*8 floats
    assert c.nbytes() == 3 * 2 * 2 * 16 * 4 * 8 * 4
    # a registered pytree: flattens/unflattens through jax
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(c)
    assert len(leaves) == 3 * 2 + 1
    assert isinstance(jax.tree_util.tree_unflatten(treedef, leaves), KVCache)


# ---------------------------------------------------------------------------
# cached-vs-uncached greedy parity (the correctness tentpole)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prompt_len", [5, 8])  # mid-bucket / boundary
def test_cached_greedy_parity_32_tokens(prompt_len,
                                        _no_persistent_compile_cache):
    model = _gpt()
    prompt = np.random.RandomState(7).randint(0, 97, prompt_len).tolist()
    eng = GenerationEngine(model, max_batch=2, max_len=64,
                           prefill_buckets=(8, 16))
    got = eng.generate(prompt, max_new_tokens=32)
    assert len(got) == 32
    assert got == _greedy_uncached(model, prompt, got)


def test_generate_convenience_on_model_caches_engine(
        _no_persistent_compile_cache):
    model = _gpt()
    prompt = [3, 1, 4, 1, 5]
    got = model.generate(prompt, max_new_tokens=8, max_len=64,
                         prefill_buckets=(8,))
    assert len(got) == 8
    assert got == _greedy_uncached(model, prompt, got)
    eng = model._serve_engine
    # second call reuses the cached engine (and its compiled executables)
    model.generate(prompt, max_new_tokens=4, max_len=64,
                   prefill_buckets=(8,))
    assert model._serve_engine is eng


def test_generate_stops_at_eos():
    model = _gpt()
    eng = GenerationEngine(model, max_batch=1, max_len=64,
                           prefill_buckets=(8,))
    free_run = eng.generate([1, 2, 3], max_new_tokens=8)
    eos = free_run[1]
    out = eng.generate([1, 2, 3], max_new_tokens=8, eos_id=eos)
    # greedy is deterministic: stops right after the FIRST eos emission
    assert out == free_run[:free_run.index(eos) + 1]
    assert out[-1] == eos and len(out) < 8


# ---------------------------------------------------------------------------
# O(1) decode: compile counters + static lint
# ---------------------------------------------------------------------------
def test_decode_compiles_once_over_64_tokens():
    model = _gpt()
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=128,
                               prefill_buckets=(8, 16))
        out = eng.generate([5, 6, 7], max_new_tokens=65)
        counts = telemetry.get_telemetry().compile_counts()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert len(out) == 65
    assert counts.get("serve_decode") == 1, counts  # 64 steps, ONE compile
    assert counts.get("serve_prefill") == 1, counts  # one bucket touched


def test_prefill_compiles_once_per_bucket():
    model = _gpt()
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8, 16))
        eng.generate([1] * 5, max_new_tokens=3)    # bucket 8
        eng.generate([1] * 12, max_new_tokens=3)   # bucket 16
        eng.generate([1] * 7, max_new_tokens=3)    # bucket 8 again: cached
        counts = telemetry.get_telemetry().compile_counts()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert counts.get("serve_prefill") == 2, counts
    assert counts.get("serve_decode") == 1, counts


def test_decode_lint_clean_at_consecutive_positions():
    model = _gpt()
    eng = GenerationEngine(model, max_batch=2, max_len=32,
                           prefill_buckets=(8,))
    a1 = eng.example_decode_args([5, 3])
    a2 = eng.example_decode_args([6, 4])
    report = analysis.lint_step(eng.decode_step, *a1, extra_args=[a2])
    churn = [f for f in report
             if f.rule in ("retrace-shape-churn", "kv-cache-concat")]
    assert not churn, report.table()
    assert not report.errors, report.table()


def test_kv_cache_concat_rule_flags_legacy_gpt_cache():
    """Regression fixture: the pre-fix grow-by-concat tuple cache — the
    cache operands change shape between consecutive positions and come
    back one step larger, which is exactly the `kv-cache-concat`
    signature. The rule must name the cache paths and point at
    serving.KVCache."""
    cfg = _gpt_cfg(max_pos=32)
    with unique_name.guard():
        paddle.seed(0)
        layer = GPTDecoderLayer(cfg)
    layer.eval()

    def legacy_decode(x, k, v):
        out, cache = layer(x, cache=(k, v))
        return out, cache[0], cache[1]

    x = np.random.RandomState(0).randn(1, 1, cfg.hidden_size)
    x = x.astype(np.float32)

    def kv(t):
        shape = (1, t, cfg.num_heads, cfg.hidden_size // cfg.num_heads)
        return (np.zeros(shape, np.float32), np.zeros(shape, np.float32))

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        report = analysis.lint_step(legacy_decode, x, *kv(5),
                                    extra_args=[(x,) + kv(6)])
    findings = [f for f in report if f.rule == "kv-cache-concat"]
    assert {f.path for f in findings} == {"args[1]", "args[2]"}
    assert all(f.severity == "error" for f in findings)
    assert "serving.KVCache" in findings[0].hint
    # a shape-stable signature stays silent (no variants disagree)
    clean = analysis.lint_step(legacy_decode, x, *kv(5),
                               extra_args=[(x,) + kv(5)])
    assert not [f for f in clean if f.rule == "kv-cache-concat"]


def test_tuple_cache_shim_still_works_and_warns_once():
    from paddle_tpu.utils import _WARNED_ONCE

    cfg = _gpt_cfg(max_pos=32)
    with unique_name.guard():
        paddle.seed(0)
        layer = GPTDecoderLayer(cfg)
    layer.eval()
    _WARNED_ONCE.discard("gpt-kv-cache-concat")
    hd = cfg.hidden_size // cfg.num_heads
    k0 = Tensor(np.zeros((1, 3, cfg.num_heads, hd), np.float32))
    v0 = Tensor(np.zeros((1, 3, cfg.num_heads, hd), np.float32))
    x = Tensor(np.random.RandomState(0).randn(1, 1, cfg.hidden_size)
               .astype(np.float32))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out, cache = layer(x, cache=(k0, v0))
        out2, cache2 = layer(x, cache=cache)
    msgs = [str(x.message) for x in w]
    assert sum("deprecated" in m for m in msgs) == 1  # warns ONCE
    assert tuple(cache[0].shape) == (1, 4, cfg.num_heads, hd)   # grew...
    assert tuple(cache2[0].shape) == (1, 5, cfg.num_heads, hd)  # ...again
    assert tuple(out2.shape) == (1, 1, cfg.hidden_size)


# ---------------------------------------------------------------------------
# continuous batching
# ---------------------------------------------------------------------------
def _request_stream(seed, n, vocab=97):
    rng = np.random.RandomState(seed)
    return [Request(prompt=rng.randint(0, vocab,
                                       int(rng.randint(3, 14))).tolist(),
                    max_new_tokens=int(rng.randint(4, 12)), rid=i)
            for i in range(n)]


def _run_stream(seed):
    model = _gpt(seed=3, max_pos=64)
    eng = GenerationEngine(model, max_batch=4, max_len=64,
                           prefill_buckets=(8, 16))
    sched = Scheduler(eng)
    for req in _request_stream(seed, 9):
        sched.submit(req)
    finished = sched.run()
    return sched, {r.rid: list(r.tokens) for r in finished}


def test_scheduler_admit_evict_deterministic():
    s1, out1 = _run_stream(11)
    s2, out2 = _run_stream(11)
    assert s1.events == s2.events  # identical admit/evict log
    assert out1 == out2
    assert len(out1) == 9
    # slots were actually recycled: more admits than batch slots
    admits = [e for e in s1.events if e[1] == "admit"]
    assert len(admits) == 9 > s1.engine.max_batch
    assert 0.0 < s1.occupancy() <= 1.0


def test_scheduler_matches_single_request_generate(
        _no_persistent_compile_cache):
    """Continuous batching with slot churn produces the SAME tokens per
    request as serving each request alone — cross-slot isolation."""
    model = _gpt(seed=3, max_pos=64)
    eng = GenerationEngine(model, max_batch=3, max_len=64,
                           prefill_buckets=(8, 16))
    sched = Scheduler(eng)
    reqs = _request_stream(5, 7)
    for r in reqs:
        sched.submit(r)
    sched.run()
    solo = GenerationEngine(model, max_batch=1, max_len=64,
                            prefill_buckets=(8, 16))
    for r in reqs:
        want = solo.generate(r.prompt, max_new_tokens=r.max_new_tokens)
        assert r.tokens == want, f"request {r.rid} diverged"
        assert r.finish_reason == "length"
        assert r.ttft_s is not None and r.latency_s is not None


def test_dead_slots_are_masked_out_and_cost_a_live_request_nothing(
        _no_persistent_compile_cache):
    """A request served beside a freed slot and two never-used ones gives
    the tokens it gives alone, before and after its neighbours have sat
    through more than 2 x max_len decode steps without a request. In every
    decode step the attention mask says "no valid key" (``q_pos`` −1) for
    exactly the slots that hold no request, and such a slot's length stays
    where its request left it."""
    import jax
    from mask_spy import MaskSpy

    model = _gpt(seed=4, max_pos=32)
    seen = []
    eng = GenerationEngine(MaskSpy(model, seen), max_batch=4, max_len=32,
                           prefill_buckets=(8,))
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, 97, size=6).tolist()

    def serve(slot, p, new):
        """Serve ``p`` in ``slot`` through the engine's own steps; the mask
        of every decode step is held against the live slots."""
        out = [eng.prefill(slot, p)]
        feed = np.zeros((4,), np.int32)
        for _ in range(new - 1):
            feed[slot] = out[-1]
            live, before, n = eng._live.copy(), eng.lengths(), len(seen)
            out.append(int(eng.decode_once(feed)[slot]))
            jax.effects_barrier()
            (q_pos,) = seen[n:]
            assert ((q_pos[:, 0] == -1) == ~live).all(), (q_pos, live)
            assert (q_pos[live, 0] == before[live]).all()
            assert (eng.lengths() == before + live).all()
        return out

    serve(1, rng.randint(0, 97, size=5).tolist(), 4)
    eng.release_slot(1)               # slot 1 freed; 2 and 3 never used
    first = serve(0, prompt, 12)
    assert first == _greedy_uncached(model, prompt, first)
    eng.release_slot(0)
    idle = 0
    while idle <= 2 * eng.max_len:    # other requests come and go in slot 0
        serve(0, rng.randint(0, 97, size=7).tolist(), 20)
        eng.release_slot(0)
        idle += 19
    assert eng.lengths()[1:].tolist() == [5 + 3, 0, 0]
    assert serve(0, prompt, 12) == first


def test_decode_kernel_serves_a_request_among_dead_slots_as_alone(
        _no_persistent_compile_cache, monkeypatch):
    """The same through the decode attention KERNEL (interpreted; heads of
    64 over a 256-slot cache, the threshold of the long-cache routes moved
    down to it): a request in slot 2, between a never-used slot, a freed
    one and another never-used one, is served token for token as an engine
    of one slot serves it."""
    from paddle_tpu.nn.functional import attention
    from paddle_tpu.ops import pallas

    monkeypatch.setattr(attention, "BLOCKWISE_MIN_KV", 256)

    with unique_name.guard():
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=2,
            max_position_embeddings=256, hidden_dropout=0.0,
            attention_dropout=0.0, initializer_range=0.6))
    model.eval()
    rng = np.random.RandomState(5)
    prompt = rng.randint(0, 512, size=9).tolist()
    telemetry.reset()
    telemetry.enable()
    try:
        with pallas.interpret_mode():
            eng = GenerationEngine(model, max_batch=4, max_len=256,
                                   prefill_buckets=(16,))
            feed = np.zeros((4,), np.int32)
            feed[1] = eng.prefill(1, rng.randint(0, 512, size=5).tolist())
            for _ in range(3):
                feed[1] = eng.decode_once(feed)[1]
            eng.release_slot(1)
            got = [eng.prefill(2, prompt)]
            for _ in range(15):
                feed[:] = 0
                feed[2] = got[-1]
                got.append(int(eng.decode_once(feed)[2]))
            alone = GenerationEngine(model, max_batch=1, max_len=256,
                                     prefill_buckets=(16,)).generate(
                                         prompt, max_new_tokens=16)
        routes = telemetry.get_telemetry().counters()
    finally:
        telemetry.disable()
        telemetry.reset()
    assert routes.get("attn.decode_route.flash_decode", 0) > 0, routes
    assert len(set(got)) > 2, "degenerate model; parity is vacuous"
    assert got == alone
    assert eng.lengths().tolist() == [0, 5 + 3, 9 + 15, 0]


def test_tick_record_counts_the_slots_the_decode_step_attended():
    """``serve.decode_live_slots`` in a tick's record: of ``max_batch``
    slots, those that held a request in the tick's decode step (the others
    the attention neither fetched nor visited)."""
    model = _gpt(max_pos=64)
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=3, max_len=64,
                               prefill_buckets=(8, 16))
        sched = Scheduler(eng)
        sched.submit(Request(prompt=[5, 6, 7], max_new_tokens=6))
        sched.submit(Request(prompt=[8, 9], max_new_tokens=3))
        live = []
        while sched.queue or sched.active:
            sched.step()
            live.append(len(sched.active))
        ticks = telemetry.get_telemetry().steps(kind="serve.tick",
                                                owner=sched.sched_id)
    finally:
        telemetry.disable()
        telemetry.reset()
    counted = [t.counts.get("serve.decode_live_slots") for t in ticks]
    assert len(counted) == len(live) and None not in counted
    # a tick's decode step runs before the tick evicts what finished in it
    assert max(counted) == 2 and min(counted) == 1
    assert all(c >= n for c, n in zip(counted, live))
    assert "serve.state_live_slots" not in ticks[0].counts  # no state here


def test_scheduler_rejects_oversized_requests():
    model = _gpt(max_pos=64)
    eng = GenerationEngine(model, max_batch=2, max_len=32,
                           prefill_buckets=(8, 16))
    sched = Scheduler(eng)
    with pytest.raises(ValueError, match="prefill bucket"):
        sched.submit(Request(prompt=[1] * 20, max_new_tokens=4))
    with pytest.raises(ValueError, match="cache capacity"):
        sched.submit(Request(prompt=[1] * 10, max_new_tokens=30))
    with pytest.raises(ValueError, match="empty prompt"):
        sched.submit(Request(prompt=[], max_new_tokens=4))


def test_scheduler_publishes_telemetry():
    model = _gpt(max_pos=64)
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8, 16))
        sched = Scheduler(eng)
        for r in _request_stream(2, 4):
            r.max_new_tokens = 4
            sched.submit(r)
        sched.run()
        tm = telemetry.get_telemetry()
        counters, gauges = tm.counters(), tm.gauges()
        ttft = tm.get("serve.ttft_s")
        latency = tm.get("serve.latency_s")
    finally:
        telemetry.disable()
        telemetry.reset()
    assert counters["serve.admitted"] == 4
    assert counters["serve.evicted"] == 4
    assert counters["serve.tokens_generated"] == 16
    assert counters["serve.decode_steps"] == sched.decode_steps
    # a fully-drained run() RETIRES the lifecycle gauges (stale-gauge
    # fix) — counters/histograms survive
    assert "serve.requests_in_flight" not in gauges
    assert "serve.queue_depth" not in gauges
    assert ttft.get("count") == 4
    assert latency.get("count") == 4


def test_tick_record_holds_the_named_phases():
    """Every Scheduler.step() leaves one tick record in telemetry's ring:
    the scheduler's own tick index and id, ``serve.tick`` around the named
    phases, each nested inside it, their sum no longer than it."""
    model = _gpt(max_pos=64)
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8, 16))
        other = Scheduler(eng)          # an earlier scheduler: another id
        other.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
        other.run()
        sched = Scheduler(eng)
        assert sched.sched_id > other.sched_id
        for r in _request_stream(2, 4):
            r.max_new_tokens = 4
            sched.submit(r)
        steps = 0
        while sched.queue or sched.active:
            sched.step()
            steps += 1
        tm = telemetry.get_telemetry()
        ticks = tm.steps(kind="serve.tick", owner=sched.sched_id)
        others = tm.steps(kind="serve.tick", owner=other.sched_id)
        waits = tm.get("phase.serve.queue_wait")
    finally:
        telemetry.disable()
        telemetry.reset()
    assert [t.index for t in ticks] == list(range(steps))
    assert [t.index for t in others] == list(range(len(others))) != []
    top = set(telemetry.SERVE_PHASES)
    admits = 0
    for t in ticks:
        (tick,) = [sp for sp in t.spans if sp[0] == "serve.tick"]
        inside = [sp for sp in t.spans
                  if sp[0] not in ("serve.tick", "serve.queue_wait")]
        # every phase lies inside the tick ...
        assert all(tick[1] <= a <= b <= tick[2] for _, a, b in inside)
        # ... the top-level ones are disjoint and in tick order ...
        tops = [sp for sp in inside if sp[0] in top]
        assert all(x[2] <= y[1] for x, y in zip(tops, tops[1:]))
        assert sum(t.phases[n] for n in top if n in t.phases) \
            <= t.phases["serve.tick"]
        # ... and the tick ran the plain decode path's phases
        assert [n for n, _, _ in tops if n != "serve.admit"] == [
            "serve.expire", "serve.decode_feed", "serve.decode_dispatch",
            "serve.decode_readback", "serve.bookkeep"]
        if "serve.admit" in t.phases:
            (admit,) = [sp for sp in tops if sp[0] == "serve.admit"]
            kids = [sp for sp in inside if sp[0].startswith("serve.prefill_")]
            n = sum(1 for sp in t.spans if sp[0] == "serve.queue_wait")
            admits += n
            assert [k[0] for k in kids] == [
                "serve.prefill_dispatch", "serve.prefill_readback"] * n
            assert all(admit[1] <= a <= b <= admit[2] for _, a, b in kids)
        # the compiled step's own dispatch (or compile) nests in the phase
        (disp,) = [sp for sp in tops if sp[0] == "serve.decode_dispatch"]
        inner = [sp for sp in inside if sp[0] in ("dispatch", "compile")
                 and disp[1] <= sp[1] and sp[2] <= disp[2]]
        assert len(inner) == 1
    # one queue wait per admitted request, in the tick that admitted it
    assert admits == 4 == waits["count"] - 1  # the other scheduler's one


def test_ttft_and_queue_wait_count_from_due():
    """A load generator sets ``due_ns``; a late submit then counts as
    waiting. Without it a request is due when it is submitted."""
    import time

    model = _gpt(max_pos=64)
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8,))
        sched = Scheduler(eng)
        late = Request(prompt=[1, 2, 3], max_new_tokens=2,
                       due_ns=time.perf_counter_ns() - 250_000_000)
        plain = Request(prompt=[1, 2, 3], max_new_tokens=2)
        sched.submit(late)
        sched.submit(plain)
        sched.run()
        tm = telemetry.get_telemetry()
        (tick,) = [t for t in tm.steps(kind="serve.tick",
                                       owner=sched.sched_id)
                   if "serve.queue_wait" in t.phases]
        ttft = tm.get("serve.ttft_s")
    finally:
        telemetry.disable()
        telemetry.reset()
    assert plain.due_ns == plain.submit_ns
    assert late.due_ns < late.submit_ns
    assert late.ttft_s == pytest.approx(
        (late.first_token_ns - late.due_ns) / 1e9)
    assert late.ttft_s >= 0.25
    # admitted in FIFO order: the late request's wait is the first filed
    w_late, w_plain = [(b - a) / 1e9 for n, a, b in tick.spans
                       if n == "serve.queue_wait"]
    assert 0.25 <= w_late <= late.ttft_s
    assert w_plain <= plain.ttft_s
    assert plain.ttft_s == pytest.approx(
        (plain.first_token_ns - plain.submit_ns) / 1e9)
    assert ttft["sum"] == pytest.approx(late.ttft_s + plain.ttft_s)


@pytest.mark.parametrize("which", ["decode", "prefill", "verify", "chunk"])
def test_engine_steps_lower_under_their_step_names(which):
    """Every serving program has a name a trace reader can hold:
    ``jit_serve_decode`` and not the one closure name all steps shared."""
    eng = GenerationEngine(_gpt(max_pos=64), max_batch=2, max_len=64,
                           prefill_buckets=(8,), spec_k=2, prefill_chunk=8)
    step, args = {
        "decode": (eng.decode_step, eng.example_decode_args([1])),
        "prefill": (eng.prefill_step,
                    (np.zeros((1, 8), np.int32), np.int32(1), np.int32(0),
                     eng._example_cache([0]))),
        "verify": (eng.verify_step, eng.example_verify_args([1])),
        "chunk": (eng.chunk_step, eng.example_chunk_args([0])),
    }[which]
    name = {"chunk": "serve_prefill_chunk"}.get(which, f"serve_{which}")
    assert step.name == name
    assert f"module @jit_{name}" in step.lower(*args).as_text()


def test_speculative_tick_files_draft_verify_accept_phases():
    model = _gpt(max_pos=64)
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8,), spec_k=2)
        sched = Scheduler(eng)
        sched.submit(Request(prompt=[5, 6, 5, 6, 5, 6], max_new_tokens=8))
        sched.run()
        tm = telemetry.get_telemetry()
        ticks = tm.steps(kind="serve.tick", owner=sched.sched_id)
        spec_ticks = tm.counters().get("serve.spec_ticks", 0)
    finally:
        telemetry.disable()
        telemetry.reset()
    spec = [t for t in ticks if "serve.verify_dispatch" in t.phases]
    assert len(spec) == spec_ticks >= 1
    for t in spec:
        names = [n for n, _, _ in t.spans if n in telemetry.SERVE_PHASES]
        assert names[-5:] == ["serve.draft", "serve.verify_dispatch",
                              "serve.verify_readback", "serve.accept",
                              "serve.bookkeep"]
        assert sum(t.phases[n] for n in set(names)) <= t.phases["serve.tick"]


def test_scheduler_gauges_retired_on_drain_and_shutdown():
    """Regression (ISSUE 8 satellite, mirrors the PR 5 DeviceLoader fix):
    a drained or shut-down scheduler must not leave stale
    serve.requests_in_flight / serve.queue_depth gauges behind."""
    model = _gpt(max_pos=64)
    telemetry.reset()
    telemetry.enable()
    try:
        eng = GenerationEngine(model, max_batch=2, max_len=64,
                               prefill_buckets=(8, 16))
        sched = Scheduler(eng)
        for r in _request_stream(3, 3):
            r.max_new_tokens = 3
            sched.submit(r)
        tm = telemetry.get_telemetry()
        assert tm.gauges()["serve.queue_depth"] == 3.0
        # mid-serve (NOT drained): gauges live
        sched.step()
        g = tm.gauges()
        assert g["serve.requests_in_flight"] == 2.0
        assert g["serve.queue_depth"] == 1.0
        # partial run that stops before the drain keeps them live too
        sched.run(max_steps=1)
        assert "serve.requests_in_flight" in tm.gauges()
        # full drain retires them
        sched.run()
        g = tm.gauges()
        assert "serve.requests_in_flight" not in g
        assert "serve.queue_depth" not in g
        # and republishing works: new traffic brings them back...
        for r in _request_stream(5, 1):
            r.max_new_tokens = 2
            sched.submit(r)
        sched.step()
        assert "serve.requests_in_flight" in tm.gauges()
        # ...until an explicit shutdown retires them again, mid-flight
        sched.shutdown()
        g = tm.gauges()
        assert "serve.requests_in_flight" not in g
        assert "serve.queue_depth" not in g
        # shutdown is idempotent and only touches the lifecycle gauges
        tm.set_gauge("serve.tokens_per_s", 42.0)
        sched.shutdown()
        assert tm.gauges()["serve.tokens_per_s"] == 42.0
    finally:
        telemetry.disable()
        telemetry.reset()
