"""Nemotron-H through ``serving.GenerationEngine``: the cache the model
declares (K/V beside recurrent state beside nothing), prefill in a padded
bucket then decoding through it, slots decoded together and reused, what
the tick records, what the engine refuses; and GPT-2 through the same
declared cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.nn.functional import LengthMask
from paddle_tpu.profiler import telemetry
from paddle_tpu.serving import (GenerationEngine, RecurrentStateError,
                                Request, Scheduler)
from paddle_tpu.serving.kv_cache import (CountsView, DecodeView, KVCache,
                                         PrefillView, StateDecodeView,
                                         StatePrefillView)

import nemotron_h_tiny as tiny

MAX_LEN = 128


@pytest.fixture(scope="module")
def served():
    cfg = tiny.tiny_config()
    model, named = tiny.build(cfg)
    return cfg, model, named


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


def test_cache_is_what_the_model_declares(served, engine):
    kinds = [layer["kind"] for layer in served[1].cache_spec()]
    assert kinds == ["state", "counts", "state", "kv", "counts"]
    cache = engine.cache
    assert [k is not None for k in cache.ks] == [k == "kv" for k in kinds]
    assert [s is not None for s in cache.states] \
        == [k == "state" for k in kinds]
    assert cache.ks[3].shape == (3, MAX_LEN, 2, 16)  # K/V heads, not query
    assert cache.states[0]["conv"].shape == (3, 3, 64 + 2 * 2 * 16)
    assert cache.states[0]["ssm"].shape == (3, 8, 8, 16)
    assert cache.states[0]["ssm"].dtype == jnp.float32
    assert engine.has_state and len(engine.count_names) == 4


def test_logits_at_every_served_position(served):
    # the engine's views driven by hand: prefill in a padded bucket, then 40
    # decode steps through K/V and state, the logits of every position
    # against the reference's one full forward pass
    cfg, model, named = served
    spec = model.cache_spec()
    prompt, steps, bucket = _prompt(0, 21), 40, 32
    seq = prompt + _prompt(1, steps)
    want = np.asarray(tiny.reference_logits(named, cfg, seq))
    cache = KVCache.from_spec(spec, 2, MAX_LEN)

    def views(kv, state, valid):
        return [kv(k, v) if k is not None else state(st) if st is not None
                else CountsView(valid) for k, v, st in zip(
                    cache.ks, cache.vs, cache.states)]

    def collect(vs, lengths):
        return KVCache([getattr(v, "k", None) for v in vs],
                       [getattr(v, "v", None) for v in vs], lengths,
                       [getattr(v, "arrays", None) for v in vs])

    @jax.jit
    def prefill(cache_in, toks):
        nonlocal cache
        cache = cache_in
        i = jnp.arange(bucket, dtype=jnp.int32)
        n, slot = jnp.int32(len(prompt)), jnp.int32(1)
        vs = views(lambda k, v: PrefillView(k, v, slot),
                   lambda st: StatePrefillView(st, slot, n), (i < n)[None])
        with paddle.no_grad():
            lg, vs = model(paddle.Tensor(toks), attn_mask=LengthMask(
                i[None], n[None]), cache=vs)
        return lg._value, collect(vs, cache.lengths.at[1].set(n))

    @jax.jit
    def decode(cache_in, toks):
        nonlocal cache
        cache = cache_in
        pos = cache.lengths
        vs = views(lambda k, v: DecodeView(k, v, pos), StateDecodeView,
                   jnp.asarray([[False], [True]]))
        with paddle.no_grad():
            lg, vs = model(paddle.Tensor(toks), attn_mask=LengthMask(
                pos[:, None]), cache=vs)
        return lg._value, collect(vs, pos + 1)

    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    lg, state = prefill(cache, toks)
    np.testing.assert_allclose(lg[0, :len(prompt)], want[:len(prompt)],
                               atol=3e-4)
    for t in range(len(prompt), len(seq)):
        lg, state = decode(state, np.asarray([[0], [seq[t]]], np.int32))
        np.testing.assert_allclose(lg[1, 0], want[t], atol=3e-4)


def test_generate_serves_the_references_greedy_tokens(served, engine):
    cfg, _, named = served
    prompt = _prompt(2, 19)
    tokens = engine.generate(prompt, max_new_tokens=44)
    assert len(tokens) == 44 and len(set(tokens)) > 8  # no single token
    assert _served_gap(named, cfg, prompt, tokens) < 1e-3


def test_slots_of_different_lengths_and_a_reused_slot(served, engine,
                                                      counting):
    # three requests of different lengths decoded together, then a fourth
    # into a slot the first ones left: each is served as a fresh engine
    # serves it alone (a reused slot's state starts from zeros)
    cfg, model, named = served
    sched = Scheduler(engine)
    reqs = [sched.submit(Request(prompt=_prompt(10 + i, n),
                                 max_new_tokens=m))
            for i, (n, m) in enumerate([(17, 9), (23, 17), (30, 6)])]
    sched.run()
    later = [sched.submit(Request(prompt=_prompt(20, 31), max_new_tokens=12))]
    sched.run()
    assert {r.slot for r in later} <= {0, 1, 2}
    for r in reqs + later:
        assert len(r.tokens) == r.max_new_tokens
        assert _served_gap(named, cfg, r.prompt, r.tokens) < 1e-3
    fresh = GenerationEngine(model, max_batch=1, max_len=MAX_LEN)
    for r in later:
        assert fresh.generate(r.prompt, max_new_tokens=12) == r.tokens
    # what the ticks recorded: 2 expert layers count every routed token
    ticks = telemetry.get_telemetry().steps(kind="serve.tick",
                                            owner=sched.sched_id)
    both = lambda c, name: c.get(name, 0) + c.get(name + ".prefill", 0)
    counts = [{n: both(t.counts, n) for n in (
        "moe.tokens_routed", "moe.pairs_on_held", "moe.busiest_expert_rows",
        "moe.experts_hit", "serve.state_live_slots")}
              for t in ticks if t.counts]
    assert any("moe.pairs_on_held.prefill" in t.counts for t in ticks)
    routed = sum(c["moe.tokens_routed"] for c in counts)
    served_positions = sum(len(r.prompt) + len(r.tokens) - 1
                           for r in reqs + later)
    assert routed == 2 * served_positions
    on_held = sum(c["moe.pairs_on_held"] for c in counts)
    assert 0 < on_held < 2 * routed  # top-2, half the experts held
    assert all(c["moe.busiest_expert_rows"] <= c["moe.pairs_on_held"]
               and c["moe.experts_hit"] <= c["moe.pairs_on_held"]
               for c in counts)
    assert max(c["serve.state_live_slots"] for c in counts) == 3
    assert not engine._live.any()  # every slot released


@pytest.mark.parametrize("kw", [{"spec_k": 2}, {"prefill_chunk": 16}],
                         ids=["spec_k", "prefill_chunk"])
def test_recurrent_state_refuses_what_would_rewind_it(served, kw):
    with pytest.raises(RecurrentStateError, match="recurrent state"):
        GenerationEngine(served[1], max_batch=2, max_len=64, **kw)


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=4,
                    max_position_embeddings=64, hidden_dropout=0.0,
                    attention_dropout=0.0)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


def test_gpt_declares_its_kv_and_nothing_else(gpt):
    assert gpt.cache_spec() == [{"kind": "kv", "heads": 4, "head_dim": 8,
                                 "dtype": jnp.dtype("float32")}] * 2
    eng = GenerationEngine(gpt, max_batch=2, max_len=32)
    # the cache's leaves are what they were: the layers' K, their V, lengths
    leaves = jax.tree_util.tree_leaves(eng.cache)
    assert [a.shape for a in leaves] == [(2, 32, 4, 8)] * 4 + [(2,)]
    assert eng.cache.states == (None, None)
    assert not eng.has_state and eng.count_names == ()
    # no counting layer: the step returns bare tokens; the live mask is the
    # attention's, handed to every model's step
    args = eng.example_decode_args([3])
    assert len(args) == 7 and args[-1].dtype == bool
    tok, _, cache = eng.decode_step(*args)
    assert list(tok.shape) == [2] and isinstance(cache, KVCache)


def test_gpt_through_the_declared_cache_serves_the_uncached_tokens(gpt):
    eng = GenerationEngine(gpt, max_batch=2, max_len=32)
    prompt = _prompt(3, 9)
    prompt = [t % 64 for t in prompt]
    served_tokens = eng.generate(prompt, max_new_tokens=12)
    seq = list(prompt)
    with paddle.no_grad():
        full = jax.jit(lambda t: gpt(paddle.Tensor(t))._value)
    for _ in range(12):  # the full forward pass, no cache, one token a time
        ids = np.zeros((1, 32), np.int32)  # one shape: causal, so padding
        ids[0, :len(seq)] = seq            # after a position cannot reach it
        seq.append(int(np.argmax(full(ids)[0, len(seq) - 1])))
    assert served_tokens == seq[len(prompt):]
