"""Request-scoped tracing (ISSUE 8): span model, propagation through the
serving scheduler and the hapi fit loop, compile attribution, exports.

Contracts under test:
  * zero overhead while disabled — ``span()``/``start_span()`` hand back a
    shared no-op singleton, nothing is recorded;
  * one exported trace reconstructs a served request END TO END: submit →
    queue wait → prefill (with the bucket compile attributed inside it) →
    every decode token interval → evict, all sharing the request's trace
    id (acceptance criterion);
  * a decode step shared by multiple slots yields exactly ONE span per
    active request, each linked to the shared batched-dispatch span;
  * ``Model.fit`` emits epoch/step spans under the same API, with the
    train-step compile parented inside the first step span;
  * the PR 2/3/6 compile-count contracts hold with tracing on: decode
    still compiles exactly once.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTConfig, GPTForCausalLM
from paddle_tpu.nn import CrossEntropyLoss
from paddle_tpu.profiler import telemetry, tracing
from paddle_tpu.serving import GenerationEngine, Request, Scheduler
from paddle_tpu.utils import unique_name


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


@pytest.fixture
def _clean_telemetry():
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _gpt(seed=0, max_pos=64):
    with unique_name.guard():
        paddle.seed(seed)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=max_pos, hidden_dropout=0.0,
            attention_dropout=0.0))
    model.eval()
    return model


# ---------------------------------------------------------------------------
# span model
# ---------------------------------------------------------------------------
def test_disabled_by_default_null_singletons():
    assert not tracing.enabled()
    s1 = tracing.span("a")
    s2 = tracing.start_span("b")
    assert s1 is s2 is tracing.NULL_SPAN
    # the whole Span surface no-ops
    with s1 as s:
        s.set_attr("k", 1).end()
    assert tracing.current_span() is None
    with tracing.activate(s1):
        pass
    assert tracing.get_tracer().spans() == []
    # the boundary call hands out the very same singleton while both
    # systems are off, and files nothing anywhere
    assert not telemetry.enabled()
    assert telemetry.phase_span("dispatch") is tracing.NULL_SPAN
    assert telemetry.get_telemetry().phase_records() == []


def test_span_nesting_parenting_and_ids():
    tracing.enable()
    with tracing.span("root", attrs={"k": "v"}) as root:
        assert tracing.current_span() is root
        with tracing.span("child") as child:
            with tracing.span("grandchild") as gc:
                pass
        with tracing.span("sibling") as sib:
            pass
    assert tracing.current_span() is None
    assert child.trace_id == root.trace_id == gc.trace_id == sib.trace_id
    assert child.parent_id == root.span_id
    assert sib.parent_id == root.span_id
    assert gc.parent_id == child.span_id
    assert root.parent_id is None
    assert root.attrs["k"] == "v"
    # ends are monotone and every span landed in the ring
    names = [s.name for s in tracing.get_tracer().spans()]
    assert names == ["grandchild", "child", "sibling", "root"]
    assert root.duration_s >= child.duration_s >= gc.duration_s >= 0


def test_separate_roots_get_separate_traces():
    tracing.enable()
    with tracing.span("a") as a:
        pass
    with tracing.span("b") as b:
        pass
    assert a.trace_id != b.trace_id
    assert set(tracing.get_tracer().trace_ids()) == {a.trace_id, b.trace_id}


def test_manual_spans_and_activation():
    tracing.enable()
    tr = tracing.get_tracer()
    root = tracing.start_span("request")
    # not current until activated
    assert tracing.current_span() is None
    with tracing.activate(root):
        assert tracing.current_span() is root
        inner = tracing.span("work")
        with inner as w:
            pass
    assert tracing.current_span() is None
    assert w.parent_id == root.span_id
    assert root.end_ns is None  # activation must NOT end it
    root.end()
    root.end()  # idempotent
    assert len(tr.spans(root.trace_id)) == 2


def test_ring_bound_and_dropped_counter():
    tracing.enable(ring_size=8)
    for i in range(20):
        with tracing.span(f"s{i}"):
            pass
    tr = tracing.get_tracer()
    assert len(tr.spans()) == 8
    assert tr.dropped == 12
    tracing.enable(ring_size=8192)  # restore the default for later tests


def test_export_jsonl_and_chrome(tmp_path):
    tracing.enable()
    with tracing.span("outer", attrs={"rid": 7}):
        with tracing.span("inner"):
            pass
    p = tmp_path / "trace.jsonl"
    n = tracing.get_tracer().export_jsonl(str(p))
    rows = [json.loads(l) for l in p.read_text().splitlines()]
    assert n == len(rows) == 2
    by_name = {r["name"]: r for r in rows}
    assert by_name["inner"]["parent"] == by_name["outer"]["span"]
    assert by_name["inner"]["trace"] == by_name["outer"]["trace"]
    assert by_name["outer"]["attrs"]["rid"] == 7
    assert all(r["end_ns"] >= r["start_ns"] for r in rows)

    cp = tmp_path / "trace_chrome.json"
    ne = tracing.get_tracer().export_chrome(str(cp))
    doc = json.loads(cp.read_text())
    assert ne == 2
    evs = doc["traceEvents"]
    assert all(e["ph"] == "X" for e in evs)
    assert {e["name"] for e in evs} == {"outer", "inner"}
    assert evs[0]["ts"] <= evs[1]["ts"]


def test_export_chrome_merges_telemetry(tmp_path, _clean_telemetry):
    telemetry.enable()
    tracing.enable()
    with telemetry.phase_span("dispatch"):
        pass
    with tracing.span("req"):
        pass
    cp = tmp_path / "merged.json"
    n = tracing.get_tracer().export_chrome(str(cp), include_telemetry=True)
    evs = json.loads(cp.read_text())["traceEvents"]
    assert n == len(evs) == 2
    assert {e["name"] for e in evs} == {"req", "telemetry::dispatch"}


# ---------------------------------------------------------------------------
# serving: the end-to-end request reconstruction (acceptance criterion)
# ---------------------------------------------------------------------------
def _serve(n_requests=3, max_batch=2, max_new=4, slo=None):
    model = _gpt()
    eng = GenerationEngine(model, max_batch=max_batch, max_len=64,
                           prefill_buckets=(8, 16))
    sched = Scheduler(eng, slo=slo)
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, 97, 5).tolist(),
                    max_new_tokens=max_new) for _ in range(n_requests)]
    for r in reqs:
        sched.submit(r)
    sched.run()
    sched.shutdown()  # closes the serve_session span
    return eng, sched, reqs


def test_request_trace_reconstructs_end_to_end(tmp_path, _clean_telemetry):
    telemetry.enable()
    tracing.enable()
    eng, sched, reqs = _serve(n_requests=3, max_batch=2, max_new=4)
    tr = tracing.get_tracer()

    for req in reqs:
        assert req.trace_id is not None
        spans = {s.span_id: s for s in tr.spans(req.trace_id)}
        by_name = {}
        for s in spans.values():
            by_name.setdefault(s.name, []).append(s)
        root = by_name["request"][0]
        queue = by_name["queue"][0]
        prefill = by_name["prefill"][0]
        # ONE decode span per request: first decoded token -> evict
        assert len(by_name["decode"]) == 1
        decode = by_name["decode"][0]
        stamps = decode.attrs["token_end_ns"]

        # all spans share the request's trace and hang off its root
        assert root.parent_id is None
        assert queue.parent_id == root.span_id
        assert prefill.parent_id == root.span_id
        assert decode.parent_id == root.span_id

        # the life cycle is ordered: submit → queue wait → prefill →
        # every decode token's stamp → evict
        assert root.start_ns <= queue.start_ns <= queue.end_ns
        assert queue.end_ns <= prefill.start_ns <= prefill.end_ns
        assert decode.start_ns >= prefill.end_ns - 1
        prev = decode.start_ns
        for t in stamps:
            assert t >= prev  # the shared batched interval's end
            prev = t
        assert root.end_ns >= decode.end_ns >= prev

        # token accounting: prefill's token + one stamp (and one shared
        # decode_step id) per subsequent token
        assert len(stamps) == len(req.tokens) - 1
        assert len(decode.attrs["decode_steps"]) == len(stamps)
        assert decode.attrs["tokens"] == req.tokens[1:]
        assert decode.attrs["first_index"] == 1
        assert root.attrs["finish_reason"] == req.finish_reason
        assert root.attrs["ttft_s"] == pytest.approx(req.ttft_s)
        assert root.attrs["latency_s"] == pytest.approx(req.latency_s)

        # the engine's prefill phases nest inside the scheduler's prefill
        # span — same trace, so compile attribution joins up
        for phase in ("serve.prefill_dispatch", "serve.prefill_readback"):
            assert by_name[phase][0].parent_id == prefill.span_id

    # compile attribution: the FIRST request through a cold bucket carries
    # the serve_prefill compile span inside its own trace
    first = reqs[0]
    comp = [s for s in tr.spans(first.trace_id) if s.name == "compile"]
    assert comp, "no compile span attributed to the first request"
    assert comp[0].attrs["step"] == "serve_prefill"
    assert comp[0].attrs["compile_index"] == 1
    disp = [s for s in tr.spans(first.trace_id)
            if s.name == "serve.prefill_dispatch"]
    assert comp[0].parent_id == disp[0].span_id
    # a warm bucket's call is a plain `dispatch` under the same phase
    warm = [s for s in tr.spans(reqs[1].trace_id) if s.name == "dispatch"]
    assert warm and not [s for s in tr.spans(reqs[1].trace_id)
                         if s.name == "compile"]

    # JSONL export round-trips the whole reconstruction
    p = tmp_path / "req.jsonl"
    tr.export_jsonl(str(p), trace_id=first.trace_id)
    rows = [json.loads(l) for l in p.read_text().splitlines()]
    assert {r["trace"] for r in rows} == {first.trace_id}
    assert {"request", "queue", "prefill", "decode",
            "compile"} <= {r["name"] for r in rows}
    assert [r for r in rows if r["name"] == "decode"][0]["attrs"][
        "tokens"] == first.tokens[1:]

    # PR 6 contract unchanged under tracing: decode compiled EXACTLY once
    assert telemetry.get_telemetry().compile_counts()["serve_decode"] == 1


def test_shared_decode_step_one_span_per_active_request(_clean_telemetry):
    """Two requests decoding in the same batched step: each request's OWN
    decode span holds a stamp for that step, linked to the shared
    decode_step span."""
    telemetry.enable()
    tracing.enable()
    eng, sched, reqs = _serve(n_requests=2, max_batch=2, max_new=4)
    tr = tracing.get_tracer()

    session = [s for s in tr.spans() if s.name == "serve_session"]
    shared = [s for s in tr.spans() if s.name == "decode_step"]
    assert session and shared
    assert all(s.parent_id == session[0].span_id for s in shared)
    # both requests were admitted in tick 0, so every decode_step ran 2
    # slots: per shared span, exactly one token stamp per request
    decodes = [s for s in tr.spans() if s.name == "decode"]
    assert len(decodes) == 2  # one per request, not one per token
    for ds in shared:
        linked = [s for s in decodes
                  if ds.span_id in s.attrs["decode_steps"]]
        assert len(linked) == ds.attrs["active"] == 2
        assert ({s.trace_id for s in linked}
                == {r.trace_id for r in reqs})
        assert all(s.attrs["decode_trace"] == ds.trace_id for s in linked)
        # each stamp is the shared dispatch interval's end, verbatim
        for s in linked:
            i = s.attrs["decode_steps"].index(ds.span_id)
            assert s.attrs["token_end_ns"][i] == ds.end_ns
            assert s.start_ns <= ds.start_ns
    # the shared span holds the tick's decode phases, on the session trace
    for name in ("serve.decode_feed", "serve.decode_dispatch",
                 "serve.decode_readback"):
        kids = [s for s in tr.spans() if s.name == name]
        assert len(kids) == len(shared)
        assert {s.parent_id for s in kids} == {ds.span_id for ds in shared}


def test_one_boundary_call_feeds_both_sinks_identically(_clean_telemetry):
    """``telemetry.phase_span`` is THE boundary call: the phase in
    telemetry's ring and the ``Span`` in the tracer's carry the same name
    and the very same start and end stamps; nothing is timed twice."""
    telemetry.enable()
    tracing.enable()
    with tracing.span("root") as root:
        with telemetry.phase_span("serve.decode_readback",
                                  attrs={"slot": 3}, key="k") as ph:
            assert tracing.current_span().name == "serve.decode_readback"
            ph.set_attr("late", True)
    (phase,) = telemetry.get_telemetry().phase_records("serve.decode_readback")
    (span,) = [s for s in tracing.get_tracer().spans()
               if s.name == "serve.decode_readback"]
    assert (phase[1], phase[2]) == (span.start_ns, span.end_ns)
    assert (ph.start_ns, ph.end_ns) == (span.start_ns, span.end_ns)
    assert phase[4] == "k"
    assert span.parent_id == root.span_id and span.trace_id == root.trace_id
    assert span.attrs == {"slot": 3, "late": True}
    # telemetry alone: the phase, no Span; tracing alone: the Span, no phase
    tracing.disable()
    with telemetry.phase_span("dispatch"):
        pass
    assert not [s for s in tracing.get_tracer().spans()
                if s.name == "dispatch"]
    telemetry.disable()
    tracing.enable()
    n = len(telemetry.get_telemetry().phase_records())
    with tracing.span("root2"):
        with telemetry.phase_span("h2d_copy"):
            pass
    assert [s for s in tracing.get_tracer().spans() if s.name == "h2d_copy"]
    assert len(telemetry.get_telemetry().phase_records()) == n
    # a phase joins a trace, it never roots one
    with telemetry.phase_span("data_wait"):
        pass
    assert not [s for s in tracing.get_tracer().spans()
                if s.name == "data_wait"]


def test_compiled_step_call_is_one_span_dispatch_or_compile(_clean_telemetry):
    """CompiledStep.__call__ marks its boundary once: the call that traces
    is a ``compile`` in both sinks (same stamps), a cached one a
    ``dispatch``; there is no second timing of either."""
    from paddle_tpu.jit.functionalize import CompiledStep

    telemetry.enable()
    tracing.enable()
    lin = paddle.nn.Linear(3, 3)
    step = CompiledStep(lambda x: lin(x).square().mean(), stateful=[lin])
    step.name = "toy_step"
    x = paddle.to_tensor(np.ones((2, 3), np.float32))
    with tracing.span("root"):
        step(x)
        step(x)
    tm = telemetry.get_telemetry()
    spans = {s.name: s for s in tracing.get_tracer().spans()}
    for name in ("compile", "dispatch"):
        (phase,) = tm.phase_records(name, key="toy_step")
        assert (phase[1], phase[2]) == (spans[name].start_ns,
                                        spans[name].end_ns)
    assert spans["compile"].attrs == {"step": "toy_step", "compile_index": 1}
    assert tm.compile_counts() == {"toy_step": 1}


class _StubEngine:
    """Scheduler-facing engine surface with no model behind it: every slot
    decodes token 1. Lets a test drive hundreds of full-width ticks."""

    def __init__(self, max_batch, max_len=512):
        self.max_batch, self.max_len = max_batch, max_len
        self.prefill_buckets = (max_len,)
        self.spec_k = 0
        self.prefill_chunk = None

    def prefill(self, slot, prompt):
        with telemetry.phase_span("serve.prefill_dispatch"):
            pass
        with telemetry.phase_span("serve.prefill_readback"):
            return 1

    def decode_once(self, feed):
        with telemetry.phase_span("serve.decode_dispatch"):
            pass
        with telemetry.phase_span("serve.decode_readback"):
            return np.ones((self.max_batch,), np.int32)


def test_a_saturated_window_fits_the_default_ring(_clean_telemetry):
    """200 ticks at 32 slots with requests of ~50 tokens (what a saturated
    benchmark window holds: ~6,400 tokens, ~130 requests): the default
    8,192-span ring drops nothing and keeps every request's queue and
    prefill span. The per-tick ``decode_token`` fan-out this replaced
    would have filed 6,400 spans for the tokens alone."""
    telemetry.enable()
    tracing.enable()
    tr = tracing.get_tracer()
    assert tr.ring_size == 8192
    sched = Scheduler(_StubEngine(32))
    rng = np.random.RandomState(0)
    reqs = []
    for tick in range(200):
        while len(sched.queue) + len(sched.active) < 36:
            reqs.append(sched.submit(Request(
                prompt=[1] * 8, max_new_tokens=int(rng.randint(40, 60)))))
        sched.step()
    assert sched.decode_steps == 200 and sched.occupancy() == 1.0
    sched.shutdown()
    assert sum(len(r.tokens) for r in reqs) >= 6000
    assert tr.dropped == 0
    names = [s.name for s in tr.spans()]
    admitted = [r for r in reqs if r.tokens]
    assert names.count("queue") == len(reqs) >= 120
    assert names.count("prefill") == len(admitted)
    assert names.count("decode") == len(admitted)
    assert names.count("serve.tick") == 200
    # every token is still on the record: in its request's decode span
    decoded = sum(len(s.attrs["token_end_ns"]) for s in tr.spans()
                  if s.name == "decode")
    assert decoded == sum(len(r.tokens) - 1 for r in admitted)
    # and telemetry's ring holds all 200 tick records of this scheduler
    ticks = telemetry.get_telemetry().steps(kind="serve.tick",
                                            owner=sched.sched_id)
    assert [t.index for t in ticks] == list(range(200))


def test_scheduler_tracing_off_is_free(_clean_telemetry):
    """Tracing disabled: no Request picks up spans and the tracer stays
    empty — the serving loop's disabled path does zero tracing work."""
    telemetry.enable()
    eng, sched, reqs = _serve(n_requests=2, max_batch=2, max_new=3)
    assert all(r.trace_span is None and r.trace_id is None for r in reqs)
    assert tracing.get_tracer().spans() == []


def test_generate_emits_its_own_trace():
    tracing.enable()
    model = _gpt()
    eng = GenerationEngine(model, max_batch=1, max_len=64,
                           prefill_buckets=(8,))
    out = eng.generate([1, 2, 3], max_new_tokens=3)
    tr = tracing.get_tracer()
    gen = [s for s in tr.spans() if s.name == "generate"]
    assert len(gen) == 1
    inside = tr.spans(gen[0].trace_id)
    names = [s.name for s in inside]
    assert names.count("serve.prefill_dispatch") == 1
    assert names.count("serve.prefill_readback") == 1
    assert names.count("serve.decode_dispatch") == len(out) - 1
    assert names.count("serve.decode_readback") == len(out) - 1


# ---------------------------------------------------------------------------
# training: Model.fit under the same span model
# ---------------------------------------------------------------------------
class _ToyDS:
    def __init__(self, n=48):
        rng = np.random.RandomState(0)
        self.x = rng.randn(n, 8).astype(np.float32)
        w = rng.randn(8).astype(np.float32)
        self.y = (self.x @ w > 0).astype(np.int64)

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def test_model_fit_emits_step_spans(_clean_telemetry):
    tracing.enable()
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                               paddle.nn.Linear(16, 2))
    model = paddle.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    model.prepare(opt, CrossEntropyLoss())
    model.fit(_ToyDS(), batch_size=16, epochs=1, verbose=0)

    tr = tracing.get_tracer()
    epochs = [s for s in tr.spans() if s.name == "train_epoch"]
    steps = [s for s in tr.spans() if s.name == "train_step"]
    assert len(epochs) == 1
    assert len(steps) == 3  # 48 samples / batch 16
    root = epochs[0]
    assert all(s.parent_id == root.span_id for s in steps)
    assert all(s.trace_id == root.trace_id for s in steps)
    assert [s.attrs["step"] for s in
            sorted(steps, key=lambda s: s.start_ns)] == [0, 1, 2]
    assert root.attrs["samples"] == 48
    # the train-step compile is attributed inside the first step span —
    # even though telemetry was off (tracing-only compile attribution)
    comps = [s for s in tr.spans(root.trace_id) if s.name == "compile"]
    assert comps, "train-step compile not attributed to the trace"
    first_step = min(steps, key=lambda s: s.start_ns)
    assert comps[0].parent_id == first_step.span_id
    # telemetry stayed untouched: tracing alone must not populate it
    assert telemetry.get_telemetry().counters() == {}
