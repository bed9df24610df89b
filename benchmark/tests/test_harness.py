"""The yardstick's own arithmetic: reducer, last line, work counts, load
generator, and the plain reference against the program at a tiny size."""
import json
import math
import os

import numpy as np
import pytest

from harness import lastline, loadgen, trace_reduce as tr, work
from harness.builders import load_json, sizes_of

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def fixture(name):
    return tr.read(os.path.join(DATA, name))


# -- trace reducer -----------------------------------------------------------
def synthetic():
    ms = 1e6
    ops = [["%while.1 = ...", 10 * ms, 40 * ms],       # contains the next two
           ["%fusion.1 = f32[8]{0} fusion()", 10 * ms, 15 * ms],
           ["%fusion.2 = f32[8]{0} fusion()", 30 * ms, 20 * ms],
           ["%all-reduce.1 = f32[8]{0} all-reduce()", 60 * ms, 10 * ms],
           ["%fusion.3 = f32[8]{0} fusion()", 65 * ms, 10 * ms],
           ["%fusion.4 = f32[8]{0} fusion()", 95 * ms, 20 * ms]]  # past the end
    return {"window": [0.0, 100 * ms],
            "spans": [["bench:loader_next", 0.0, 9 * ms],
                      ["bench:step_dispatch", 9 * ms, 2 * ms],
                      ["bench:loss_readback", 50 * ms, 50 * ms]],
            "devices": {"/device:TPU:0": {
                "ops": ops, "modules": [["jit_pure(1)", 10 * ms, 65 * ms]]}}}


def test_busy_is_a_union_clipped_to_the_window():
    t = synthetic()
    # 10..50 (the loop and its children once), 60..75, 95..100
    assert tr.busy_s(t) == pytest.approx(0.060)
    assert tr.busy_s(t) <= tr.window_s(t) == pytest.approx(0.100)
    summed = sum(d for _, _, d in t["devices"]["/device:TPU:0"]["ops"]) / 1e9
    assert summed > tr.window_s(t)  # what a sum over nested events reads


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    b = tr.breakdown(synthetic())
    gaps = dict(b["idle_gaps"])
    assert gaps["loader_next"] == pytest.approx(0.010)   # 0..10
    assert gaps["loss_readback"] == pytest.approx(0.030)  # 50..60, 75..95
    ops = dict(b["device_ops"])
    assert ops["fusion f32[8]"] == pytest.approx(0.050)  # 15 + 20 + 10 + 5
    assert ops.get("while", 0.0) == pytest.approx(0.005)  # its own 25..30


def test_idle_gap_goes_to_the_innermost_span_covering_most_of_it():
    """The program's ``paddle_tpu:*`` phases nest inside the benchmark's
    tick: a gap is named by the phase, not by the tick around it."""
    ms = 1e6
    t = {"window": [0.0, 100 * ms],
         "spans": [["bench:sched_step", 0.0, 100 * ms],
                   ["paddle_tpu:serve.tick", 1 * ms, 98 * ms],
                   ["paddle_tpu:serve.admit", 2 * ms, 28 * ms],
                   ["paddle_tpu:serve.prefill_dispatch", 2 * ms, 7 * ms],
                   ["paddle_tpu:serve.decode_dispatch", 30 * ms, 10 * ms],
                   ["paddle_tpu:serve.decode_readback", 40 * ms, 58 * ms]],
         "devices": {"/device:TPU:0": {"modules": [], "ops": [
             ["%fusion.1 = f32[8]{0} fusion()", 10 * ms, 18 * ms],
             ["%fusion.2 = f32[8]{0} fusion()", 36 * ms, 60 * ms]]}}}
    gaps = dict(tr.breakdown(t)["idle_gaps"])
    # 0..10: prefill_dispatch covers 7 of 10 and is the shortest that does;
    # 28..36: admit covers 2, decode_dispatch 6; 96..100: the read-back 2,
    # under half, so the shortest span over half of it: the tick
    assert gaps == {"serve.prefill_dispatch": pytest.approx(0.010),
                    "serve.decode_dispatch": pytest.approx(0.008),
                    "serve.tick": pytest.approx(0.004)}
    t["spans"] = t["spans"][:1]      # the benchmark's span alone: as before
    assert dict(tr.breakdown(t)["idle_gaps"]) == {
        "sched_step": pytest.approx(0.022)}
    t["spans"] = []
    assert dict(tr.breakdown(t)["idle_gaps"]) == {
        "unattributed": pytest.approx(0.022)}


def test_cache_live_share_and_nothing_to_read():
    from readers import cache

    # 20 decode steps that read 4,000 live positions each, of 32 x 1024
    facts = {"cache_slots": 32 * 1024,
             "traced_work": {"decode_steps": 20, "decode_live_tokens": 80000}}
    assert cache.live_share({"facts": facts}, None) == pytest.approx(
        100 * 4000 / 32768)
    facts["traced_work"]["decode_steps"] = 0
    assert cache.live_share({"facts": facts}, None) is None


def test_tools_overlay_merges_nested_traffic():
    import tools

    base = {"rate_per_s": 2.4, "prompt_len": {"median": 128, "sigma": 0.8}}
    got = tools.overlay(base, {"prompt_len": {"sigma": 1.2}, "seed": 7})
    assert got == {"rate_per_s": 2.4, "seed": 7,
                   "prompt_len": {"median": 128, "sigma": 1.2}}
    assert base["prompt_len"]["sigma"] == 0.8


def test_no_device_op_is_an_error_not_a_zero():
    t = synthetic()
    t["devices"]["/device:TPU:0"]["ops"] = []
    with pytest.raises(ValueError):
        tr.busy_s(t)
    assert tr.matched_s(synthetic(), "no_such_kernel") is None


def test_recorded_train_step():
    """Part of one GPT-2 124M train step recorded on a v5e (PR 25)."""
    t = fixture("train_step_v5e.json")
    busy, window = tr.busy_s(t), tr.window_s(t)
    assert 0 < busy <= window
    assert busy / window > 0.9
    sizes = sizes_of(load_json("configs", "gpt2-124m"), False)
    from readers.trace import fill
    flash = load_json("metrics", "flash_attn_roofline.train")["args"]["pattern"]
    ce = load_json("metrics", "fused_ce_step_share.train")["args"]["pattern"]
    assert tr.matched_s(t, fill(flash, sizes)) > 0.01   # 12 forward kernels
    assert tr.matched_s(t, fill(ce, sizes)) > 0.01      # the forward scan
    b = tr.breakdown(t)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(window - busy,
                                                              rel=1e-6)


def test_recorded_serving_ticks():
    """Two and a half ticks of the GPT-2 large serving cell recorded on a
    v5e (PR 25): three decode programs and one prefill bucket."""
    t = fixture("serve_ticks_v5e.json.gz")
    busy, window = tr.busy_s(t), tr.window_s(t)
    assert 0 < busy <= window
    progs = tr.programs(t, "jit_pure|serve_")
    runs = sorted(n for n, _ in progs.values())
    assert runs == [1, 3]                     # a prefill, the decode steps
    decode_s = max(progs.values())[1]
    assert 0.9 * busy < decode_s <= busy
    host, inside, n = tr.busy_inside_s(t, "bench:sched_step")
    assert n == 3 and 0 < host - inside < 0.02 * n   # ms of host a tick
    b = tr.breakdown(t)
    assert dict(b["idle_gaps"]).keys() <= {"sched_step", "submit",
                                            "idle_no_request", "unattributed"}
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(window - busy,
                                                              rel=1e-6)


def test_recorded_serving_phases():
    """A few ticks of the steady serving cell recorded on a v5e (PR 29),
    one of them admitting a request, with the program's own
    ``paddle_tpu:*`` phases beside the benchmark's spans: the idle gaps are
    named by phase, and every metric read from the trace reads what it
    reads with those spans taken out again."""
    import types

    import run as bench

    t = fixture("serve_phases_v5e.json.gz")
    names = {s[0] for s in t["spans"]}
    assert {"bench:sched_step", "paddle_tpu:serve.tick",
            "paddle_tpu:serve.admit", "paddle_tpu:serve.decode_dispatch",
            "paddle_tpu:serve.decode_readback"} <= names
    busy, window = tr.busy_s(t), tr.window_s(t)
    b = tr.breakdown(t)
    gaps = dict(b["idle_gaps"])
    assert any(k.startswith("serve.") for k in gaps)
    assert gaps.get("sched_step", 0.0) < 0.1 * (window - busy)
    assert sum(gaps.values()) == pytest.approx(window - busy, rel=1e-6)
    bare = dict(t, spans=[s for s in t["spans"] if s[0].startswith("bench:")])
    was = tr.breakdown(bare)
    assert was["device_ops"] == b["device_ops"]
    assert dict(was["idle_gaps"]).keys() <= {"sched_step", "submit",
                                              "unattributed"}
    cell = load_json("workloads", "gpt2_large.serve_chat_steady")
    ctx = types.SimpleNamespace(
        sizes=sizes_of(load_json("configs", cell["config"]), False), chips=1)
    steps = max(n for n, _ in tr.programs(t, "serve_decode").values())
    facts = {"cache_slots": 32 * 1024, "traced_work": {
        "decode_steps": steps, "decode_positions": 23 * steps,
        "decode_live_tokens": 5000 * steps, "n_positions": 400,
        "n_keys": 5000 * steps, "n_outputs": 23 * steps}}
    read = lambda trace: {
        m: bench.read_metric(m, {"trace": trace, "facts": facts, "values": {
            "ttft_mean_ms": 59.0}, "device_kind": "TPU v5 lite"}, ctx)[0]
        for m in cell["per_layer"]}
    got = read(t)
    assert got == read(bare) and None not in got.values()
    assert 0 < got["decode_step_roofline.steady"] < 100
    assert 0 < got["host_ms_per_tick.steady"] < 20


# -- the last line -----------------------------------------------------------
def good_line(**over):
    kw = dict(correct=True, attempted=10, failed=0,
              values={"mfu.train": 50.0, "device_idle_share.train": 1.0},
              wanted=["mfu.train", "device_idle_share.train"],
              units={"mfu.train": "%", "device_idle_share.train": "%"},
              device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                      "memory_peak_bytes": 10 ** 10, "window_s": 3.0,
                      "busy_s": 2.9},
              traced=True, breakdown={"device_ops": [], "idle_gaps": []},
              compared={"loss_gap": {"value": 1e-5, "limit": 1e-4}})
    kw.update(over)
    return kw


def test_lastline_accepts_a_good_line():
    line = json.loads(lastline.build(**good_line()))
    assert list(line)[-1] == "compared"
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("over", [
    {"values": {"mfu.train": 50.0}},                                 # missing
    {"values": {"mfu.train": 101.0, "device_idle_share.train": 1.0}},  # >100
    {"values": {"mfu.train": math.nan, "device_idle_share.train": 1.0}},
    {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 10 ** 10, "window_s": 3.0,
                "busy_s": 0.0}},
    {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 10 ** 10, "window_s": 3.0,
                "busy_s": 3.1}},
    {"device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 10 ** 10}},
    {"compared": {}},
    {"attempted": 0},
])
def test_lastline_rejects(over):
    with pytest.raises(lastline.MalformedLine):
        lastline.build(**good_line(**over))


CELLS = sorted(f[:-len(".json")] for f in os.listdir(os.path.join(
    os.path.dirname(DATA), os.pardir, "workloads")))


@pytest.mark.parametrize("cell", CELLS)
def test_lastline_holds_an_untraced_line_to_every_end_to_end_metric(cell):
    """Whatever a cell names under ``end_to_end`` (``ttft_p95_ms`` in the
    steady cell, if it is there) has to be in its ``--trace 0`` line, with
    the unit and the bound its own file gives."""
    wanted = load_json("workloads", cell)["end_to_end"]
    specs = {m: load_json("metrics", m) for m in wanted}
    assert "setup_s" in wanted and len(wanted) >= 2
    assert all(0 < s["bound"] <= 0.1 and s["source"] in
               ("host_clock", "device_trace") for s in specs.values())
    kw = good_line(values={m: 1.5 for m in wanted}, wanted=wanted,
                   units={m: specs[m]["unit"] for m in wanted}, traced=False,
                   device={"platform": "tpu", "kind": "TPU v5 lite",
                           "count": 1, "memory_peak_bytes": 10 ** 10})
    line = json.loads(lastline.build(**kw))
    assert list(line["metrics"]) == wanted
    for m in wanted:
        short = dict(kw, values={k: v for k, v in kw["values"].items()
                                 if k != m})
        with pytest.raises(lastline.MalformedLine):
            lastline.build(**short)


# -- work counts, by hand ----------------------------------------------------
def test_work_gpt2_124m():
    s = sizes_of(load_json("configs", "gpt2-124m"), False)
    h, L, V = 768, 12, 50304
    assert work.matmul_params(s) == L * 12 * h * h == 84934656
    # 50304*768 + 1024*768 + 12*(12*768^2 + 13*768) + 2*768
    assert work.n_params(s) == 38633472 + 786432 + 12 * 7087872 + 1536
    fwd = 2 * 84934656 + 2 * V * h + L * 4 * h * 512.5
    assert work.train_flops_per_token(s, 1024) == pytest.approx(3 * fwd)
    assert work.train_flops_per_token(s, 1024) == pytest.approx(798.1e6,
                                                                rel=1e-3)
    assert work.kv_bytes_per_token(s) == 2 * 12 * 768 * 2 == 36864
    flops, nbytes = work.flash_train_work(s, 24, 1024)
    assert flops == pytest.approx(12 * 6 * 2 * 768 * 24 * 1024 * 1025 / 2)
    assert nbytes == 12 * 12 * 24 * 1024 * 768 * 2


def test_work_gpt2_large():
    s = sizes_of(load_json("configs", "gpt2-large"), False)
    assert work.n_params(s) == pytest.approx(774.1e6, rel=2e-3)
    assert work.kv_bytes_per_token(s) == 2 * 36 * 1280 * 2 == 184320
    # one decode step, 32 slots of 200 live tokens: weights once + the cache
    assert work.decode_step_bytes(s, 6400) == \
        work.n_params(s) * 2 + 6400 * 184320
    # a prompt of 3 tokens and one output: 3 positions see 1+2+3 keys
    assert work.serve_flops(s, 3, 6, 1) == \
        3 * 2 * 36 * 12 * 1280 ** 2 + 36 * 4 * 1280 * 6 + 2 * 50304 * 1280
    assert work.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        work.peaks("TPU v9")


# -- load generator ----------------------------------------------------------
def test_loadgen_same_seed_same_schedule_other_seed_same_work():
    tr_ = load_json("workloads", "gpt2_large.serve_chat_steady")["traffic"]
    a = loadgen.schedule(tr_, 7, 30.0, 50257)
    assert a == loadgen.schedule(tr_, 7, 30.0, 50257)
    b = loadgen.schedule(tr_, 3000000019, 30.0, 50257)
    assert a != b and len(a) == len(b) == round(tr_["rate_per_s"] * 30)
    key = lambda xs: sorted((len(x.prompt), x.max_new) for x in xs)
    assert key(a) == key(b)                     # the same set of sizes
    gaps = lambda xs: sorted(np.round(np.diff([0.0] + [x.due_s for x in xs]), 9))
    assert gaps(a) == gaps(b)                   # and of arrival gaps
    assert all(0 < x.due_s < 30.0 for x in a)
    assert all(16 <= len(x.prompt) <= 768 and 16 <= x.max_new <= 256
               and len(x.prompt) + x.max_new <= 1024 for x in a)
    mean, worst = loadgen.lateness_ms([0.0, 1.0], [0.002, 1.0])
    assert mean == pytest.approx(1.0) and worst == pytest.approx(2.0)


def test_loadgen_backlog_at_open():
    tr_ = dict(load_json("workloads", "gpt2_large.serve_chat_sat")["traffic"],
               rate_per_s=2.0, backlog_at_open=5)
    a = loadgen.schedule(tr_, 7, 10.0, 50257)
    b = loadgen.schedule(tr_, 8, 10.0, 50257)
    assert len(a) == len(b) == 25
    assert [x.due_s for x in a[:5]] == [0.0] * 5 and a[5].due_s > 0.0
    key = lambda xs: sorted((len(x.prompt), x.max_new) for x in xs)
    assert key(a) == key(b) and a != b


SERVING = ["gpt2_large.serve_chat_sat", "gpt2_large.serve_chat_steady"]


@pytest.mark.parametrize("seconds", [51.0, 12.0])  # a run; a traced run
@pytest.mark.parametrize("cell", SERVING)
def test_loadgen_every_seed_the_same_work_at_the_cells_rate(cell, seconds):
    """What the spread of a serving cell rests on: at the rate in its file
    every seed is sent the same count of requests and the same totals of
    prompt and output tokens, over a whole run and over a traced one (8 s
    of lead and 4 s under the profiler)."""
    spec = load_json("workloads", cell)
    assert spec["trace_lead_s"] + spec["trace_seconds"] == 12.0
    tr_ = spec["traffic"]
    want = None
    for seed in (1, 77, 2147483659, 3000000019):
        a = loadgen.schedule(tr_, seed, seconds, 50257)
        got = (len(a), sum(len(x.prompt) for x in a),
               sum(x.max_new for x in a))
        want = want or got
        assert got == want
        assert all(0.0 <= x.due_s < seconds for x in a)
        assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    assert want[0] == round(tr_["rate_per_s"] * seconds) + tr_.get(
        "backlog_at_open", 0)


def test_sat_opens_on_two_full_engines():
    """``backlog_at_open`` 64 = 2 x ``max_batch``: no lull in the Poisson
    schedule empties the queue before the arrivals overtake the engine."""
    spec = load_json("workloads", "gpt2_large.serve_chat_sat")
    assert spec["traffic"]["backlog_at_open"] == 64 \
        == 2 * spec["engine"]["max_batch"] and spec["drain"] is False
    a = loadgen.schedule(spec["traffic"], 2147483659, 51.0, 50257)
    assert [x.due_s for x in a[:64]] == [0.0] * 64 and a[64].due_s > 0.0
    steady = load_json("workloads", "gpt2_large.serve_chat_steady")
    assert "backlog_at_open" not in steady["traffic"] and steady["drain"]
    # the two cells differ in their rates and the backlog alone
    same = lambda t: {k: v for k, v in t.items()
                      if k not in ("rate_per_s", "backlog_at_open")}
    assert same(spec["traffic"]) == same(steady["traffic"])
    assert spec["traffic"]["rate_per_s"] > steady["traffic"]["rate_per_s"]


class _StubScheduler:
    """Admits whatever is queued, and gives every live request a token a
    tick: enough of ``serving.Scheduler`` for ``drivers.serve.Window``."""

    def __init__(self, eng):
        self.queue, self.active, self.prefilling = [], {}, {}
        self.decode_steps = 0

    def submit(self, r):
        self.queue.append(r)
        return r

    def step(self):
        for r in self.active.values():
            r.tokens.append(1)
        self.decode_steps += 1
        for r in self.queue:
            r.tokens.append(1)
            self.active[r.rid] = r
        self.queue = []
        done = [r for r in self.active.values()
                if len(r.tokens) >= r.max_new_tokens]
        for r in done:
            del self.active[r.rid]
        return done


def test_window_hands_the_program_each_due_time_and_sorts_its_ticks(
        monkeypatch):
    import time

    import paddle_tpu.serving as serving
    from drivers import serve

    monkeypatch.setattr(serving, "Scheduler", _StubScheduler)
    arrivals = [loadgen.Arrival(0.0, [1, 2, 3], 4),
                loadgen.Arrival(0.0, [1, 2], 3),
                loadgen.Arrival(0.001, [5], 2)]
    win = serve.Window(None, arrivals)
    t0 = time.perf_counter()
    win.submit_due(0.0005, t0)           # the two that are due
    assert [r.due_ns for r in win.reqs] == [int(t0 * 1e9)] * 2
    win.tick(t0)                         # admits 2, no gap yet
    win.submit_due(0.5, t0)
    assert win.reqs[2].due_ns == int((t0 + 0.001) * 1e9)
    win.tick(t0)                         # admits 1; the two live: 2 gaps
    win.tick(t0)                         # admits none; three live: 3 gaps
    assert [(t.admitted, t.gaps) for t in win.tick_log] == [
        (2, 0), (1, 2), (0, 3)]
    win.closed_s, win.total_s = 1e9, 4.0
    kinds = win.tick_kinds()
    assert {k: round(v["share_of_ticks"], 3) for k, v in kinds.items()} == {
        "0": 33.333, "1": 33.333, "2+": 33.333}
    assert {k: v["share_of_gaps"] for k, v in kinds.items()} == {
        "0": 60.0, "1": 40.0, "2+": 0.0}
    assert all(v["ms_p50"] <= v["ms_p95"] for v in kinds.values())
    # two finished in the last tick; the first still owes a token
    assert win.in_system_at_quarters()[-1] == 1


def test_train_batches_rows_all_differ():
    ids, labels = next(loadgen.train_batches(5, 50257, 8, 64))
    assert (ids[:, 1:] == labels[:, :-1]).all()
    assert len({r.tobytes() for r in ids}) == 8


# -- the reference against the program, tiny, float32 ------------------------
def test_stated_init_sets_the_score_spread():
    from harness import weights as W

    sizes = sizes_of(load_json("configs", "gpt2-large"), True)
    assert sizes["init"]["qk"] == 2.0
    w = W.make(3, sizes, "float32")
    h = sizes["n_embd"]
    assert float(np.std(np.asarray(w["qkv_w"]))) == pytest.approx(
        2.0 / h ** 0.5, rel=0.02)
    plain = W.make(3, {**sizes, "init": None}, "float32")
    assert float(np.std(np.asarray(plain["qkv_w"]))) == pytest.approx(
        0.02, rel=0.02)


@pytest.mark.parametrize("config", ["gpt2-124m", "gpt2-large"])
def test_reference_matches_the_program(config):
    import jax
    import jax.numpy as jnp

    from harness import reference_gpt2 as ref, weights as W
    from harness.builders import gpt_causal_lm

    sizes = sizes_of(load_json("configs", config), True)
    sizes["dtype"] = "float32"
    model = gpt_causal_lm(sizes, seed=11)
    model.eval()
    ids = np.random.default_rng(0).integers(0, 500, (2, 48), dtype=np.int32)
    got = np.asarray(model(jnp.asarray(ids))._value)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits_fn(
            ref.upcast(W.make(11, sizes, "float32")), jnp.asarray(ids),
            sizes["n_head"], sizes["layer_norm_epsilon"]))
    assert np.max(np.abs(got - want)) < 2e-4 * np.max(np.abs(want))
