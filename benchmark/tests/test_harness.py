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
