"""Device-side observability (profiler.devprof): memory/cost harvest,
per-mesh-axis collective attribution on the dryrun-shaped configs,
pipeline-bubble metrics, straggler detection, and OOM forensics.

Reference contract (ISSUE 5): bench telemetry carries hbm_peak_bytes /
comm_fraction, the MULTICHIP dryrun configs log per-axis collective byte
counters, and an injected dispatch OOM produces a forensics dump instead
of a bare XLA error.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit.functionalize import CompiledStep
from paddle_tpu.profiler import devprof, telemetry
from paddle_tpu.utils import unique_name

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))


def _np(t):
    return np.asarray(t._value)


def _mlp_step(name="train_step", donate_inputs=False, seed=0):
    """The bench-shaped MLP train step (model + SGD, one fused program)."""
    with unique_name.guard():
        paddle.seed(seed)
        net = paddle.nn.Sequential(paddle.nn.Linear(16, 32),
                                   paddle.nn.ReLU(),
                                   paddle.nn.Linear(32, 4))
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())

    def train_step(x, y):
        loss = F.cross_entropy(net(x), y).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step.__name__ = name
    step = CompiledStep(train_step, stateful=[net, opt],
                        donate_inputs=donate_inputs)
    rng = np.random.RandomState(seed)
    x = Tensor(rng.rand(8, 16).astype(np.float32))
    y = Tensor(rng.randint(0, 4, (8, 1)).astype(np.int64))
    return step, x, y


@pytest.fixture(autouse=True)
def _clean_registry():
    telemetry.disable()
    telemetry.reset()
    devprof.clear_reports()
    yield
    telemetry.disable()
    telemetry.reset()
    devprof.clear_reports()


# ---------------------------------------------------------------------------
# normalize_cost_analysis (shared shim: cost_model / devprof)
# ---------------------------------------------------------------------------

def test_normalize_cost_analysis_shapes():
    assert devprof.normalize_cost_analysis(None) == {}
    assert devprof.normalize_cost_analysis("garbage") == {}
    assert devprof.normalize_cost_analysis({"flops": 2}) == {"flops": 2.0}
    # newer jax: list of per-computation dicts -> numeric values summed
    out = devprof.normalize_cost_analysis(
        [{"flops": 2, "bytes accessed": 8.0, "label": "x"},
         {"flops": 3, "other": True}])
    assert out == {"flops": 5.0, "bytes accessed": 8.0}
    assert devprof.normalize_cost_analysis([]) == {}
    assert devprof.normalize_cost_analysis([None, {"a": 1}]) == {"a": 1.0}


def test_cost_model_uses_shared_normalizer():
    from paddle_tpu.cost_model import CostModel

    data = CostModel().static_cost_data(
        lambda a, b: jnp.matmul(a, b).sum(),
        (jnp.ones((16, 16)), jnp.ones((16, 16))))
    assert data["flops"] > 0
    assert isinstance(data["raw"], dict)


# ---------------------------------------------------------------------------
# memory/cost report on the bench MLP step
# ---------------------------------------------------------------------------

def test_device_report_memory_breakdown_sums_to_peak():
    step, x, y = _mlp_step()
    rep = step.device_report(x, y)
    assert rep is devprof.get_report("train_step")
    assert rep.flops > 0
    assert rep.bytes_accessed > 0
    md = rep.memory.as_dict()
    assert md["peak_bytes"] > 0
    assert (md["argument_bytes"] + md["output_bytes"] + md["temp_bytes"]
            + md["generated_code_bytes"] - md["alias_bytes"]
            == md["peak_bytes"])
    # single device: no interconnect traffic
    assert not rep.collectives
    assert rep.comm_bytes == 0
    assert rep.comm_fraction == 0.0
    assert "train_step" in rep.table()


@pytest.fixture
def _no_persistent_compile_cache():
    """Executables deserialized from the persistent XLA:CPU compile cache
    report ``alias_size_in_bytes=0`` in ``memory_analysis()`` (fresh
    in-process compiles report the real donated-alias size) — so the alias
    assertion below must compile fresh. The breakdown identity
    (arg+out+temp+code−alias == peak) holds either way."""
    import jax

    from jax._src import compilation_cache

    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    # flipping the config alone is NOT enough: the cache object was
    # initialized at conftest import and keeps serving the old dir —
    # reset it, and drop in-process executables an earlier test may have
    # deserialized (alias-less) from disk
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()  # re-attach the restored dir lazily


def test_device_report_safe_on_donated_inputs(_no_persistent_compile_cache):
    """Harvest lowers from shapes only — works after the real batch was
    donated/consumed by the step."""
    step, x, y = _mlp_step(donate_inputs=True)
    step(x, y)  # consumes x/y device buffers
    rep = step.device_report(x, y)
    assert rep.memory.peak_bytes > 0
    # state donation aliases params/accumulators into outputs -> nonzero
    # alias segment (x/y themselves can't alias: no same-shape output)
    assert rep.memory.alias_bytes > 0


def test_auto_harvest_on_first_compile_registers_telemetry():
    telemetry.enable()
    step, x, y = _mlp_step()
    step(x, y)  # first call compiles -> auto-harvest
    rep = devprof.get_report("train_step")
    assert rep is not None and rep.flops > 0
    g = telemetry.get_telemetry().gauges()
    assert g["hbm.peak_bytes"] == rep.memory.peak_bytes
    assert g["cost.flops"] == rep.flops
    assert g["comm.fraction"] == 0.0
    # once per step object: a second call must not re-harvest
    devprof.clear_reports()
    step(x, y)
    assert devprof.get_report("train_step") is None


def test_auto_harvest_does_not_perturb_compile_counts():
    """The harvest lowers through its own jit identity: the step's
    trace cache must not gain entries, or recompile telemetry would
    under-count (the lazy-accumulator contract from PR 2/3)."""
    telemetry.enable()
    step, x, y = _mlp_step()
    for _ in range(3):
        step(x, y)
    assert telemetry.get_telemetry().compile_counts() == {"train_step": 1}
    assert telemetry.summary()["recompile_count"] == 0


def test_disabled_auto_harvest():
    telemetry.enable()
    devprof.enable_auto_harvest(False)
    try:
        step, x, y = _mlp_step()
        step(x, y)
        assert devprof.get_report("train_step") is None
    finally:
        devprof.enable_auto_harvest(True)


# ---------------------------------------------------------------------------
# collective attribution — dryrun-shaped configs
# ---------------------------------------------------------------------------

def test_collectives_gspmd_dp_mp():
    """dp×mp GSPMD program (sharded batch, TP-sharded weight): the
    compiled HLO carries the partitioner-inserted collectives, attributed
    to the dp / mp mesh axes."""
    mesh = build_mesh({"dp": 2, "mp": 2})

    def fn(x, w):
        y = x._value @ w._value
        y = jax.lax.with_sharding_constraint(
            y, NamedSharding(mesh, P("dp", None)))
        return (y * y).sum()

    step = CompiledStep(fn, stateful=(), donate_state=False)
    x = Tensor(jax.device_put(jnp.ones((8, 16)),
                              NamedSharding(mesh, P("dp", None))))
    w = Tensor(jax.device_put(jnp.ones((16, 32)),
                              NamedSharding(mesh, P(None, "mp"))))
    rep = step.device_report(x, w)
    assert rep.comm_source == "hlo"
    axes = rep.collectives.axes()
    assert any("dp" in a for a in axes), rep.collectives.as_dict()
    assert any("mp" in a for a in axes), rep.collectives.as_dict()
    assert rep.comm_bytes > 0
    assert 0.0 < rep.comm_fraction < 1.0


def test_collectives_jaxpr_explicit_shard_map():
    """Explicit shard_map collectives: exact per-axis counts and the ring
    bytes-moved model (psum = 2(S−1)/S × local bytes)."""
    from jax.experimental.shard_map import shard_map

    mesh = build_mesh({"dp": 2, "mp": 2})

    def fn(x):
        def inner(v):
            s = jax.lax.psum(v, "dp")
            w = jax.lax.ppermute(v, "mp", [(0, 1), (1, 0)])
            return s + w

        v = shard_map(inner, mesh=mesh, in_specs=P("dp", "mp"),
                      out_specs=P("dp", "mp"), check_rep=False)(x._value)
        return v.sum()

    step = CompiledStep(fn, stateful=(), donate_state=False)
    x = Tensor(jax.device_put(jnp.ones((8, 16), jnp.float32),
                              NamedSharding(mesh, P("dp", "mp"))))
    rep = step.device_report(x)
    tr = rep.collectives_traced.as_dict()
    # local shard (4, 8) f32 = 128 B; S=2 for both axes
    assert tr["dp"]["prims"] == {"psum": 1}
    assert tr["dp"]["bytes"] == 2 * (2 - 1) / 2 * 128
    assert tr["mp"]["prims"] == {"ppermute": 1}
    assert tr["mp"]["bytes"] == 1.0 * 128
    # the HLO (authoritative) view sees the same traffic classes
    assert rep.comm_bytes > 0


def test_collectives_moe_all_to_all_expert_parallel():
    """The MULTICHIP MoE dryrun config: stacked expert params sharded over
    the 8-way mesh, dispatch/combine lowering to expert all_to_all —
    nonzero collective bytes attributed to the expert-parallel axis."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.data_parallel import shard_batch
    from paddle_tpu.incubate.distributed.models.moe import MoELayer

    n = 8
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs["dp_degree"] = n
    fleet.init(is_collective=True, strategy=strategy)
    hcg = fleet.get_hybrid_communicate_group()

    d, n_exp, tokens = 8, n, 4 * n
    with unique_name.guard():
        paddle.seed(3)
        experts = [paddle.nn.Sequential(paddle.nn.Linear(d, d),
                                        paddle.nn.ReLU(),
                                        paddle.nn.Linear(d, d))
                   for _ in range(n_exp)]
        moe = MoELayer(d_model=d, experts=experts, gate={"type": "gshard"},
                       moe_group=hcg.get_data_parallel_group(),
                       capacity_factor=float(n_exp))
    opt = paddle.optimizer.SGD(learning_rate=1e-2,
                               parameters=moe.parameters())

    def train_step(xb):
        out = moe(xb)
        loss = (out - 1.0).square().mean() + 0.01 * moe.aux_loss
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = CompiledStep(train_step, stateful=[moe, opt], donate_state=True)
    xs = np.random.RandomState(5).randn(tokens, d).astype(np.float32)
    x = shard_batch(Tensor(xs), hcg.get_data_parallel_group())
    rep = step.device_report(x)
    assert rep.comm_source == "hlo"
    dp_axes = {a: st for a, st in rep.collectives.as_dict().items()
               if "dp" in a}
    assert dp_axes, rep.collectives.as_dict()
    assert sum(st["bytes"] for st in dp_axes.values()) > 0
    assert rep.comm_fraction > 0


def test_collectives_zero_on_single_device():
    step, x, y = _mlp_step()
    rep = step.device_report(x, y)
    assert rep.collectives.total_count == 0
    assert rep.collectives_traced.total_count == 0


def test_hlo_group_decoding():
    assert devprof._decode_groups("{{0,1},{2,3}}") == [[0, 1], [2, 3]]
    assert devprof._decode_groups("{}") is None
    # iota form: [groups, size]<=[dims]T(perm)
    assert devprof._decode_groups("[2,2]<=[4]") == [[0, 1], [2, 3]]
    assert devprof._decode_groups("[2,2]<=[2,2]T(1,0)") == [[0, 2], [1, 3]]


def test_hlo_explicit_brace_groups_attributed_per_axis():
    """Regression (found by the ISSUE 7 shard-lint crosscheck): the line
    regex used to truncate `{{0,1},{2,3}}` at the FIRST closing brace, so
    explicit-brace groups decoded to None = "all devices" — mislabeling a
    2-wide mp all-reduce as dp+mp and mispricing it with S=4."""
    mesh = build_mesh({"dp": 2, "mp": 2})
    line = ("%all-reduce = f32[8,32]{1,0} all-reduce(f32[8,32]{1,0} "
            "%dot.1), channel_id=1, replica_groups={{0,1},{2,3}}, "
            "use_global_device_ids=true, to_apply=%add.clone")
    st = devprof.collectives_from_hlo(line, mesh=mesh)
    # groups {0,1}/{2,3} vary the mp coordinate only; S=2 ⇒ factor 1
    assert st.as_dict() == {"mp": {"count": 1, "bytes": 8 * 32 * 4.0,
                                   "prims": {"all-reduce": 1}}}
    line_dp = line.replace("{{0,1},{2,3}}", "{{0,2},{1,3}}")
    st2 = devprof.collectives_from_hlo(line_dp, mesh=mesh)
    assert list(st2.as_dict()) == ["dp"]


def test_hlo_reduce_scatter_sync_prices_result_shard():
    """Ring model: each device ships (s-1) result-shard-sized chunks. The
    sync op's shape IS the local shard."""
    mesh = build_mesh({"dp": 4})
    line = ("%reduce-scatter = f32[4,32]{1,0} reduce-scatter(f32[16,32]{1,0} "
            "%param.1), channel_id=2, replica_groups={{0,1,2,3}}, "
            "use_global_device_ids=true, dimensions={0}, to_apply=%add")
    st = devprof.collectives_from_hlo(line, mesh=mesh).as_dict()
    assert st["dp"]["prims"] == {"reduce-scatter": 1}
    assert st["dp"]["bytes"] == 3 * (4 * 32 * 4)  # (s-1) x result shard


def test_hlo_reduce_scatter_start_rescaled_to_shard():
    """Regression: the async -start op's result tuple carries the INPUT
    buffer (s x the shard) as its largest element; pricing must rescale by
    the group size so sync and async forms agree."""
    mesh = build_mesh({"dp": 4})
    line = ("%reduce-scatter-start = ((f32[16,32]{1,0}), f32[4,32]{1,0}) "
            "reduce-scatter-start(f32[16,32]{1,0} %param.1), channel_id=2, "
            "replica_groups={{0,1,2,3}}, use_global_device_ids=true, "
            "dimensions={0}, to_apply=%add")
    st = devprof.collectives_from_hlo(line, mesh=mesh).as_dict()
    assert st["dp"]["bytes"] == 3 * (4 * 32 * 4)  # == the sync price


def test_hlo_all_gather_start_max_not_sum():
    """The -start tuple repeats input+output; summing would double-count.
    max picks the gathered result, priced (s-1)/s."""
    mesh = build_mesh({"dp": 4})
    sync = ("%all-gather = f32[16,32]{1,0} all-gather(f32[4,32]{1,0} "
            "%param.1), channel_id=3, replica_groups={{0,1,2,3}}, "
            "use_global_device_ids=true, dimensions={0}")
    start = ("%all-gather-start = (f32[4,32]{1,0}, f32[16,32]{1,0}) "
             "all-gather-start(f32[4,32]{1,0} %param.1), channel_id=3, "
             "replica_groups={{0,1,2,3}}, use_global_device_ids=true, "
             "dimensions={0}")
    want = (3 / 4) * (16 * 32 * 4)
    assert devprof.collectives_from_hlo(
        sync, mesh=mesh).as_dict()["dp"]["bytes"] == want
    assert devprof.collectives_from_hlo(
        start, mesh=mesh).as_dict()["dp"]["bytes"] == want


def test_hlo_all_reduce_start_matches_sync():
    mesh = build_mesh({"dp": 2})
    sync = ("%all-reduce = f32[8,32]{1,0} all-reduce(f32[8,32]{1,0} "
            "%dot.1), channel_id=1, replica_groups={{0,1}}, "
            "use_global_device_ids=true, to_apply=%add")
    start = ("%all-reduce-start = (f32[8,32]{1,0}, f32[8,32]{1,0}) "
             "all-reduce-start(f32[8,32]{1,0} %dot.1), channel_id=1, "
             "replica_groups={{0,1}}, use_global_device_ids=true, "
             "to_apply=%add")
    want = (2 * 1 / 2) * (8 * 32 * 4)  # 2(s-1)/s, s=2
    assert devprof.collectives_from_hlo(
        sync, mesh=mesh).as_dict()["dp"]["bytes"] == want
    assert devprof.collectives_from_hlo(
        start, mesh=mesh).as_dict()["dp"]["bytes"] == want


def test_hlo_collective_broadcast_decoded():
    """collective-broadcast (GSPMD emits it for replicating a sharded
    buffer) must be decoded, not silently dropped from the comm price."""
    mesh = build_mesh({"dp": 4})
    line = ("%collective-broadcast = f32[8,32]{1,0} collective-broadcast("
            "f32[8,32]{1,0} %param.1), channel_id=5, "
            "replica_groups={{0,1,2,3}}")
    st = devprof.collectives_from_hlo(line, mesh=mesh).as_dict()
    assert st["dp"]["prims"] == {"collective-broadcast": 1}
    assert st["dp"]["bytes"] == (3 / 4) * (8 * 32 * 4)


def test_hlo_int8_wire_priced_at_one_byte():
    """The int8 EF all-gather ships s8 on the wire — the pricer must use
    the element size from the HLO dtype, not assume fp32."""
    mesh = build_mesh({"dp": 4})
    line = ("%all-gather.9 = s8[16,256]{1,0} all-gather(s8[4,256]{1,0} "
            "%bitcast.3), channel_id=7, replica_groups=[1,4]<=[4], "
            "use_global_device_ids=true, dimensions={0}")
    st = devprof.collectives_from_hlo(line, mesh=mesh).as_dict()
    assert st["dp"]["bytes"] == (3 / 4) * (16 * 256 * 1)


# ---------------------------------------------------------------------------
# pipeline bubble + straggler metrics
# ---------------------------------------------------------------------------

def test_pipeline_bubble_fraction_analytic():
    assert devprof.pipeline_bubble_fraction(2, 2) == pytest.approx(1 / 3)
    assert devprof.pipeline_bubble_fraction(8, 4) == pytest.approx(3 / 11)
    assert devprof.pipeline_bubble_fraction(4, 1) == 0.0  # no pipeline
    assert devprof.pipeline_bubble_fraction(0, 4) == 0.0


def test_bubble_from_synthetic_microbatch_spans():
    # 2 ranks, perfect 1F1B staircase: each busy 2 of the 3-tick window
    spans = {0: [(0.0, 1.0), (1.0, 2.0)], 1: [(1.0, 2.0), (2.0, 3.0)]}
    out = devprof.bubble_from_spans(spans)
    assert out["window_s"] == pytest.approx(3.0)
    assert out["per_rank"][0] == pytest.approx(1 / 3)
    assert out["per_rank"][1] == pytest.approx(1 / 3)
    assert out["bubble_fraction"] == pytest.approx(1 / 3)
    # matches the analytic schedule bubble for M=2, pp=2
    assert out["bubble_fraction"] == pytest.approx(
        devprof.pipeline_bubble_fraction(2, 2))
    # tuple-list input form
    out2 = devprof.bubble_from_spans(
        [(0, 0.0, 1.0), (0, 1.0, 2.0), (1, 1.0, 2.0), (1, 2.0, 3.0)])
    assert out2["bubble_fraction"] == pytest.approx(1 / 3)
    assert devprof.bubble_from_spans({})["bubble_fraction"] == 0.0


def test_elastic_heartbeat_carries_step_time_and_finds_stragglers(tmp_path):
    from paddle_tpu.distributed.elastic import ElasticManager

    managers = [ElasticManager(elastic_dir=str(tmp_path), rank=r,
                               world_size=3, timeout=30.0)
                for r in range(3)]
    managers[0].heartbeat(step_time_s=0.10)
    managers[1].heartbeat(step_time_s=0.11)
    managers[2].heartbeat(step_time_s=0.35)  # sick host: 3x the median
    times = managers[0].step_times()
    assert times == {0: 0.10, 1: 0.11, 2: 0.35}
    assert managers[0].stragglers(ratio=1.5) == [2]
    assert managers[0].stragglers(ratio=4.0) == []
    # healthy poll still reports nothing to restart
    assert managers[0].watch() is None


def test_elastic_heartbeat_pulls_step_gauge(tmp_path):
    from paddle_tpu.distributed.elastic import ElasticManager

    telemetry.enable()
    tm = telemetry.get_telemetry()
    tm.step_begin()
    with telemetry.phase_span("dispatch"):
        pass
    tm.step_end()
    assert "step.time_s" in tm.gauges()
    m = ElasticManager(elastic_dir=str(tmp_path), rank=0, world_size=1)
    m.heartbeat()
    assert 0 in m.step_times()


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

def test_injected_dispatch_oom_dumps_forensics(capfd):
    from paddle_tpu.fault import inject

    telemetry.enable()
    step, x, y = _mlp_step()
    step(x, y)  # compile + auto-harvest: forensics can cite the breakdown
    inject.disarm_all()
    inject.arm("oom", "dispatch", at=1)  # next dispatch (hits count
    # from arming, not from process start)
    try:
        with pytest.raises(Exception) as ei:
            step(x, y)
    finally:
        inject.disarm_all()
    # the original error is re-raised, not swallowed
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    fo = devprof.last_oom_report()
    assert fo is not None and fo.step_name == "train_step"
    # ranked report went to stderr instead of a bare XLA error
    err = capfd.readouterr().err
    assert "OOM forensics" in err
    assert "memory breakdown" in err
    assert "donation" in err
    d = fo.as_dict()
    assert d["memory"]["peak_bytes"] > 0
    assert d["donation"] == {"donate_state": True, "donate_inputs": False,
                             "donate_paths": []}
    assert d["batch"] and d["batch"][0]["nbytes"] > 0
    assert d["state"] and d["state"][0]["nbytes"] >= d["state"][-1]["nbytes"]
    assert telemetry.get_telemetry().counters().get("oom.count") == 1


def test_oom_forensics_json_round_trip(tmp_path, monkeypatch, capfd):
    from paddle_tpu.fault import inject

    monkeypatch.setenv(devprof.OOM_DUMP_ENV, str(tmp_path))
    step, x, y = _mlp_step()
    inject.disarm_all()
    inject.arm("oom", "dispatch", at=1)  # before any compile: no breakdown
    try:
        with pytest.raises(inject.InjectedResourceExhausted):
            step(x, y)
    finally:
        inject.disarm_all()
    capfd.readouterr()
    path = tmp_path / "oom_train_step.json"
    assert path.exists()
    loaded = devprof.OOMForensics.from_dict(json.loads(path.read_text()))
    assert loaded.step_name == "train_step"
    assert loaded.memory is None  # step never compiled -> unavailable
    assert loaded.batch[0]["shape"] == [8, 16] or \
        tuple(loaded.batch[0]["shape"]) == (8, 16)
    assert "unavailable" in loaded.report()


def test_non_oom_dispatch_errors_pass_through():
    from paddle_tpu.fault import inject

    step, x, y = _mlp_step()
    inject.disarm_all()
    inject.arm("error", "dispatch", at=1)
    try:
        with pytest.raises(inject.TransientError):
            step(x, y)
    finally:
        inject.disarm_all()
    assert devprof.last_oom_report() is None or \
        "transient" not in devprof.last_oom_report().error


# ---------------------------------------------------------------------------
# telemetry surface: percentiles, device section, loader gauges
# ---------------------------------------------------------------------------

def test_phase_stats_percentiles_and_report_columns():
    telemetry.enable()
    tm = telemetry.get_telemetry()
    for i in range(20):
        tm.add_phase("dispatch", 0, (i + 1) * 1_000_000)  # 1..20 ms
    st = telemetry.summary()["phases"]["dispatch"]
    assert st["p50"] == pytest.approx(0.010, abs=2e-3)
    assert st["p95"] == pytest.approx(0.019, abs=2e-3)
    table = tm.report(file=open(os.devnull, "w"))
    assert "P50(ms)" in table and "P95(ms)" in table


def test_report_renders_device_stats_section():
    telemetry.enable()
    step, x, y = _mlp_step()
    step(x, y)
    table = telemetry.get_telemetry().report(file=open(os.devnull, "w"))
    assert "device stats:" in table
    assert "hbm.peak_bytes" in table


def test_device_loader_clears_gauges_on_shutdown():
    from paddle_tpu.io import DeviceLoader

    telemetry.enable()
    loader = DeviceLoader([(np.zeros((2, 2), np.float32),)
                           for _ in range(3)])
    for _ in loader:
        pass
    assert "device_loader.queue_depth" not in \
        telemetry.get_telemetry().gauges()
    # explicit shutdown path too
    it = iter(loader)
    next(it)
    loader.shutdown()
    assert "device_loader.queue_depth" not in \
        telemetry.get_telemetry().gauges()


def test_export_scalars_includes_percentiles_and_device_gauges(tmp_path):
    from paddle_tpu.utils.log_writer import LogWriter

    telemetry.enable()
    step, x, y = _mlp_step()
    step(x, y)
    with LogWriter(str(tmp_path), file_name="t.jsonl") as w:
        telemetry.get_telemetry().export_scalars(w, step=1)
    tags = {json.loads(l)["tag"]
            for l in (tmp_path / "t.jsonl").read_text().splitlines()}
    assert "telemetry/phase/compile/p50_s" in tags
    assert "telemetry/phase/compile/p95_s" in tags
    assert "telemetry/gauge/hbm.peak_bytes" in tags
    assert "telemetry/gauge/comm.fraction" in tags


# ---------------------------------------------------------------------------
# tools integration
# ---------------------------------------------------------------------------

def test_mem_report_tool_renders_harvest(tmp_path, capsys):
    import mem_report
    from paddle_tpu.utils.log_writer import LogWriter

    telemetry.enable()
    step, x, y = _mlp_step()
    step(x, y)
    tm = telemetry.get_telemetry()
    tm.inc("comm.bytes.dp", 4096)
    tm.inc("comm.count.dp", 2)
    with LogWriter(str(tmp_path), file_name="m.jsonl") as w:
        tm.export_scalars(w, step=1)
    assert mem_report.main([str(tmp_path / "m.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "HBM peak" in out
    assert "argument_bytes" in out
    assert "dp" in out
    # no device stats -> exit 1
    (tmp_path / "empty.jsonl").write_text(
        json.dumps({"tag": "train/loss", "value": 1.0}) + "\n")
    assert mem_report.main([str(tmp_path / "empty.jsonl")]) == 1


def test_telemetry_report_tool_device_section(tmp_path, capsys):
    import telemetry_report
    from paddle_tpu.utils.log_writer import LogWriter

    telemetry.enable()
    step, x, y = _mlp_step()
    step(x, y)
    with LogWriter(str(tmp_path), file_name="t.jsonl") as w:
        telemetry.get_telemetry().export_scalars(w, step=1)
    assert telemetry_report.main([str(tmp_path / "t.jsonl")]) == 0
    out = capsys.readouterr().out
    assert "device stats:" in out
    assert "P50(ms)" in out


# ---------------------------------------------------------------------------
# hapi / Engine surfaces
# ---------------------------------------------------------------------------

def test_hapi_device_stats_logger_callback(capsys):
    from paddle_tpu.hapi.callbacks import DeviceStatsLogger

    with unique_name.guard():
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
    model = paddle.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    data = [(rng.rand(4, 8).astype(np.float32),
             rng.randint(0, 4, (4, 1)).astype(np.int64))
            for _ in range(4)]
    cb = DeviceStatsLogger()
    model.fit(data, epochs=1, verbose=0, callbacks=[cb])
    assert cb.report is not None
    assert cb.report.memory.peak_bytes > 0
    assert model.device_report() is cb.report
    assert "device cost report" in capsys.readouterr().out
    assert not telemetry.enabled()  # callback restored the flag


def test_engine_device_report_accessor():
    from paddle_tpu.distributed.auto_parallel.engine import Engine
    from paddle_tpu.distributed.auto_parallel.process_mesh import ProcessMesh
    from paddle_tpu.io import Dataset

    class _DS(Dataset):
        def __init__(self):
            rng = np.random.RandomState(0)
            self.x = rng.rand(16, 8).astype(np.float32)
            self.y = rng.randint(0, 4, (16, 1)).astype(np.int64)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return len(self.x)

    with unique_name.guard():
        paddle.seed(0)
        net = paddle.nn.Linear(8, 4)
    telemetry.enable()
    engine = Engine(model=net, loss=paddle.nn.CrossEntropyLoss(),
                    optimizer=paddle.optimizer.SGD(
                        learning_rate=0.1, parameters=net.parameters()),
                    process_mesh=ProcessMesh(np.arange(8), dim_names=["dp"]))
    engine.fit(_DS(), batch_size=8, epochs=1)
    rep = engine.device_report()
    assert rep is not None
    assert rep.memory.peak_bytes > 0
    # dp=8 data-parallel training: the gradient all-reduce shows up as
    # dp-axis collective traffic in the compiled HLO
    assert any("dp" in a for a in rep.collectives.axes()), \
        rep.collectives.as_dict()
    assert rep.comm_fraction > 0
