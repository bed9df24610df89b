"""Runtime telemetry layer (profiler/telemetry.py): phase timeline,
pipeline counters, recompile detection, exporters, and the
zero-overhead-when-disabled contract across DeviceLoader / CompiledStep /
AsyncMetricBuffer / Model.fit."""
import glob
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.hapi.callbacks import TelemetryLogger
from paddle_tpu.io import Dataset
from paddle_tpu.io.device_loader import DeviceLoader
from paddle_tpu.jit.functionalize import CompiledStep
from paddle_tpu.metric import AsyncMetricBuffer
from paddle_tpu.nn import CrossEntropyLoss
from paddle_tpu.profiler import telemetry


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.disable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


def _compiled_linear_step(in_dim=3):
    paddle.seed(0)
    lin = paddle.nn.Linear(in_dim, 3)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=lin.parameters())

    def train_step(x):
        loss = lin(x).square().mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    return CompiledStep(train_step, stateful=[lin, opt])


# ---------------------------------------------------------------------------
# registry basics
# ---------------------------------------------------------------------------

def test_disabled_by_default_and_null_span_singleton():
    assert not telemetry.enabled()
    # the disabled-path span is a shared no-op object: no allocation, no
    # timing, no locking — the zero-overhead contract
    s1 = telemetry.phase_span("data_wait")
    s2 = telemetry.phase_span("dispatch", attrs={"k": 1}, key="train_step")
    assert s1 is s2
    # ... the very singleton tracing hands out: one no-op for both systems
    from paddle_tpu.profiler import tracing
    assert s1 is tracing.NULL_SPAN
    with s1:
        pass
    tm = telemetry.get_telemetry()
    assert tm.counters() == {}
    assert tm.steps() == []
    assert telemetry.summary()["phases"] == {}


def test_counters_gauges_histograms_and_reset():
    telemetry.enable()
    tm = telemetry.get_telemetry()
    tm.inc("a")
    tm.inc("a", 2)
    tm.set_gauge("g", 7.5)
    tm.observe("lat", 0.25)
    tm.observe("lat", 0.75)
    assert tm.counters()["a"] == 3
    assert tm.gauges()["g"] == 7.5
    stat = tm.get("lat")
    assert stat["count"] == 2 and stat["sum"] == 1.0
    telemetry.reset()
    assert tm.counters() == {} and tm.gauges() == {} and tm.get("lat") == {}


def test_phase_span_and_step_records():
    telemetry.enable()
    telemetry.step_begin()
    with telemetry.phase_span("data_wait"):
        time.sleep(0.002)
    with telemetry.phase_span("dispatch"):
        pass
    telemetry.step_end()
    recs = telemetry.get_telemetry().steps()
    assert len(recs) == 1
    assert recs[0].phases["data_wait"] >= 0.002
    assert "dispatch" in recs[0].phases
    assert recs[0].wall_s >= recs[0].phases["data_wait"]
    # empty records are dropped, not ring-polluting
    telemetry.step_begin()
    telemetry.step_end()
    assert len(telemetry.get_telemetry().steps()) == 1


def test_ring_buffer_bounded():
    telemetry.enable(ring_size=8)
    try:
        for _ in range(50):
            telemetry.step_begin()
            with telemetry.phase_span("dispatch"):
                pass
        telemetry.step_end()
        tm = telemetry.get_telemetry()
        assert len(tm.steps()) == 8
        assert len(tm.chrome_spans()) <= 8 * 8
        # histograms still saw every span
        assert tm.get("phase.dispatch")["count"] == 50
    finally:
        telemetry.enable(ring_size=1024)  # restore default bound


# ---------------------------------------------------------------------------
# DeviceLoader stall accounting
# ---------------------------------------------------------------------------

def test_device_loader_stall_accounting():
    telemetry.enable()

    def slow_source():
        for i in range(4):
            time.sleep(0.01)  # slower than the consumer: forced misses
            yield (np.full((2, 4), i, np.float32),)

    for _ in DeviceLoader(slow_source()):
        pass
    c = telemetry.get_telemetry().counters()
    assert c["device_loader.prefetch_miss"] >= 3
    assert c["device_loader.stall_s"] >= 0.02
    assert c["device_loader.batches_staged"] == 4
    # 4 batches x 2x4 float32
    assert c["device_loader.bytes_staged"] == 4 * 2 * 4 * 4
    # a finished loader retires its point-in-time gauges (queue depth)
    # so the next report() doesn't show stale device stats; cumulative
    # counters (asserted above) survive
    assert "device_loader.queue_depth" not in \
        telemetry.get_telemetry().gauges()
    # the waits landed in the data_wait phase histogram
    assert telemetry.summary()["phases"]["data_wait"]["count"] >= 4


def test_device_loader_prefetch_hits_with_slow_consumer():
    telemetry.enable()
    batches = [(np.zeros((2, 2), np.float32),) for _ in range(5)]
    for _ in DeviceLoader(batches, buffer_size=4):
        time.sleep(0.005)  # let the stager run ahead
    c = telemetry.get_telemetry().counters()
    assert c.get("device_loader.prefetch_hit", 0) >= 2


def test_device_loader_untouched_when_disabled():
    assert not telemetry.enabled()
    for _ in DeviceLoader([(np.zeros((2, 2), np.float32),) for _ in range(3)]):
        pass
    assert telemetry.get_telemetry().counters() == {}
    assert telemetry.get_telemetry().steps() == []


# ---------------------------------------------------------------------------
# CompiledStep compile/dispatch attribution + recompile detection
# ---------------------------------------------------------------------------

def test_compiled_step_compile_then_dispatch():
    telemetry.enable()
    step = _compiled_linear_step()
    x = paddle.to_tensor(np.random.randn(4, 3).astype(np.float32))
    step(x)
    tm = telemetry.get_telemetry()
    first_compiles = tm.counters()["compile.count"]
    assert first_compiles >= 1
    step(x)
    step(x)
    c = tm.counters()
    assert c["compile.count"] == first_compiles  # cached: no retrace
    assert telemetry.summary()["phases"]["dispatch"]["count"] >= 2


def test_compile_seconds_split_sums_to_the_compile_phase():
    """jax.monitoring's trace / lowering / backend durations are
    attributed to the step whose call is in flight; with the rest of the
    call (``first_run_s``) they close to what the one ``compile`` phase
    measured — the old, unsplit ``compile_seconds``."""
    telemetry.enable()
    step = _compiled_linear_step()
    x = paddle.to_tensor(np.random.randn(4, 3).astype(np.float32))
    step(x)
    step(x)
    tm = telemetry.get_telemetry()
    parts = tm.compile_seconds()["train_step"]
    assert set(parts) == set(telemetry.COMPILE_PARTS)
    assert all(v >= 0.0 for v in parts.values())
    # JAX did trace, lower and compile (or load) inside that call
    assert parts["trace_s"] > 0 and parts["lower_s"] > 0
    assert parts["backend_s"] > 0
    compiled = [r for r in tm.phase_records("compile", key="train_step")]
    assert len(compiled) == tm.compile_counts()["train_step"]
    wall = sum(b - a for _, a, b, _, _ in compiled) / 1e9
    assert sum(parts.values()) == pytest.approx(wall, rel=1e-6)
    # the second call hit the executable: a keyed `dispatch`, no new part
    assert tm.phase_records("dispatch", key="train_step")
    assert tm.compile_seconds()["train_step"] == parts


def test_split_compile_gives_each_instant_to_one_part():
    ms = 1_000_000
    watch = {"trace_s": [(0, 40 * ms), (5 * ms, 10 * ms)],  # nested helper
             "lower_s": [(40 * ms, 70 * ms), (50 * ms, 55 * ms)],
             "backend_s": [(70 * ms, 90 * ms)], "cache_hits": 0}
    watch["trace_s"].append((50 * ms, 55 * ms))  # a trace made while lowering
    split = telemetry._split_compile(watch, 0, 100 * ms)
    assert split == pytest.approx({"trace_s": 0.040, "lower_s": 0.030,
                                   "backend_s": 0.020, "first_run_s": 0.010})
    # events of another call's time (before the start) are clipped away
    watch["backend_s"].append((-30 * ms, -10 * ms))
    assert telemetry._split_compile(watch, 0, 100 * ms) == pytest.approx(split)


def test_persistent_cache_hit_is_counted(tmp_path):
    import jax

    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
            jax.config.jax_persistent_cache_min_entry_size_bytes)
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        telemetry.enable()
        x = paddle.to_tensor(np.ones((5, 7), np.float32))
        _compiled_linear_step(in_dim=7)(x)   # cold: writes the entry
        tm = telemetry.get_telemetry()
        cold = tm.counters().get("compile.cache_hits", 0)
        _compiled_linear_step(in_dim=7)(x)   # same program: loads it
        assert tm.counters().get("compile.cache_hits", 0) > cold
    finally:
        jax.config.update("jax_compilation_cache_dir", prev[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          prev[2])
        cc.reset_cache()


def test_compiled_step_lowers_under_its_step_name():
    """The program's name in a device trace and in the compile cache's
    key is the step's own, not the one closure name every step shared."""
    step = _compiled_linear_step()
    x = paddle.to_tensor(np.random.randn(4, 3).astype(np.float32))
    assert step.name == "train_step"
    assert "module @jit_train_step" in step.lower(x).as_text()


def test_phase_spans_reach_a_live_profiler_trace(tmp_path):
    """Sink (c): under any live jax.profiler session the program's phases
    are ``paddle_tpu:<name>`` annotations in the xplane, on the profiler's
    own clock, nested as the phases nest."""
    import jax

    telemetry.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.phase_span("serve.tick"):
            with telemetry.phase_span("serve.decode_readback"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[-1]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(telemetry.ANNOTATION_PREFIX):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    tick = found["paddle_tpu:serve.tick"]
    wait = found["paddle_tpu:serve.decode_readback"]
    assert tick[0] <= wait[0] <= wait[1] <= tick[1]
    assert wait[1] - wait[0] >= 2_000_000
    # and the same boundary call filed the phase in telemetry's own ring
    assert [r[0] for r in telemetry.get_telemetry().phase_records()] == [
        "serve.decode_readback", "serve.tick"]


def test_open_record_keeps_index_owner_and_raw_spans():
    telemetry.enable()
    tm = telemetry.get_telemetry()
    telemetry.step_begin()            # a train record is open underneath
    rec = tm.open_record("serve.tick", index=41, owner=7)
    with telemetry.phase_span("serve.tick"):
        with telemetry.phase_span("serve.expire"):
            pass
    tm.close_record(rec)
    with telemetry.phase_span("dispatch"):
        pass                          # lands in the train record again
    telemetry.step_end()
    (tick,) = tm.steps(kind="serve.tick")
    assert (tick.index, tick.owner) == (41, 7)
    assert [n for n, _, _ in tick.spans] == ["serve.expire", "serve.tick"]
    assert set(tick.phases) == {"serve.expire", "serve.tick"}
    assert tm.steps(kind="serve.tick", owner=8) == []
    (train,) = tm.steps(kind="step")
    assert set(train.phases) == {"dispatch"}
    # the elastic heartbeat's step-time gauge is a train step's only
    assert tm.gauges()["step.time_s"] == train.wall_s
    assert tick.as_dict()["kind"] == "serve.tick"


def test_recompile_warning_on_shape_churn():
    telemetry.enable(recompile_warn_threshold=2)
    try:
        step = _compiled_linear_step()
        with pytest.warns(RuntimeWarning, match="recompilation churn"):
            for n in range(3, 7):  # every batch a new shape -> retrace each
                step(paddle.to_tensor(
                    np.random.randn(n, 3).astype(np.float32)))
        assert telemetry.get_telemetry().compile_counts()["train_step"] >= 3
        assert telemetry.summary()["recompile_count"] >= 2
    finally:
        telemetry.enable(recompile_warn_threshold=3)


def test_recompile_warning_fires_once():
    telemetry.enable(recompile_warn_threshold=1)
    try:
        step = _compiled_linear_step()
        import warnings as w

        with w.catch_warnings(record=True) as caught:
            w.simplefilter("always")
            for n in range(3, 8):
                step(paddle.to_tensor(
                    np.random.randn(n, 3).astype(np.float32)))
        churn = [x for x in caught if "recompilation churn" in str(x.message)]
        assert len(churn) == 1
    finally:
        telemetry.enable(recompile_warn_threshold=3)


# ---------------------------------------------------------------------------
# AsyncMetricBuffer readback accounting
# ---------------------------------------------------------------------------

def test_async_buffer_readback_counters():
    telemetry.enable()
    buf = AsyncMetricBuffer()
    for v in (1.0, 2.0, 3.0):
        buf.append(paddle.to_tensor(np.float32(v)))
    assert buf.drain() == [1.0, 2.0, 3.0]
    c = telemetry.get_telemetry().counters()
    assert c["metric.fences"] == 1
    assert c["metric.scalars_read"] == 3
    assert telemetry.summary()["phases"]["readback"]["count"] == 1
    # empty drain is not a fence
    buf.drain()
    assert telemetry.get_telemetry().counters()["metric.fences"] == 1


# ---------------------------------------------------------------------------
# Model.fit end-to-end (acceptance criteria)
# ---------------------------------------------------------------------------

class _ToyDS(Dataset):
    def __init__(self, n=64, seed=0):
        rng = np.random.RandomState(seed)
        self.x = rng.randn(n, 8).astype(np.float32)
        w = rng.randn(8).astype(np.float32)
        self.y = (self.x @ w > 0).astype(np.int64)

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def _prepared_model():
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(8, 16), paddle.nn.ReLU(),
                               paddle.nn.Linear(16, 2))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=0.1,
                                parameters=net.parameters())
    model.prepare(opt, CrossEntropyLoss())
    return model


def test_model_fit_with_telemetry_logger(tmp_path, capsys):
    logdir = str(tmp_path / "telemetry")
    model = _prepared_model()
    cb = TelemetryLogger(log_dir=logdir, log_freq=2, print_report=True)
    model.fit(_ToyDS(), batch_size=16, epochs=2, verbose=0, callbacks=[cb])

    # JSONL scalars landed
    files = glob.glob(logdir + "/*.jsonl")
    assert files, "TelemetryLogger wrote no JSONL"
    tags = {json.loads(l)["tag"] for l in open(files[-1]) if l.strip()}
    assert any(t.startswith("telemetry/phase/data_wait") for t in tags)
    assert any(t.startswith("telemetry/phase/dispatch") for t in tags)
    assert "telemetry/counter/compile.count" in tags
    assert "telemetry/gauge/device_loader.queue_depth" in tags

    # report table: nonzero data_wait/dispatch, recompile counter, queue
    # stats (printed at train end by the callback)
    table = capsys.readouterr().out
    assert "data_wait" in table and "dispatch" in table
    assert "compile.count" in table
    assert "device_loader.prefetch_hit" in table or \
        "device_loader.prefetch_miss" in table
    s = telemetry.summary()
    assert s["phases"]["data_wait"]["sum"] > 0
    assert s["phases"]["dispatch"]["sum"] > 0
    assert s["counters"]["compile.count"] >= 1
    assert s["steps_recorded"] >= 8  # 2 epochs x 4 batches
    # the callback turned telemetry back off after the run
    assert not telemetry.enabled()


def test_model_fit_disabled_is_zero_overhead():
    """With telemetry disabled, the instrumented fit loop must do NO
    telemetry work: nothing recorded, no step records, no counters."""
    model = _prepared_model()
    model.fit(_ToyDS(), batch_size=16, epochs=1, verbose=0)
    tm = telemetry.get_telemetry()
    assert not telemetry.enabled()
    assert tm.counters() == {}
    assert tm.gauges() == {}
    assert tm.steps() == []
    assert tm.chrome_spans() == []
    assert telemetry.summary()["phases"] == {}
    # and the disabled-path guard itself is trivially cheap (no-op span +
    # flag check, generous bound to stay robust on loaded CI hosts)
    t0 = time.perf_counter()
    for _ in range(100_000):
        telemetry.enabled()
        telemetry.step_begin()
    dt = time.perf_counter() - t0
    assert dt < 1.0, f"disabled-path guard too slow: {dt:.3f}s / 100k calls"


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_export_scalars_and_report_tool_roundtrip(tmp_path):
    import os
    import subprocess
    import sys

    telemetry.enable()
    tm = telemetry.get_telemetry()
    for _ in range(3):
        telemetry.step_begin()
        for phase in telemetry.PHASES:
            with telemetry.phase_span(phase):
                pass
    telemetry.step_end()
    tm.inc("device_loader.prefetch_hit", 5)
    tm.set_gauge("device_loader.queue_depth", 2)
    from paddle_tpu.utils.log_writer import LogWriter

    with LogWriter(str(tmp_path), file_name="t.jsonl") as w:
        tm.export_scalars(w, step=3)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "telemetry_report.py"),
         str(tmp_path / "t.jsonl")],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    for phase in telemetry.PHASES:
        assert phase in out.stdout
    assert "prefetch_hit" in out.stdout
    assert "queue_depth" in out.stdout


def test_profiler_merges_telemetry_spans():
    from paddle_tpu.profiler import Profiler, ProfilerTarget, RecordEvent

    telemetry.enable()
    prof = Profiler(targets=[ProfilerTarget.CPU],
                    on_trace_ready=lambda p: None)
    with prof:
        with RecordEvent("host_span"):
            with telemetry.phase_span("dispatch"):
                time.sleep(0.001)
    names = [e.name for e in prof.profiler_result.events]
    assert "host_span" in names
    assert "telemetry::dispatch" in names
    tel = [e for e in prof.profiler_result.events
           if e.name == "telemetry::dispatch"]
    assert tel[0].event_type == "Telemetry"
    assert tel[0].end_ns - tel[0].start_ns >= 1_000_000  # the 1ms sleep
