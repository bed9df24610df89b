"""Shared harness for the hardware model benchmarks (bench.py,
tools/bench_resnet.py, tools/bench_bert.py).

Measurement discipline (identical to bench.py, see its comments for the
rationale): 3 warmup steps, then issue all measured steps back-to-back with
donated state so each step's inputs depend on the previous step's outputs,
fence on the LAST loss only, fetch the rest after the timer for the
finiteness check.

Measurement protocol (async-pipeline revision): batches flow through
``paddle_tpu.io.DeviceLoader`` — a background thread double-buffers the
host→device transfer of the next ``prefetch`` batches — and per-step losses
accumulate on device in a ``metric.AsyncMetricBuffer``; the ONLY in-timer
fence is the final loss. The measured number therefore reflects the
production input pipeline (prefetch + deferred readback), not a host-bound
loop. Pass ``prefetch=0`` to ``measure_steps`` for the legacy synchronous
feed. Steps compiled with ``donate_inputs=True`` consume the staged
batches — don't reuse a batch list across two measured runs in-process.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

# chip bf16 peak FLOP/s by device_kind substring; MFU is only reported when
# the chip is known — never against a guessed peak
PEAKS = {"v5 lite": 197e12, "v5e": 197e12, "v4": 275e12, "v5p": 459e12,
         "v6 lite": 918e12, "v6e": 918e12}


def device_peak():
    import jax

    kind = jax.devices()[0].device_kind.lower()
    return kind, next((p for k, p in PEAKS.items() if k in kind), None)


def measure_steps(step, batches, iters, warmup=3, prefetch=2,
                  collect_telemetry=True):
    """Run the warmup+steady-state protocol; returns (seconds, losses).

    ``batches`` may be host batches (numpy tuples) or device Tensors; with
    ``prefetch > 0`` they are staged host→device through ``DeviceLoader``
    so transfers overlap compute, and losses are read back only after the
    timer stops (single fence on the last loss inside the timed region).

    With ``collect_telemetry`` (default) the run enables the runtime
    telemetry registry (reset first, spanning warmup so compile counts are
    captured) and marks a phase record per measured step; summarize it into
    the BENCH json with :func:`telemetry_block`. The per-step cost is a few
    guarded ns-clock reads — noise against any real step.
    """
    from paddle_tpu.io import DeviceLoader
    from paddle_tpu.metric import AsyncMetricBuffer

    telemetry = None
    if collect_telemetry:
        from paddle_tpu.profiler import telemetry

        telemetry.reset()
        telemetry.enable()
    try:
        feed = iter(DeviceLoader(batches, buffer_size=prefetch)
                    if prefetch else batches)
        buf = AsyncMetricBuffer()
        for _ in range(warmup):
            loss = step(*next(feed))
            np.asarray(loss._value)
        t0 = time.perf_counter()
        losses = []
        for _ in range(iters):
            if telemetry is not None:
                telemetry.step_begin()
            losses.append(step(*next(feed)))
        float(np.asarray(losses[-1]._value))  # fence on the dependence chain
        total = time.perf_counter() - t0
        if telemetry is not None:
            telemetry.step_end()
        for l in losses:
            buf.append(l)
        vals = buf.result()  # post-timer readback for the finiteness check
        assert all(np.isfinite(v) for v in vals), \
            f"bench losses not finite: {vals}"
        return total, vals
    finally:
        if telemetry is not None:
            telemetry.disable()  # data stays readable for telemetry_block


def telemetry_block(total_seconds, steps):
    """Phase-attribution block for the emitted BENCH json, from the
    telemetry collected by ``measure_steps``: steps/s, mean data-wait
    fraction of the timed region, compile/recompile counts, per-phase
    seconds (measured steps only — warmup phases are outside the step
    records), DeviceLoader prefetch stats, and the devprof device ground
    truth — ``hbm_peak_bytes`` (compiled HBM peak), ``comm_fraction``
    (interconnect bytes / total memory traffic) and per-mesh-axis
    collective byte counters — harvested at the step's first compile."""
    from paddle_tpu.profiler import devprof, telemetry

    s = telemetry.summary()
    recs = telemetry.get_telemetry().steps()
    phase_s = {}
    for r in recs:
        for k, v in r.phases.items():
            phase_s[k] = phase_s.get(k, 0.0) + v
    counters = s["counters"]
    gauges = s["gauges"]
    # device stats: prefer the live gauges; fall back to the harvest
    # registry when another enable/reset cycle cleared them
    hbm_peak = gauges.get("hbm.peak_bytes")
    comm_fraction = gauges.get("comm.fraction")
    comm_by_axis = {k[len("comm.bytes."):]: int(v)
                    for k, v in counters.items()
                    if k.startswith("comm.bytes.")}
    rep = devprof.last_report()
    if rep is not None:
        if hbm_peak is None and rep.memory is not None:
            hbm_peak = rep.memory.peak_bytes
        if comm_fraction is None:
            comm_fraction = rep.comm_fraction
        if not comm_by_axis:
            comm_by_axis = {a: int(st["bytes"])
                            for a, st in rep.collectives.as_dict().items()}
    return {
        "steps_per_sec": round(steps / total_seconds, 3) if total_seconds
        else None,
        "data_wait_frac": round(phase_s.get("data_wait", 0.0) / total_seconds,
                                4) if total_seconds else None,
        "compile_count": int(counters.get("compile.count", 0)),
        "recompile_count": int(s["recompile_count"]),
        "phase_s": {k: round(v, 6) for k, v in sorted(phase_s.items())},
        "prefetch": {
            "hits": int(counters.get("device_loader.prefetch_hit", 0)),
            "misses": int(counters.get("device_loader.prefetch_miss", 0)),
            "stall_s": round(float(
                counters.get("device_loader.stall_s", 0.0)), 6),
            "bytes_staged": int(
                counters.get("device_loader.bytes_staged", 0)),
        },
        "hbm_peak_bytes": int(hbm_peak) if hbm_peak is not None else None,
        "comm_fraction": (round(float(comm_fraction), 4)
                          if comm_fraction is not None else None),
        "comm_bytes_by_axis": comm_by_axis,
    }


def compiled_flops(step, batches):
    """FLOPs of ONE compiled train step from XLA's own cost analysis
    (includes remat recompute — i.e. this yields hardware-FLOPs utilization,
    the honest number for 'how busy is the MXU'). Prefers the devprof
    report harvested at the step's first compile (no second lowering);
    falls back to lowering against the example batch."""
    from paddle_tpu.profiler import devprof
    from paddle_tpu.profiler.devprof import normalize_cost_analysis

    rep = devprof.get_report(getattr(step, "name", ""))
    if rep is not None and rep.flops:
        return rep.flops
    try:
        lowered = step.lower(*batches[0])
        cost = normalize_cost_analysis(lowered.compile().cost_analysis())
        return cost.get("flops", 0.0) or None
    except Exception as e:  # pragma: no cover - cost analysis is best-effort
        print(f"cost_analysis unavailable: {e!r}", file=sys.stderr)
        return None


def emit(result, artifact=None):
    """Print the one-line JSON and optionally persist a repo-root artifact."""
    print(json.dumps(result))
    if artifact:
        with open(artifact, "w") as f:
            json.dump(result, f, indent=1)
            f.write("\n")
