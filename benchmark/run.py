"""One run of one cell: build, warm up, measure, compare, print one line.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU with the chips the cell asks for and exits non-zero without
one. ``--rehearse`` is the explicit tiny CPU run for debugging the harness
(Pallas interpreted; the line names ``cpu``; never a result). Everything
that belongs to one cell, configuration or metric is a file the harness
finds by name: see ``README.md`` beside this file.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)                   # harness, drivers, readers
sys.path.insert(0, os.path.dirname(HERE))  # paddle_tpu, the system under test


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def memory_peak():
    """The fullest chip's peak. On this libtpu ``peak_bytes_in_use`` counts
    buffers only and the programs' temporaries show in
    ``peak_bytes_reserved`` (PERF.md, PR 21): the sum agrees with the
    compiler's memory analysis of the step."""
    import jax

    peak = 0
    for d in jax.devices():
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0))
                   + int(st.get("peak_bytes_reserved", 0)))
    if peak == 0:  # the CPU backend reports nothing (rehearsal only)
        import resource
        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
    return peak


def read_metric(name, out, ctx):
    from harness.builders import load_json

    spec = load_json("metrics", name)
    module, fn = spec["reader"].split(".")
    reader = getattr(importlib.import_module(f"readers.{module}"), fn)
    return reader(out, ctx, **spec.get("args", {})), spec["unit"]


def model_of(config):
    """The model class's three modules under ``harness/``, found by the
    names its configuration gives: what builds the program's model, what
    makes its weights from the seed, and its plain reference."""
    module, fn = config["builder"].split(".")
    return types.SimpleNamespace(
        build=getattr(importlib.import_module(f"harness.{module}"), fn),
        weights=importlib.import_module(f"harness.{config['weights']}"),
        reference=importlib.import_module(f"harness.{config['reference']}"))


def make_ctx(args, seed):
    """Everything a driver needs for one run of one cell."""
    import jax

    from harness.builders import load_json, sizes_of

    cell = load_json("workloads", args.workload)
    config = load_json("configs", cell["config"])
    # the accelerator's runtime comes up here (5-11 s on a v5e host, the
    # part of set-up that swings by seconds from run to run): inside
    # setup_s, as a user pays it, and logged beside it
    t_backend = time.perf_counter()
    devs = jax.devices()
    backend_s = time.perf_counter() - t_backend
    log(f"set-up: imports {t_backend - T_START:.1f}s, the accelerator's "
        f"runtime {backend_s:.1f}s (both inside setup_s)")
    platform = devs[0].platform
    if args.rehearse:
        if platform != "cpu":
            sys.exit("--rehearse is the CPU rehearsal: set JAX_PLATFORMS=cpu")
        cell = {**cell, **cell.get("rehearse", {})}
    elif platform != "tpu" or len(devs) < cell["chips"]:
        sys.exit(f"benchmark: cell {args.workload} needs {cell['chips']} TPU "
                 f"chip(s); jax found {len(devs)} x {platform}. `--rehearse` "
                 f"is the tiny CPU run.")
    trace_dir = os.path.join(HERE, ".trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    return types.SimpleNamespace(
        cell=cell, config=config, sizes=sizes_of(config, args.rehearse),
        traffic=cell["traffic"], chips=cell["chips"], seed=seed,
        seconds=args.seconds, trace=bool(args.trace),
        trace_seconds=min(cell.get("trace_seconds", 3.0), args.seconds),
        trace_dir=trace_dir, rehearse=args.rehearse, control=False,
        model=model_of(config), t_start=T_START, backend_s=backend_s,
        t_import=time.perf_counter(), log=log, memory_peak=memory_peak)


def run_once(args, seed, control=False, on_trace=None):
    """One run of the cell; the line to print. ``control`` and ``on_trace``
    are for ``tools.py`` (reading limits, keeping a fixture)."""
    import jax

    from harness import lastline, trace_reduce

    ctx = make_ctx(args, seed)
    ctx.control = control
    cell, trace_dir = ctx.cell, ctx.trace_dir
    devs = jax.devices()
    platform = devs[0].platform
    out = importlib.import_module(f"drivers.{cell['driver']}").run(ctx)
    # a rehearsal borrows the v5e's peaks so that the readers run; its
    # shares mean nothing and its line names the cpu
    out["device_kind"] = "TPU v5 lite" if args.rehearse \
        else devs[0].device_kind
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out["memory_peak_bytes"]}
    breakdown = None
    if ctx.trace:
        t_read = time.perf_counter()
        xplane = trace_reduce.find_xplane(trace_dir)
        trace = trace_reduce.load(xplane, platform)
        log(f"trace: read in {time.perf_counter() - t_read:.1f}s")
        out["trace"] = trace
        device["window_s"] = trace_reduce.window_s(trace)
        device["busy_s"] = trace_reduce.busy_s(trace)
        breakdown = trace_reduce.breakdown(trace)
        if on_trace:
            on_trace(trace, xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
    for name, c in out["compared"].items():
        log(f"compared {name}: {json.dumps(c)}")
    if not out["values"]:  # a training control: readings, no window
        return json.dumps({"control": out["compared"]})
    wanted = cell["per_layer"] if ctx.trace else cell["end_to_end"]
    values, units = {}, {}
    for name in wanted:
        values[name], units[name] = read_metric(name, out, ctx)
    return lastline.build(
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], values=values, wanted=wanted, units=units,
        device=device, traced=ctx.trace, breakdown=breakdown,
        compared=out["compared"], allow_missing=args.rehearse)


def parser():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap


@contextlib.contextmanager
def program(rehearse):
    """The system under test, switched on as a run needs it: its compile
    cache where ``framework/compile_cache.py`` decides, its counters on."""
    if not os.path.isdir(os.path.join(os.path.dirname(HERE), "paddle_tpu")):
        sys.exit("benchmark: the system under test (paddle_tpu/) is not "
                 "beside benchmark/")

    from paddle_tpu.framework.compile_cache import enable_compile_cache
    from paddle_tpu.ops import pallas
    from paddle_tpu.profiler import devprof, telemetry

    cache_dir = enable_compile_cache()
    telemetry.enable()
    devprof.enable_auto_harvest(False)  # no second lowering per step
    log(f"benchmark: compile cache at {cache_dir}")
    with (pallas.interpret_mode() if rehearse
          else contextlib.nullcontext()):
        yield


def main(argv=None):
    args = parser().parse_args(argv)
    with program(args.rehearse):
        print(run_once(args, args.seed), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
