#!/usr/bin/env bash
# Test-suite runner (round-5 VERDICT item 9).
#
#   tools/run_tests.sh          # full suite, serial
#   tools/run_tests.sh --fast   # skip the table-driven sweeps + spawned
#                               # multi-process jobs: warm < 10 min
#   tools/run_tests.sh --slow   # ONLY the sweeps + multi-process jobs
#                               # (the --fast complement; fast ∪ slow = full)
#
# Why serial: this suite is COMPILE-dominated and per-process jit caches
# don't share — measured on the 8-core pool host, pytest-xdist made it
# SLOWER (warm: 20:42 @ -n4 loadfile vs 15:40 serial; cold: 36:01 @ -n4
# worksteal vs ~24 min serial) because workers race to compile the same
# executables 4x. The fast/slow split is the useful shard: run --fast for
# the quick signal, --slow in a second (or later) job.
#
# The persistent XLA:CPU compile cache (.jax_cache/<host-key> in the
# checkout unless JAX_COMPILATION_CACHE_DIR is set — see
# paddle_tpu/framework/compile_cache.py) is what makes warm runs fast; if a
# run SIGABRTs mid-suite after a machine change, rm -rf the cache.
set -euo pipefail
cd "$(dirname "$0")/.."

# the sweep files re-check every op-table entry (fp32 FD + bf16/fp16),
# the launch/elastic files spawn real 2-process jobs, and the deep
# parallelism files (ring attention / 1F1B pipeline / per-tick RNG) carry
# the heaviest mesh compiles — together they are the bulk of wall-time
# (measured --durations=25: sequence_parallel ~194 s, pipeline ~104 s)
SLOW_FILES=(
  tests/test_op_grad_sweep.py
  tests/test_op_grad_sweep_lowp.py
  tests/test_static_parity_sweep.py
  tests/test_launch_multiprocess.py
  tests/test_native_core.py
  tests/test_sequence_parallel.py
  tests/test_pipeline_schedule.py
  tests/test_rng_dropout.py
)

MODE="full"
ARGS=()
for a in "$@"; do
  case "$a" in
    --fast) MODE="fast" ;;
    --slow) MODE="slow" ;;
    *) ARGS+=("$a") ;;
  esac
done

PY=(python -m pytest -q -p no:cacheprovider)
export PYTHONPATH="$(pwd)${PYTHONPATH:+:$PYTHONPATH}"

# telemetry exporter smoke (full/fast paths): enable the runtime telemetry
# registry, push a few spans through the LogWriter JSONL exporter, and
# render the phase table with tools/telemetry_report.py — CI exercises the
# whole export chain even when no test touches it
if [[ "$MODE" != "slow" ]]; then
  SMOKE_DIR="$(mktemp -d /tmp/pt_telemetry_smoke.XXXXXX)"
  JAX_PLATFORMS=cpu python - "$SMOKE_DIR" <<'PYEOF'
import sys, time
from paddle_tpu.profiler import telemetry
from paddle_tpu.utils.log_writer import LogWriter

telemetry.reset()
telemetry.enable()
tm = telemetry.get_telemetry()
for _ in range(3):
    telemetry.step_begin()
    for phase in telemetry.PHASES:
        with telemetry.phase_span(phase):
            time.sleep(0.001)
telemetry.step_end()
tm.inc("smoke.batches", 3)
tm.set_gauge("device_loader.queue_depth", 2)
with LogWriter(sys.argv[1], file_name="telemetry_smoke.jsonl") as w:
    tm.export_scalars(w, step=3)
telemetry.disable()
PYEOF
  python tools/telemetry_report.py "$SMOKE_DIR/telemetry_smoke.jsonl"
  # devprof smoke: compile a tiny train step with telemetry on (triggering
  # the auto-harvest of memory/cost/comm ground truth), run it, export the
  # scalars, and render the ranked HBM/comm table with the stdlib-only
  # tools/mem_report.py
  JAX_PLATFORMS=cpu python - "$SMOKE_DIR" <<'PYEOF'
import sys
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.jit.functionalize import CompiledStep
from paddle_tpu.profiler import devprof, telemetry
from paddle_tpu.utils.log_writer import LogWriter

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(16, 32), paddle.nn.ReLU(),
                           paddle.nn.Linear(32, 16))
opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=net.parameters())
def train_step(x, y):
    loss = ((net(x) - y) ** 2).mean()
    loss.backward(); opt.step(); opt.clear_grad()
    return loss
step = CompiledStep(train_step, stateful=[net, opt])
rng = np.random.RandomState(0)
batches = [(rng.rand(8, 16).astype("float32"),
            rng.rand(8, 16).astype("float32")) for _ in range(8)]
telemetry.enable()
for x, y in batches:
    step(x, y)
telemetry.disable()
rep = devprof.get_report("train_step")
assert rep is not None and rep.memory.peak_bytes > 0
with LogWriter(sys.argv[1], file_name="devprof_smoke.jsonl") as w:
    telemetry.get_telemetry().export_scalars(w, step=4)
PYEOF
  python tools/mem_report.py "$SMOKE_DIR/devprof_smoke.jsonl"
  # graph-lint gate: statically lint the bench-zoo train steps (resnet +
  # bert, no device execution) plus the serving tier's batched decode
  # and speculative-verify steps — any error-severity finding (a
  # state-pytree retrace hazard, or a kv-cache-concat/shape-churn finding
  # on either serving step, which must be shape-stable across positions
  # and acceptance patterns) fails the runner via exit status
  JAX_PLATFORMS=cpu python tools/graph_lint.py \
    --models resnet bert serve-decode serve-verify \
    --jsonl "$SMOKE_DIR/graph_lint.jsonl"
  # shard-lint gate (ISSUE 7 + 14): abstract SPMD propagation over the
  # MULTICHIP zoo — the dp×mp + MoE + dp-zero (ZeRO sharded update)
  # configs must lint with zero error findings AND the predicted per-axis
  # collective bytes must agree with the compiled-HLO measurement
  # (--measure; exit 1 on either; dp-zero also proves the deliberate
  # param all-gather is a declared reshard, not an implicit one), while
  # the injected mismatched-constraint fixture MUST be flagged (exit 1)
  JAX_PLATFORMS=cpu python tools/shard_lint.py --models dp-mp moe dp-zero \
    --measure --jsonl "$SMOKE_DIR/shard_lint.jsonl"
  if JAX_PLATFORMS=cpu python tools/shard_lint.py --models dp-mp \
      --fixture mismatched-constraint > /dev/null 2>&1; then
    echo "shard_lint missed the mismatched-constraint fixture" >&2; exit 1
  fi
  # mem-lint gate (ISSUE 12 + 15 + 18): fusion-aware per-eqn liveness
  # over the zoo — the clean configs (incl. the blockwise longctx train
  # step, the chunked-prefill serving step, and the now-measurable
  # dp-plain/dp-zero steps) must lint with zero errors AND the predicted
  # HBM peak must agree with compiled.memory_analysis() within the
  # ratcheted MEM_RTOL=0.10 (+64 KiB atol) band (--measure, never
  # under-predicting beyond it); the undonated long-context fixture MUST
  # be flagged over its injected budget (exit 1); the longctx config
  # must FIT a synthetic capacity that the einsum path (the scan's
  # threshold patched out of reach) must BLOW on the same shapes; the
  # selective-remat planner must get the predicted peak under its budget
  # (--fixture remat-plan, exit 0); and the fusion A/B leg
  # (--fixture fusion-ab) must show the fusion simulation eliding
  # temporaries without dipping under the donated-state floor;
  # --smoke runs every leg
  JAX_PLATFORMS=cpu python tools/mem_lint.py --smoke
  # serving chaos gate (ISSUE 10 + 13): flood the scheduler (speculation
  # + chunked prefill ON) under injected OOM/transient-error/stall plus
  # draft and mid-verify faults, and hard-assert the resilience contract
  # — every request ends with exactly one terminal finish_reason,
  # survivors match the PLAIN-GREEDY clean run token-for-token, verify
  # faults degrade to plain ticks, the overload SLOs page, and
  # post-chaos throughput recovers to >=90%
  JAX_PLATFORMS=cpu python tools/chaos_serve.py --smoke
  # checkpoint-doctor smoke: write two CheckpointManager steps (one torn
  # via fault injection), then exercise the verify/inspect/prune CLI —
  # verify MUST flag the torn step (exit 1) and pass the intact one
  JAX_PLATFORMS=cpu python - "$SMOKE_DIR/ckpt" <<'PYEOF'
import sys
import numpy as np
from paddle_tpu.fault import CheckpointManager, inject

m = CheckpointManager(sys.argv[1])
m.save(1, {"model": {"w": np.arange(8, dtype=np.float32)},
           "cursor": {"epoch": 0, "step": 1}})
inject.arm("torn", "ckpt.write", at=1)
m.save(2, {"model": {"w": np.ones(8, np.float32)},
           "cursor": {"epoch": 0, "step": 2}})
inject.disarm_all()
assert m.verify(2), "torn injection failed to corrupt step 2"
assert m.load()[0] == 1, "fallback to verified step 1 failed"
PYEOF
  if python tools/ckpt_doctor.py verify "$SMOKE_DIR/ckpt"; then
    echo "ckpt_doctor verify missed the torn checkpoint" >&2; exit 1
  fi
  python tools/ckpt_doctor.py verify "$SMOKE_DIR/ckpt" --step 1
  python tools/ckpt_doctor.py inspect "$SMOKE_DIR/ckpt" --step 1
  python tools/ckpt_doctor.py prune "$SMOKE_DIR/ckpt" --keep 1 --dry-run
  # /metrics scrape round-trip (ISSUE 8): populate the registry with
  # serve.*/step.* families, stand the OpenMetrics endpoint up on an
  # ephemeral port, scrape it over HTTP with the stdlib parser, and
  # assert the known families (incl. histogram _count/_sum via the
  # summary family) survived the render→serve→parse round trip
  JAX_PLATFORMS=cpu python - <<'PYEOF'
import sys, time
sys.path.insert(0, "tools")
import metrics_scrape
from paddle_tpu.profiler import telemetry

telemetry.reset()
telemetry.enable()
tm = telemetry.get_telemetry()
telemetry.step_begin()
for phase in telemetry.PHASES:
    with telemetry.phase_span(phase):
        time.sleep(0.001)
telemetry.step_end()
tm.inc("serve.decode_steps", 7)
tm.set_gauge("serve.queue_depth", 3)
for v in (0.05, 0.1, 0.2):
    tm.observe("serve.ttft_s", v)
srv = telemetry.serve_metrics(port=0)
try:
    rc = metrics_scrape.main([
        srv.url,
        "--assert-family", "serve_decode_steps",
        "--assert-family", "serve_queue_depth",
        "--assert-family", "serve_ttft_s",
        "--assert-family", "step_time_s",
        "--assert-family", "phase_dispatch",
    ])
    assert rc == 0, "metrics scrape round trip failed"
    fams = metrics_scrape.parse_openmetrics(metrics_scrape.fetch(srv.url))
    count = metrics_scrape.sample_value(fams, "serve_ttft_s",
                                        "serve_ttft_s_count")
    total = metrics_scrape.sample_value(fams, "serve_ttft_s",
                                        "serve_ttft_s_sum")
    assert count == 3 and abs(total - 0.35) < 1e-9, (count, total)
finally:
    srv.close()
    telemetry.disable()
    telemetry.reset()
PYEOF
  rm -rf "$SMOKE_DIR"
fi

# Run pytest with a single retry-on-crash (PR 7 HOST NOTE): this pool host
# intermittently SIGABRTs/segfaults inside XLA:CPU dispatch mid-suite. A
# crash exit (rc >= 128) with NO test failures recorded in the log is that
# host flake, not a red suite — re-run once before reporting red. A run
# with real failures (or a second crash) still exits nonzero.
run_pytest() {
  local log rc
  log="$(mktemp /tmp/pt_pytest_run.XXXXXX.log)"
  set +e
  "${PY[@]}" "$@" 2>&1 | tee "$log"
  rc=${PIPESTATUS[0]}
  set -e
  if (( rc >= 128 )) && \
      ! grep -qaE '^(FAILED|ERROR)[ :]|[0-9]+ (failed|errors?)' "$log"; then
    echo "run_tests.sh: pytest crashed (rc=$rc) with no test failures in" \
         "the log — retrying once (intermittent XLA dispatch crash on this" \
         "pool host; see the PR 7 HOST NOTE)" >&2
    set +e
    "${PY[@]}" "$@" 2>&1 | tee "$log"
    rc=${PIPESTATUS[0]}
    set -e
  fi
  rm -f "$log"
  return "$rc"
}

case "$MODE" in
  full)
    run_pytest tests/ "${ARGS[@]:-}"
    ;;
  fast)
    IGNORES=()
    for f in "${SLOW_FILES[@]}"; do IGNORES+=("--ignore=$f"); done
    run_pytest tests/ "${IGNORES[@]}" "${ARGS[@]:-}"
    ;;
  slow)
    run_pytest "${SLOW_FILES[@]}" "${ARGS[@]:-}"
    ;;
esac
