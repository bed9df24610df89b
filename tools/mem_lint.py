#!/usr/bin/env python
"""Memory-lint the MULTICHIP + serving zoo configs (static analysis only —
nothing executes on a device unless ``--measure`` is given).

For each config this builds a dryrun-shaped step (dp×mp Megatron-style TP
train step; the static-shape ``serve_decode`` over the KV cache), runs the
abstract per-equation liveness analysis over its jaxpr
(``paddle_tpu.analysis.mem_lint`` — no XLA invocation), prints the findings
table plus the predicted memory timeline (live-set peak, top contributors
with pytree/eqn provenance), and (with ``--jsonl``) emits one JSON object
per finding. ``--format sarif`` instead writes a SARIF 2.1.0 document to
stdout for CI annotations.

``--measure`` additionally compiles each config through
``profiler.devprof`` and prints the predicted-vs-measured HBM peak
crosscheck (``analysis.crosscheck_mem`` — the accuracy loop; the
prediction is an upper-bound model, gated at ``MEM_RTOL`` and never
allowed to UNDER-predict the compiled peak beyond it).

``--fixture undonated-longctx`` swaps the zoo for a long-context
attention step whose weights are NOT donated, linted against a small HBM
budget: the regression fixture for ``hbm-peak-over-capacity`` (+
``hbm-undonated-input`` with its predicted peak delta) — the run must
exit 1 (``tools/run_tests.sh`` gates exactly this).

``--smoke`` runs the CI gate in one go: clean zoo with ``--measure``
(zero errors, crosscheck agrees) AND the fixture (must exit 1).

Exit status: 1 when any finding at/above ``--fail-on`` severity survived
(default ``error``) or a crosscheck row disagreed.

Usage:
    JAX_PLATFORMS=cpu python tools/mem_lint.py
        [--models dp-mp serve-decode] [--jsonl PATH]
        [--format table|sarif] [--fixture undonated-longctx]
        [--measure] [--capacity BYTES] [--fail-on error|warning|never]
        [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# the dp×mp mesh needs virtual devices; flags must land before jax
# initializes its backend (same forcing as tests/conftest.py)
if os.environ.get("PADDLE_TPU_HW_TESTS") != "1":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: the fixture's injected HBM budget (bytes) when --capacity is not given:
#: well under the undonated long-context peak, well over the clean zoo's
FIXTURE_CAPACITY = 16 << 20

#: the long-context gate's synthetic budget: the blockwise longctx
#: timeline predicts ~45 MiB and fits, the einsum score matrix pushes the
#: SAME shapes to ~80 MiB and must blow it (run_tests.sh asserts both)
LONGCTX_CAPACITY = 56 << 20


def build_dp_mp(fixture=None):
    """Megatron-style TP MLP train step under a dp×mp mesh, sized so real
    activation residuals (not fusion-elidable elementwise temps) dominate
    the peak — the config the predicted-vs-measured crosscheck is gated
    on. Donated state: the timeline's donation aliasing must match XLA's
    arg/out alias accounting."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.utils import unique_name

    mesh = build_mesh({"dp": 2, "mp": 2})
    with unique_name.guard():
        paddle.seed(0)
        l1 = paddle.nn.Linear(512, 2048)
        l2 = paddle.nn.Linear(2048, 512)
    put = jax.device_put
    l1.weight._value = put(l1.weight._value,
                           NamedSharding(mesh, P(None, "mp")))
    l1.bias._value = put(l1.bias._value, NamedSharding(mesh, P("mp")))
    l2.weight._value = put(l2.weight._value,
                           NamedSharding(mesh, P("mp", None)))
    l2.bias._value = put(l2.bias._value, NamedSharding(mesh, P()))
    opt = paddle.optimizer.SGD(
        learning_rate=0.1,
        parameters=list(l1.parameters()) + list(l2.parameters()))

    def train_step(x, y):
        h = paddle.nn.functional.relu(l1(x))
        out = l2(h)
        loss = ((out - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step.__name__ = "dp_mp_train_step"
    step = CompiledStep(train_step, stateful=[l1, l2, opt],
                        donate_state=True)
    rng = np.random.RandomState(0)
    x = Tensor(put(jnp.asarray(rng.randn(256, 512), jnp.float32),
                   NamedSharding(mesh, P("dp", None))))
    y = Tensor(put(jnp.asarray(rng.randn(256, 512), jnp.float32),
                   NamedSharding(mesh, P("dp", None))))
    return step, (x, y), mesh, True  # measurable on XLA:CPU


def build_serve_decode(fixture=None):
    """The serving tier's O(1) static-shape ``serve_decode`` over the KV
    cache (small GPT, weights threaded as donated state so the compiled
    ``memory_analysis`` counts them as arguments — the crosscheckable
    configuration)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.utils import unique_name

    with unique_name.guard():
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=128, hidden_dropout=0.0,
            attention_dropout=0.0))
    model.eval()
    eng = GenerationEngine(model, max_batch=4, max_len=128,
                           freeze_weights=False)
    return eng.decode_step, tuple(eng.example_decode_args([3, 5])), None, True


def _build_dp_adam(zero):
    """Shared builder for the ZeRO optimizer-state accounting pair: a bf16
    MLP under a pure-dp mesh with AdamW(multi_precision=True) — 12 bytes of
    fp32 optimizer state per param (master + moment1 + moment2). ``dp-plain``
    keeps that state replicated; ``dp-zero`` wraps the optimizer in
    ``ShardedOptimizer`` so every accumulator lives at 1/dp per replica —
    the predicted peak must drop by ~the sharded accumulator bytes
    (pinned in tests/test_mem_lint.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.distributed.sharding import ShardedOptimizer
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.utils import unique_name

    mesh = build_mesh({"dp": 8})
    with unique_name.guard():
        paddle.seed(0)
        l1 = paddle.nn.Linear(256, 1024)
        l2 = paddle.nn.Linear(1024, 256)
    rep = NamedSharding(mesh, P())
    for lyr in (l1, l2):
        for p in lyr.parameters():
            p._value = jax.device_put(p._value.astype(jnp.bfloat16), rep)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-2, multi_precision=True,
        parameters=list(l1.parameters()) + list(l2.parameters()))
    if zero:
        opt = ShardedOptimizer(opt, axis="dp", mesh=mesh)
    stateful_opt = opt._inner_opt if zero else opt

    def train_step(x, y):
        h = paddle.nn.functional.relu(l1(x))
        out = l2(h)
        loss = ((out - y).astype(jnp.float32) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step.__name__ = "dp_zero_step" if zero else "dp_plain_step"
    step = CompiledStep(train_step, stateful=[l1, l2, stateful_opt],
                        donate_state=True)
    rng = np.random.RandomState(4)
    put = jax.device_put
    x = Tensor(put(jnp.asarray(rng.randn(64, 256), jnp.bfloat16),
                   NamedSharding(mesh, P("dp", None))))
    y = Tensor(put(jnp.asarray(rng.randn(64, 256), jnp.bfloat16),
                   NamedSharding(mesh, P("dp", None))))
    # measurable since ISSUE 18: the step is optimizer-temp dominated —
    # exactly where the fusion-blind model over-predicted XLA's fused
    # update kernel — and the fusion-aware timeline (analysis.fusion)
    # elides those elementwise temporaries, so the predicted peak now
    # crosschecks against memory_analysis within MEM_RTOL (the pinned
    # dp-fold peak drop in tests/test_mem_lint.py rides on top)
    return step, (x, y), mesh, True


def build_dp_plain(fixture=None):
    return _build_dp_adam(zero=False)


def build_dp_zero(fixture=None):
    return _build_dp_adam(zero=True)


def build_undonated_longctx(fixture=None):
    """The fixture: a long-context attention forward whose weights are NOT
    donated (``donate_state=False``) — the [b, h, q, k] score matrix plus
    undonated parameters blow past the injected HBM budget, so
    ``hbm-peak-over-capacity`` must fire (error → exit 1) and
    ``hbm-undonated-input`` must report the predicted peak delta."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.ops.dispatch import apply_op
    from paddle_tpu.utils import unique_name

    b, s, h, d = 2, 1024, 4, 64
    with unique_name.guard():
        paddle.seed(0)
        qkv = paddle.nn.Linear(h * d, 3 * h * d)
        out = paddle.nn.Linear(h * d, h * d)
    opt = paddle.optimizer.SGD(
        learning_rate=0.1,
        parameters=list(qkv.parameters()) + list(out.parameters()))

    def attn_fn(pv):
        pv = pv.reshape(b, s, 3, h, d)
        q = jnp.moveaxis(pv[:, :, 0], 2, 1)  # [b, h, s, d]
        k = jnp.moveaxis(pv[:, :, 1], 2, 1)
        v = jnp.moveaxis(pv[:, :, 2], 2, 1)
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        attn = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores), v)
        return jnp.moveaxis(attn, 1, 2).reshape(b, s, h * d)

    def train_step(x, y):
        proj = qkv(x)  # [b, s, 3hd]
        merged = apply_op("longctx_attn", attn_fn, (proj,), {})
        loss = ((out(merged) - y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step.__name__ = "undonated_longctx_step"
    step = CompiledStep(train_step, stateful=[qkv, out, opt],
                        donate_state=False)
    rng = np.random.RandomState(0)
    x = Tensor(np.asarray(rng.randn(b, s, h * d), np.float32))
    y = Tensor(np.asarray(rng.randn(b, s, h * d), np.float32))
    return step, (x, y), None, False  # static-only: the fixture never runs


def build_longctx(fixture=None):
    """Long-context GPT train step at seq 1024 — over the blockwise
    threshold, so causal training attention runs the KV-block scan (ISSUE
    15) instead of the O(seq²) einsum score matrix. Measurable on
    XLA:CPU: the predicted peak must agree with ``memory_analysis`` and
    never under-predict. ``--smoke`` lints the SAME shapes once more with
    the threshold out of reach (the einsum path), under one ``--capacity``
    that only the blockwise timeline fits."""
    import paddle_tpu as paddle
    from paddle_tpu.framework.tensor import Tensor
    from paddle_tpu.jit.functionalize import CompiledStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.utils import unique_name

    cfg = GPTConfig(vocab_size=256, hidden_size=128, num_layers=2,
                    num_heads=2, max_position_embeddings=1024,
                    hidden_dropout=0.0, attention_dropout=0.0)
    with unique_name.guard():
        paddle.seed(0)
        model = GPTForCausalLM(cfg)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=model.parameters())

    def train_step(ids, labels):
        loss = model.loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step.__name__ = "longctx_train_step"
    step = CompiledStep(train_step, stateful=[model, opt],
                        donate_state=True)
    rng = np.random.RandomState(0)
    ids = Tensor(rng.randint(0, cfg.vocab_size, (1, 1024))
                 .astype(np.int64))
    return step, (ids, ids), None, True


def build_serve_chunk(fixture=None):
    """The chunked-prefill serving step over a 1024-row KV cache: chunk
    queries attend the slot's FULL cached row through the length-masked
    blockwise path — the serving-side long-context crosscheck target."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import GenerationEngine
    from paddle_tpu.utils import unique_name

    with unique_name.guard():
        paddle.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
            max_position_embeddings=1024, hidden_dropout=0.0,
            attention_dropout=0.0))
    model.eval()
    eng = GenerationEngine(model, max_batch=2, max_len=1024,
                           prefill_buckets=(128,), prefill_chunk=128,
                           freeze_weights=False)
    return (eng.chunk_step, tuple(eng.example_chunk_args([256], off=256)),
            None, True)


def run_remat_fixture(capacity=None, out=sys.stdout):
    """``--fixture remat-plan``: the selective-remat planner must get the
    longctx step's PREDICTED peak under the budget (default: 70% of the
    baseline peak). Returns 0 on success, 1 when the plan misses — the
    run_tests.sh gate asserts 0."""
    from paddle_tpu import analysis
    from paddle_tpu.analysis import remat_plan

    step, batch, _, _ = build_longctx()
    tl = analysis.analyze_memory(step, *batch)
    budget = float(capacity) if capacity else 0.7 * tl.peak_bytes
    plan = remat_plan.plan_remat(tl, budget_bytes=budget,
                                 min_bytes=1 << 16, min_span=0.2)
    print(f"\n== remat-plan fixture ({step.name}) ==", file=out)
    print(plan.table(), file=out)
    ok = plan.ok and plan.sites and plan.peak_after <= budget
    print(f"remat-plan fixture: predicted {tl.peak_bytes:.0f} -> "
          f"{plan.peak_after:.0f} bytes under budget {budget:.0f} -> "
          f"{'OK' if ok else 'FAIL'}", file=out)
    return 0 if ok else 1


def run_fusion_ab(out=sys.stdout):
    """``--fixture fusion-ab``: the fusion on/off A/B gate. The SAME
    optimizer-temp-dominated step (dp-plain) is walked twice; the
    fusion-aware timeline must (a) certify a non-trivial byte volume as
    elided, (b) predict a strictly lower-or-equal peak, and (c) never go
    below the step's irreducible floor (donated state bytes — fusion can
    elide temporaries, not parameters). Returns 0 on success."""
    from paddle_tpu import analysis

    step, batch, _, _ = build_dp_plain()
    tl_on = analysis.analyze_memory(step, *batch, fusion=True)
    tl_off = analysis.analyze_memory(step, *batch, fusion=False)
    floor = tl_on.donated_bytes
    delta = tl_off.peak_bytes - tl_on.peak_bytes
    ok = (tl_on.fused_bytes > 0
          and tl_on.peak_bytes <= tl_off.peak_bytes
          and tl_on.peak_bytes >= floor)
    print(f"\n== fusion A/B ({step.name}) ==", file=out)
    print(f"fusion off peak {tl_off.peak_bytes:.0f} B, on "
          f"{tl_on.peak_bytes:.0f} B (delta {delta:.0f} B, "
          f"{tl_on.fused_bytes:.0f} B of temporaries elided, state floor "
          f"{floor:.0f} B) -> {'OK' if ok else 'FAIL'}", file=out)
    return 0 if ok else 1


ZOO = {
    "dp-mp": build_dp_mp,
    "serve-decode": build_serve_decode,
    "dp-plain": build_dp_plain,
    "dp-zero": build_dp_zero,
    "longctx": build_longctx,
    "serve-chunk": build_serve_chunk,
}

FIXTURES = {
    "undonated-longctx": build_undonated_longctx,
    "remat-plan": run_remat_fixture,  # special-cased: a planner gate
    "fusion-ab": run_fusion_ab,       # special-cased: fusion on/off A/B
}


def lint_zoo(models, fixture=None, measure=False, capacity=None,
             out=sys.stdout, fusion=True):
    """Returns ``[(name, LintReport, MemoryTimeline, crosscheck_rows)]``
    (import-friendly: the tests drive this directly). ``fusion=False``
    runs the fusion-blind legacy timeline (looser upper bound — the A/B
    smoke leg compares both)."""
    from paddle_tpu import analysis

    config = {"fusion": bool(fusion)}
    if capacity is not None:
        config["hbm_capacity_bytes"] = float(capacity)
    builders = (
        [(fixture, FIXTURES[fixture])] if fixture
        else [(name, ZOO[name]) for name in models])
    results = []
    for name, build in builders:
        step, batch, mesh, measurable = build(fixture=fixture)
        report = analysis.lint_step(step, *batch, mesh=mesh, config=config)
        tl = report.memory  # the timeline lint_step attached
        print(f"\n== {name} ({step.name}) ==", file=out)
        print(report.table(), file=out)
        if tl is not None:
            print(tl.table(), file=out)
        else:
            print("memory timeline: unavailable (mem lint failed — see "
                  "warnings)", file=out)
        rows = None
        if measure and measurable:
            from paddle_tpu.profiler import devprof

            rep = devprof.device_report(step, *batch, register=False)
            rtol = (analysis.MEM_RTOL if fusion
                    else analysis.MEM_RTOL_UNFUSED)
            rows = analysis.crosscheck_mem(tl, rep, rtol=rtol)
            for r in rows:
                ratio = ("n/a" if r["ratio"] is None
                         else f"{r['ratio']:.3f}")
                print(f"crosscheck: metric={r['metric']} "
                      f"predicted={r['predicted_bytes']:.0f} "
                      f"measured={r['measured_bytes']:.0f} "
                      f"ratio={ratio} agrees={r['agrees']} "
                      f"under_predicted={r['under_predicted']}"
                      + (f" skipped={r['skipped']}" if r["skipped"]
                         else ""), file=out)
        elif measure:
            print(f"crosscheck: skipped ({name} is static-only)", file=out)
        results.append((name, report, tl, rows))
    return results


def run(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--models", nargs="+",
                    default=["dp-mp", "serve-decode", "dp-plain", "dp-zero",
                             "longctx", "serve-chunk"],
                    choices=sorted(ZOO))
    ap.add_argument("--jsonl", default=None,
                    help="write one JSON object per finding to this path")
    ap.add_argument("--format", default="table",
                    choices=["table", "sarif"],
                    help="sarif: emit a SARIF 2.1.0 document on stdout "
                         "(CI annotations) instead of tables")
    ap.add_argument("--fixture", default=None,
                    choices=sorted(FIXTURES),
                    help="lint the undonated long-context regression "
                         "fixture against a small HBM budget instead of "
                         "the zoo (the run must exit 1)")
    ap.add_argument("--measure", action="store_true",
                    help="also compile measurable configs via devprof and "
                         "print the predicted-vs-measured peak crosscheck")
    ap.add_argument("--capacity", type=float, default=None,
                    help="HBM budget in bytes for hbm-peak-over-capacity "
                         "(default: auto-detected device budget; the "
                         f"fixture defaults to {FIXTURE_CAPACITY})")
    ap.add_argument("--fail-on", default="error",
                    choices=["error", "warning", "never"],
                    help="exit 1 when findings at/above this severity "
                         "exist")
    ap.add_argument("--no-fusion", action="store_true",
                    help="run the fusion-blind legacy timeline (looser "
                         "upper bound, crosschecked at MEM_RTOL_UNFUSED "
                         "instead of MEM_RTOL) — the --smoke A/B leg "
                         "compares both")
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: clean zoo with --measure must pass, the "
                         "undonated fixture must exit 1, the longctx config "
                         "must fit a capacity the einsum path blows, and "
                         "the remat planner must hit its budget")
    args = ap.parse_args(argv)

    if args.smoke:
        clean = run(["--measure"])
        fixture = run(["--fixture", "undonated-longctx"])
        # the ISSUE 15 long-context gate: one synthetic HBM budget that
        # the blockwise timeline fits and the einsum score matrix blows
        bw = run(["--models", "longctx", "--capacity",
                  str(LONGCTX_CAPACITY)])
        from paddle_tpu.nn.functional import attention

        # the control: the same shapes with the scan's threshold out of
        # reach, so causal training takes the einsum score matrix
        min_kv, attention.BLOCKWISE_MIN_KV = attention.BLOCKWISE_MIN_KV, 1 << 62
        try:
            es = run(["--models", "longctx", "--capacity",
                      str(LONGCTX_CAPACITY)])
        finally:
            attention.BLOCKWISE_MIN_KV = min_kv
        remat = run(["--fixture", "remat-plan"])
        ab = run(["--fixture", "fusion-ab"])
        ok = (clean == 0 and fixture == 1 and bw == 0 and es == 1
              and remat == 0 and ab == 0)
        print(f"\nmem lint smoke: clean-zoo rc={clean} (want 0), "
              f"fixture rc={fixture} (want 1), longctx-blockwise rc={bw} "
              f"(want 0), longctx-einsum rc={es} (want 1), remat-plan "
              f"rc={remat} (want 0), fusion-ab rc={ab} (want 0) -> "
              f"{'OK' if ok else 'FAIL'}")
        return 0 if ok else 1

    capacity = args.capacity
    if args.fixture == "remat-plan":
        return run_remat_fixture(capacity)
    if args.fixture == "fusion-ab":
        return run_fusion_ab()
    if args.fixture and capacity is None:
        capacity = FIXTURE_CAPACITY

    sink = open(os.devnull, "w") if args.format == "sarif" else sys.stdout
    results = lint_zoo(args.models, fixture=args.fixture,
                       measure=args.measure, capacity=capacity, out=sink,
                       fusion=not args.no_fusion)

    if args.format == "sarif":
        from paddle_tpu.analysis import sarif_report

        findings = [f for _, report, _, _ in results for f in report]
        json.dump(sarif_report(findings, tool="paddle-tpu-mem-lint"),
                  sys.stdout, indent=1)
        sys.stdout.write("\n")

    if args.jsonl:
        with open(args.jsonl, "w") as fh:
            for name, report, _, _ in results:
                for f in report:
                    fh.write(json.dumps({"model": name, **f.as_dict()},
                                        sort_keys=True) + "\n")
        print(f"wrote {sum(len(r) for _, r, _, _ in results)} findings to "
              f"{args.jsonl}", file=sink)

    n_err = sum(len(r.errors) for _, r, _, _ in results)
    n_warn = sum(len(r.warnings) for _, r, _, _ in results)
    # fusion-aware timelines must agree both ways; the legacy --no-fusion
    # path over-predicts by design (fusion-blindness is its documented
    # bias), so only under-prediction gates there
    fusion_on = not getattr(args, "no_fusion", False)
    bad_cross = sum(
        1 for _, _, _, rows in results for r in (rows or ())
        if r["under_predicted"] or (fusion_on and r["agrees"] is False))
    print(f"\nmem lint: {n_err} error(s), {n_warn} warning(s), "
          f"{bad_cross} crosscheck disagreement(s) across "
          f"{len(results)} config(s)", file=sink)
    if args.fail_on == "never":
        return 0
    gate = n_err + bad_cross + (n_warn if args.fail_on == "warning" else 0)
    return 1 if gate else 0


def main(argv=None):
    return run(argv)


if __name__ == "__main__":
    sys.exit(main())
