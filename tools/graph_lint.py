#!/usr/bin/env python
"""Graph-lint a small model zoo (static analysis only — nothing executes
on a device unless ``--run-steps`` is given).

For each model this builds a train step at CPU smoke scale (ResNet-50 with
SGD+momentum, BERT MLM with AdamW, the serving steps), abstractly traces it with ``paddle_tpu.analysis.lint_step`` against two
example batches, prints the findings table, and (with ``--jsonl``) emits one
JSON object per finding — ``Finding.as_dict()`` plus a ``model`` key;
``Finding.from_dict`` round-trips the lines.

Exit status: 1 when any finding at/above ``--fail-on`` severity survived
(default ``error``) — ``tools/run_tests.sh`` smoke-runs this as a CI gate.

``--fixture adam-lazy`` swaps every model's optimizer for a pre-fix Adam
whose accumulators materialize lazily during the first step: the regression
fixture for the retrace-state-structure rule (the Adam/AdamW double-trace
PR 2's telemetry measured). ``--run-steps N`` additionally executes N real
steps per model under telemetry and prints the static-prediction vs
observed-compile-count crosscheck.

Usage:
    JAX_PLATFORMS=cpu python tools/graph_lint.py [--models mlp resnet bert]
        [--jsonl PATH] [--fixture adam-lazy] [--fail-on error|warning|never]
        [--run-steps N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _lazy_adam(paddle):
    class LazyAdam(paddle.optimizer.Adam):
        """Pre-fix fixture: defeat the eager accumulator init so moment/
        beta-pow state materializes lazily inside the first traced step —
        the state-pytree instability the lint must catch."""

        def _ensure_accumulators(self):
            pass

    return LazyAdam


def _step_of(model_fwd_loss, model, opt, name):
    from paddle_tpu.jit.functionalize import CompiledStep

    def train_step(x, y):
        loss = model_fwd_loss(x, y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step.__name__ = name
    return CompiledStep(train_step, stateful=[model, opt], donate_state=True)


def _batches(x_fn, y_fn, n=2):
    from paddle_tpu.framework.tensor import Tensor

    rng = np.random.RandomState(0)
    return [(Tensor(x_fn(rng)), Tensor(y_fn(rng))) for _ in range(n)]


def build_mlp(fixture=None):
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(32, 64), paddle.nn.ReLU(),
                               paddle.nn.Linear(64, 10))
    opt_cls = (_lazy_adam(paddle) if fixture == "adam-lazy"
               else paddle.optimizer.Adam)
    opt = opt_cls(learning_rate=1e-3, parameters=net.parameters())

    def fwd_loss(x, y):
        return F.cross_entropy(net(x), y).mean()

    step = _step_of(fwd_loss, net, opt, "mlp_train_step")
    return step, _batches(
        lambda r: r.randn(8, 32).astype(np.float32),
        lambda r: r.randint(0, 10, (8, 1)).astype(np.int64))


def build_resnet(fixture=None):
    """ResNet-50 at CPU smoke scale (32x32, 10 classes, SGD+momentum)."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    model = resnet50(num_classes=10)
    if fixture == "adam-lazy":
        opt = _lazy_adam(paddle)(learning_rate=0.1,
                                 parameters=model.parameters())
    else:
        opt = paddle.optimizer.Momentum(
            learning_rate=0.1, momentum=0.9, parameters=model.parameters(),
            weight_decay=1e-4)

    def fwd_loss(x, y):
        return F.cross_entropy(model(x).astype("float32"), y,
                               reduction="mean")

    step = _step_of(fwd_loss, model, opt, "resnet_train_step")
    return step, _batches(
        lambda r: r.randn(4, 3, 32, 32).astype(np.float32),
        lambda r: r.randint(0, 10, (4, 1)).astype(np.int64))


def build_bert(fixture=None):
    """BERT MLM at a CPU smoke config, AdamW — the optimizer whose lazy double-trace this lint regression-
    tests."""
    import paddle_tpu as paddle
    from paddle_tpu.models import BertConfig, BertForPretraining

    paddle.seed(0)
    cfg = BertConfig(vocab_size=512, hidden_size=128, num_layers=2,
                     num_heads=2, intermediate_size=256,
                     max_position_embeddings=64,
                     hidden_dropout=0.0, attention_dropout=0.0)
    model = BertForPretraining(cfg)
    opt_cls = (_lazy_adam(paddle) if fixture == "adam-lazy"
               else paddle.optimizer.AdamW)
    opt = opt_cls(learning_rate=1e-4, parameters=model.parameters())

    def fwd_loss(ids, labels):
        return model.loss(ids, labels)

    step = _step_of(fwd_loss, model, opt, "bert_train_step")
    return step, _batches(
        lambda r: r.randint(0, 512, (4, 64)).astype(np.int32),
        lambda r: r.randint(0, 512, (4, 64)).astype(np.int32))


def build_serve_decode(fixture=None):
    """The serving tier's batched decode step (tiny GPT, static-shape KV
    cache) against two CONSECUTIVE generation positions — the O(1)-decode
    acceptance gate: with the preallocated cache both example batches have
    IDENTICAL signatures, so the `retrace-shape-churn` and
    `kv-cache-concat` rules must stay silent (the grow-by-concat cache
    they exist to catch is regression-tested in tests/test_serving.py)."""
    del fixture  # no optimizer in the serving path
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import GenerationEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    engine = GenerationEngine(GPTForCausalLM(cfg), max_batch=2, max_len=32,
                              prefill_buckets=(8,))
    return engine.decode_step, [engine.example_decode_args([5, 3]),
                                engine.example_decode_args([6, 4])]


def build_serve_verify(fixture=None):
    """The speculative-decoding verify step (``[batch, k+1]`` window)
    against two different slot-length vectors — the ISSUE-13 analogue of
    the serve-decode gate: lengths live inside the static cache, so both
    example signatures are identical and the shape-churn rules must stay
    silent (one compile serves every acceptance pattern)."""
    del fixture  # no optimizer in the serving path
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.serving import GenerationEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                    num_heads=2, max_position_embeddings=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    engine = GenerationEngine(GPTForCausalLM(cfg), max_batch=2, max_len=32,
                              prefill_buckets=(8,), spec_k=3)
    return engine.verify_step, [engine.example_verify_args([5, 3]),
                                engine.example_verify_args([9, 6])]


ZOO = {"mlp": build_mlp, "resnet": build_resnet, "bert": build_bert,
       "serve-decode": build_serve_decode, "serve-verify": build_serve_verify}


def lint_zoo(models, fixture=None, run_steps=0, out=sys.stdout):
    """Returns ``[(model_name, LintReport)]`` (import-friendly: the tests
    drive this directly)."""
    from paddle_tpu import analysis

    results = []
    for name in models:
        step, batches = ZOO[name](fixture=fixture)
        args = batches[0]  # (x, y) train pairs or n-ary serving args
        report = analysis.lint_step(step, *args, extra_args=batches[1:])
        print(f"\n== {name} ({step.name}) ==", file=out)
        print(report.table(), file=out)
        if run_steps > 0:
            from paddle_tpu.profiler import telemetry

            telemetry.reset()
            telemetry.enable()
            try:
                for _ in range(run_steps):
                    step(*args)
                checks = analysis.crosscheck_telemetry(report)
            finally:
                telemetry.disable()
            for c in checks:
                print(f"crosscheck: predicted_retrace="
                      f"{c['predicted_retrace']} observed_compiles="
                      f"{c['observed_compiles']} agrees={c['agrees']}",
                      file=out)
        results.append((name, report))
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--models", nargs="+", default=["mlp", "resnet", "bert"],
                    choices=sorted(ZOO))
    ap.add_argument("--jsonl", default=None,
                    help="write one JSON object per finding to this path")
    ap.add_argument("--format", default="table", choices=["table", "sarif"],
                    help="sarif: emit a SARIF 2.1.0 document on stdout "
                         "(CI annotations) instead of tables")
    ap.add_argument("--fixture", default=None, choices=["adam-lazy"],
                    help="adam-lazy: pre-fix lazy-accumulator optimizer")
    ap.add_argument("--fail-on", default="error",
                    choices=["error", "warning", "never"],
                    help="exit 1 when findings at/above this severity exist")
    ap.add_argument("--run-steps", type=int, default=0,
                    help="also run N real steps per model under telemetry "
                         "and print the lint-vs-telemetry crosscheck")
    args = ap.parse_args(argv)

    sink = open(os.devnull, "w") if args.format == "sarif" else sys.stdout
    results = lint_zoo(args.models, fixture=args.fixture,
                       run_steps=args.run_steps, out=sink)

    if args.format == "sarif":
        from paddle_tpu.analysis import sarif_report

        findings = [f for _, report in results for f in report]
        json.dump(sarif_report(findings, tool="paddle-tpu-graph-lint"),
                  sys.stdout, indent=1)
        sys.stdout.write("\n")

    if args.jsonl:
        with open(args.jsonl, "w") as fh:
            for name, report in results:
                for f in report:
                    fh.write(json.dumps({"model": name, **f.as_dict()},
                                        sort_keys=True) + "\n")
        print(f"\nwrote {sum(len(r) for _, r in results)} findings to "
              f"{args.jsonl}", file=sink)

    n_err = sum(len(r.errors) for _, r in results)
    n_warn = sum(len(r.warnings) for _, r in results)
    print(f"\ngraph lint: {n_err} error(s), {n_warn} warning(s) across "
          f"{len(results)} model(s)", file=sink)
    if args.fail_on == "never":
        return 0
    gate = n_err + (n_warn if args.fail_on == "warning" else 0)
    return 1 if gate else 0


if __name__ == "__main__":
    sys.exit(main())
