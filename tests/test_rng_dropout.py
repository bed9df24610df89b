"""Per-tick RNG for pipelined dropout + static-mode per-run dropout.
VERDICT item 8 + ADVICE medium (static dropout baked as constant).
Reference: fleet/meta_parallel/parallel_layers/random.py RNGStatesTracker."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn.functional as F
from paddle_tpu.distributed import fleet
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.models import GPTConfig
from paddle_tpu.utils import unique_name


def _init_fleet(pp=2):
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs["dp_degree"] = 1
    strategy.hybrid_configs["mp_degree"] = 1
    strategy.hybrid_configs["pp_degree"] = pp
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group()


def _build_piped(cfg, hcg, micro):
    from paddle_tpu.distributed.meta_parallel import build_pipelined_gpt

    return build_pipelined_gpt(cfg, hcg, num_microbatches=micro)


def test_pipelined_dropout_trains_and_varies():
    """dropout>0 no longer raises; identical microbatch contents produce
    different losses across steps (fresh masks), and eval mode is
    deterministic."""
    hcg = _init_fleet(pp=2)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=32, hidden_dropout=0.5,
                    attention_dropout=0.0)
    with unique_name.guard():
        paddle.seed(0)
        piped = _build_piped(cfg, hcg, micro=2)

    ids = Tensor(np.random.RandomState(0).randint(0, 64, (4, 16)).astype(np.int64))

    # forward only: nothing here calls backward, and with grad recording on
    # each eager loss also lowers the schedule's vjp
    with paddle.no_grad():
        piped.train()
        l1 = float(np.asarray(piped.loss(ids, ids)._value))
        l2 = float(np.asarray(piped.loss(ids, ids)._value))
        assert np.isfinite(l1) and np.isfinite(l2)
        assert l1 != l2, "train-mode dropout produced identical losses across steps"

        piped.eval()
        e1 = float(np.asarray(piped.loss(ids, ids)._value))
        e2 = float(np.asarray(piped.loss(ids, ids)._value))
        assert e1 == e2, "eval mode must be deterministic"


def test_pipelined_dropout_masks_differ_across_microbatches():
    """Two microbatches with IDENTICAL content must get different masks."""
    hcg = _init_fleet(pp=2)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=32, hidden_dropout=0.5,
                    attention_dropout=0.0)
    with unique_name.guard():
        paddle.seed(0)
        piped = _build_piped(cfg, hcg, micro=2)
    piped.train()

    row = np.random.RandomState(1).randint(0, 64, (1, 16)).astype(np.int64)
    ids = Tensor(np.repeat(row, 4, axis=0))  # 4 identical rows, 2 microbatches
    out = piped(ids)  # [batch, seq, vocab] logits (no labels)
    a = np.asarray(out._value)
    # microbatch 0 = rows 0..1, microbatch 1 = rows 2..3; identical inputs,
    # different dropout ticks -> different activations
    assert not np.allclose(a[0], a[2]), "identical microbatches got identical masks"


def test_pipelined_dropout_eval_matches_single_device():
    """Eval-mode (dropout off) parity with the plain model is preserved."""
    from paddle_tpu.models import GPTForCausalLM
    from tests.test_pipeline_schedule import _copy_gpt_into_pipeline

    hcg = _init_fleet(pp=2)
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2, num_heads=2,
                    max_position_embeddings=32, hidden_dropout=0.3,
                    attention_dropout=0.0)
    with unique_name.guard():
        paddle.seed(0)
        ref = GPTForCausalLM(cfg)
    with unique_name.guard():
        paddle.seed(1)
        piped = _build_piped(cfg, hcg, micro=2)
    _copy_gpt_into_pipeline(ref, piped, pp=2, per=1)

    ids = Tensor(np.random.RandomState(2).randint(0, 64, (4, 16)).astype(np.int64))
    ref.eval()
    piped.eval()
    l_ref = float(np.asarray(ref.loss(ids, ids)._value))
    l_pp = float(np.asarray(piped.loss(ids, ids)._value))
    np.testing.assert_allclose(l_pp, l_ref, rtol=2e-5)


def test_static_dropout_fresh_per_run():
    """ADVICE medium: static programs must draw fresh dropout masks per
    Executor.run (the mask is an in-graph op on the threaded RNG key, not a
    recorded constant)."""
    paddle.enable_static()
    try:
        main = paddle.static.Program()
        startup = paddle.static.Program()
        with paddle.static.program_guard(main, startup):
            x = paddle.static.data("x", [8, 32], "float32")
            y = F.dropout(x, p=0.5, training=True)
            out_name = y.name
        exe = paddle.static.Executor()
        exe.run(startup)
        xv = np.ones((8, 32), np.float32)
        (r1,) = exe.run(main, feed={"x": xv}, fetch_list=[out_name])
        (r2,) = exe.run(main, feed={"x": xv}, fetch_list=[out_name])
        assert not np.allclose(r1, r2), "static dropout replayed an identical mask"
        # scale check: surviving entries are upscaled by 1/(1-p)
        kept = r1[r1 != 0]
        np.testing.assert_allclose(kept, 2.0, rtol=1e-6)
        # determinism under paddle.seed
        paddle.seed(7)
        (a1,) = exe.run(main, feed={"x": xv}, fetch_list=[out_name])
        paddle.seed(7)
        (a2,) = exe.run(main, feed={"x": xv}, fetch_list=[out_name])
        np.testing.assert_allclose(a1, a2)
    finally:
        paddle.disable_static()


def test_static_dropout_grad_consistent_with_forward():
    """The backward replay must see the SAME mask as the forward (both read
    the same per-run key)."""
    paddle.enable_static()
    try:
        main = paddle.static.Program()
        startup = paddle.static.Program()
        with paddle.static.program_guard(main, startup):
            x = paddle.static.data("x", [4, 8], "float32")
            x.stop_gradient = False
            lin = paddle.nn.Linear(8, 8)
            h = lin(x)
            d = F.dropout(h, p=0.5, training=True)
            loss = (d * d).sum()
            pairs = paddle.static.append_backward(loss)
        w_name = lin.weight.name
        exe = paddle.static.Executor()
        exe.run(startup)
        xv = np.random.RandomState(0).randn(4, 8).astype(np.float32)
        out, g = exe.run(main, feed={"x": xv},
                         fetch_list=[d.name, f"{w_name}@GRAD"])
        # d(loss)/dw = x^T @ (2*d*mask*scale); where out==0 the grad
        # contribution must vanish -> check grad is finite and nonzero
        assert np.isfinite(g).all() and (g != 0).any()
    finally:
        paddle.disable_static()


def test_static_clone_for_test_disables_dropout():
    """Program.clone(for_test=True) must run dropout as identity (reference
    clone(for_test=True) semantics)."""
    paddle.enable_static()
    try:
        main = paddle.static.Program()
        startup = paddle.static.Program()
        with paddle.static.program_guard(main, startup):
            x = paddle.static.data("x", [4, 16], "float32")
            d = F.dropout(x, p=0.5, training=True)
        eval_prog = main.clone(for_test=True)
        exe = paddle.static.Executor()
        exe.run(startup)
        xv = np.random.RandomState(0).randn(4, 16).astype(np.float32)
        (r1,) = exe.run(eval_prog, feed={"x": xv}, fetch_list=[d.name])
        (r2,) = exe.run(eval_prog, feed={"x": xv}, fetch_list=[d.name])
        np.testing.assert_allclose(r1, xv, atol=1e-7)  # identity, no mask
        np.testing.assert_allclose(r1, r2)
        # the train program still masks
        (t1,) = exe.run(main, feed={"x": xv}, fetch_list=[d.name])
        assert (t1 == 0).any()
    finally:
        paddle.disable_static()


def test_static_clone_for_test_downscale_mode():
    """downscale_in_infer dropout must become x*(1-p) at eval, not identity."""
    paddle.enable_static()
    try:
        main = paddle.static.Program()
        startup = paddle.static.Program()
        with paddle.static.program_guard(main, startup):
            x = paddle.static.data("x", [4, 16], "float32")
            d = F.dropout(x, p=0.5, training=True, mode="downscale_in_infer")
        eval_prog = main.clone(for_test=True)
        exe = paddle.static.Executor()
        exe.run(startup)
        xv = np.random.RandomState(0).randn(4, 16).astype(np.float32)
        (r,) = exe.run(eval_prog, feed={"x": xv}, fetch_list=[d.name])
        np.testing.assert_allclose(r, 0.5 * xv, atol=1e-7)
    finally:
        paddle.disable_static()
