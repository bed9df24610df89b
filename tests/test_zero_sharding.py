"""Comm-optimized data parallelism (distributed/sharding/zero.py):

* ``ShardedOptimizer`` — ZeRO cross-replica sharded weight update over the
  dp axis (reduce-scatter grads → update the local 1/dp shard → all-gather
  params) must match the replicated-Adam step's losses and cut per-replica
  optimizer-state bytes ~dp-fold;
* int8 collectives with per-block scales and error-feedback residuals —
  the EF telescoping identity makes the quantized stream unbiased over
  steps;
* checkpoint kill-and-resume round-trips the SHARDED optimizer state;
* the ``spmd-replicated-optimizer-state`` lint rule goes quiet under the
  sharded update, and the deliberate param all-gather is a declared
  reshard (no ``spmd-implicit-resharding`` error);
* ``Engine(zero_stage=...)`` / ``Model.prepare(zero=...)`` knobs wire the
  same wrapper.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.distributed.collective import Group
from paddle_tpu.distributed.mesh import build_mesh
from paddle_tpu.distributed.sharding import (
    ShardedOptimizer,
    int8_all_gather,
    int8_all_reduce,
    int8_reduce_scatter,
)
from paddle_tpu.distributed.sharding.zero import (
    dequantize_int8_block,
    quantize_int8_block,
)
from paddle_tpu.framework.tensor import Tensor
from paddle_tpu.jit.functionalize import CompiledStep
from paddle_tpu.utils import unique_name

DP = 8
FP32_RTOL = 1e-5   # XLA:CPU reduction scheduling wiggles the last ulp
INT8_RTOL = 2e-2   # quantized wire: looser, documented contract


def _mlp(seed=0):
    with unique_name.guard():
        paddle.seed(seed)
        return paddle.nn.Sequential(
            paddle.nn.Linear(16, 64), paddle.nn.ReLU(),
            paddle.nn.Linear(64, 16))


def _build(dp=DP, zero=True, quantize=None, seed=0, lr=1e-2):
    mesh = build_mesh({"dp": dp})
    net = _mlp(seed)
    rep = NamedSharding(mesh, P())
    for p in net.parameters():
        p._value = jax.device_put(p._value, rep)
    opt = paddle.optimizer.AdamW(learning_rate=lr,
                                 parameters=net.parameters())
    stepper = (ShardedOptimizer(opt, axis="dp", mesh=mesh,
                                quantize=quantize) if zero else opt)

    def train_step(x, y):
        loss = ((net(x) - y) ** 2).mean()
        loss.backward()
        stepper.step()
        stepper.clear_grad()
        return loss

    step = CompiledStep(train_step, stateful=[net, opt], donate_state=True)
    return mesh, net, opt, step


def _batches(mesh, n, seed=0, batch=16):
    sh = NamedSharding(mesh, P("dp", None))
    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        x = jax.device_put(rng.randn(batch, 16).astype(np.float32), sh)
        y = jax.device_put(rng.randn(batch, 16).astype(np.float32), sh)
        out.append((Tensor(x), Tensor(y)))
    return out


def _losses(step, mesh, n=4, seed=0):
    return [float(np.asarray(step(x, y)._value))
            for x, y in _batches(mesh, n, seed)]


def _local_bytes(arr):
    if hasattr(arr, "sharding") and hasattr(arr.sharding, "shard_shape"):
        shape = arr.sharding.shard_shape(arr.shape)
    else:
        shape = arr.shape
    return int(np.prod(shape)) * arr.dtype.itemsize


def _acc_bytes(opt):
    return sum(_local_bytes(v) for store in opt._accumulators.values()
               for v in store.values())


# ---------------------------------------------------------------------------
# parity + state sharding
# ---------------------------------------------------------------------------

def test_fp32_zero_parity_with_replicated_adam():
    mesh, _, _, base = _build(zero=False)
    want = _losses(base, mesh)
    mesh, _, _, step = _build(zero=True)
    got = _losses(step, mesh)
    np.testing.assert_allclose(got, want, rtol=FP32_RTOL)


def test_int8_zero_parity_within_quantized_contract():
    mesh, _, _, base = _build(zero=False)
    want = _losses(base, mesh)
    mesh, _, _, step = _build(zero=True, quantize="int8")
    got = _losses(step, mesh)
    np.testing.assert_allclose(got, want, rtol=INT8_RTOL)


@pytest.mark.parametrize("quantize", [None, "int8"])
def test_dp_step_compiles_once(quantize):
    """Step 2 must reuse step 1's executable. Two things used to compile it
    again on any multi-device mesh: the optimizer's step counter and the RNG
    key sat on one device and came back with a mesh sharding, and the int8
    residuals were born inside the first trace (the step threads the INNER
    optimizer, so nothing created them ahead of it)."""
    from paddle_tpu.profiler import telemetry

    telemetry.reset()
    telemetry.enable()
    try:
        mesh, _, opt, step = _build(zero=True, quantize=quantize)
        if quantize:
            assert "ef_residual" in opt._accumulators
        _losses(step, mesh, n=3)
        assert telemetry.get_telemetry().compile_counts() == {"train_step": 1}
    finally:
        telemetry.disable()
        telemetry.reset()


def test_optimizer_state_bytes_drop_dp_fold():
    mesh, _, base_opt, base = _build(zero=False)
    _losses(base, mesh, n=1)
    mesh, _, zero_opt, step = _build(zero=True)
    _losses(step, mesh, n=1)
    rep, shard = _acc_bytes(base_opt), _acc_bytes(zero_opt)
    # both Linear weights shard over dp; only the tiny biases (and the
    # scalar beta powers) stay replicated — the ratio lands near DP
    assert rep / shard > 0.8 * DP, (rep, shard)
    # every dp-divisible >=2-D accumulator is born sharded
    checked = 0
    for store in zero_opt._accumulators.values():
        for acc in store.values():
            if getattr(acc, "ndim", 0) >= 2 and acc.shape[0] % DP == 0:
                assert _local_bytes(acc) == acc.nbytes // DP, acc.shape
                checked += 1
    assert checked >= 4  # moment1/moment2 x both weights


# ---------------------------------------------------------------------------
# int8 collectives + error feedback
# ---------------------------------------------------------------------------

def test_int8_quantize_roundtrip_blockwise():
    rng = np.random.RandomState(0)
    x = rng.randn(4, 300).astype(np.float32) * 3.0  # pads 300 -> 2 blocks
    q, scales = quantize_int8_block(x)
    assert q.dtype == jnp.int8 and q.shape == (4, 512)
    assert scales.shape == (4, 2)
    deq = np.asarray(dequantize_int8_block(q, scales, 300))
    assert deq.shape == x.shape
    # per-element error bounded by half a scale step
    bound = np.repeat(np.asarray(scales), 256, axis=-1)[:, :300] * 0.5 + 1e-7
    assert (np.abs(deq - x) <= bound).all()


def test_int8_error_feedback_unbiased_over_steps():
    """EF telescoping: with a CONSTANT input stream the naive quantizer's
    per-step rounding error accumulates linearly, while the residual-
    compensated stream's cumulative error stays bounded by ONE step's
    quantization error (sum_t dequant_t = sum_t x_t + r_0 - r_T)."""
    mesh = build_mesh({"dp": DP})
    g = Group(mesh, "dp")
    rng = np.random.RandomState(1)
    x = (rng.randn(DP, 96).astype(np.float32) * 2.0)
    true_step = np.asarray(x).sum(0)
    T = 30

    # one program for the T steps of the stream: called eagerly, shard_map
    # lowers every primitive of the collective anew on every call
    ef_step = jax.jit(lambda xs, rs: int8_all_reduce(xs, group=g, residual=rs))
    acc_ef = np.zeros_like(true_step)
    r = jnp.zeros(x.shape, jnp.float32)      # what residual=None starts from
    for _ in range(T):
        out, r = ef_step(x, r)
        acc_ef += np.asarray(out)
    # naive: same collective, residual thrown away every step
    out0, _ = int8_all_reduce(x, group=g)
    acc_naive = np.asarray(out0) * T

    err_ef = np.abs(acc_ef - true_step * T).max()
    err_naive = np.abs(acc_naive - true_step * T).max()
    one_step = np.abs(np.asarray(out0) - true_step).max()
    assert err_ef <= one_step * 2.0 + 1e-5, (err_ef, one_step)
    # the naive stream's bias grows ~T-fold; EF must beat it decisively
    assert err_ef < err_naive / 5.0, (err_ef, err_naive)
    # telescoping identity: what's missing is exactly the final residuals
    assert np.allclose(acc_ef + np.asarray(r).sum(0), true_step * T,
                       atol=1e-2)


def test_int8_reduce_scatter_and_all_gather_shapes():
    mesh = build_mesh({"dp": DP})
    g = Group(mesh, "dp")
    rng = np.random.RandomState(2)
    x = rng.randn(DP, DP * 4, 32).astype(np.float32)
    out, r = int8_reduce_scatter(x, group=g)
    assert out.shape == (DP * 4, 32) and r.shape == x.shape
    want = np.asarray(x).sum(0)
    assert np.abs(np.asarray(out) - want).max() < 0.2 * np.abs(want).max()

    shards = rng.randn(DP, 4, 32).astype(np.float32)
    gat, _ = int8_all_gather(shards, group=g)
    assert gat.shape == (DP * 4, 32)
    want = np.asarray(shards).reshape(DP * 4, 32)
    assert np.abs(np.asarray(gat) - want).max() < 0.1 * np.abs(want).max()


# ---------------------------------------------------------------------------
# checkpoint kill-and-resume round-trips sharded optimizer state
# ---------------------------------------------------------------------------

def test_checkpoint_resume_with_sharded_state_dp2(tmp_path):
    from paddle_tpu.fault import CheckpointManager

    dp = 2
    # uninterrupted reference: 5 straight steps
    mesh, _, _, step = _build(dp=dp, zero=True, seed=3)
    want = _losses(step, mesh, n=5, seed=7)

    # killed run: 3 steps, checkpoint, rebuild from scratch, 2 more
    mesh, net, opt, step = _build(dp=dp, zero=True, seed=3)
    first = _losses(step, mesh, n=3, seed=7)
    m = CheckpointManager(str(tmp_path / "ck"))
    m.save(3, {"model": net.state_dict(), "opt": opt.state_dict()})

    mesh2, net2, opt2, step2 = _build(dp=dp, zero=True, seed=99)
    loaded_step, payloads = m.load()
    assert loaded_step == 3
    net2.set_state_dict(payloads["model"])
    opt2.set_state_dict(payloads["opt"])
    # restore re-applies the accumulator transform: moments come back
    # SHARDED, not replicated
    resharded = 0
    for store in opt2._accumulators.values():
        for acc in store.values():
            if getattr(acc, "ndim", 0) >= 2 and acc.shape[0] % dp == 0:
                assert _local_bytes(acc) == acc.nbytes // dp, acc.shape
                resharded += 1
    assert resharded >= 4
    batches = _batches(mesh2, 5, seed=7)
    rest = [float(np.asarray(step2(x, y)._value)) for x, y in batches[3:]]
    np.testing.assert_allclose(first + rest, want, rtol=1e-4)


# ---------------------------------------------------------------------------
# lint contract: rule quiet under the sharded update, all-gather declared
# ---------------------------------------------------------------------------

def test_replicated_state_rule_quiet_and_gather_declared():
    mesh, _, _, step = _build(zero=True)
    x, y = _batches(mesh, 1)[0]
    report = analysis.lint_step(step, x, y, mesh=mesh,
                                config={"zero_min_bytes": 1024})
    assert not report.by_rule("spmd-replicated-optimizer-state")
    # the deliberate ZeRO param all-gather comes from a sharding-policy
    # module: priced, but never an implicit-resharding finding
    assert not report.by_rule("spmd-implicit-resharding")
    # the plain step DOES trip the rule with the same floor (the contrast
    # proves quiet-for-the-right-reason, not a broken rule)
    mesh, _, _, base = _build(zero=False)
    x, y = _batches(mesh, 1)[0]
    dirty = analysis.lint_step(base, x, y, mesh=mesh,
                               config={"zero_min_bytes": 1024})
    assert dirty.by_rule("spmd-replicated-optimizer-state")


# ---------------------------------------------------------------------------
# Engine / hapi knobs
# ---------------------------------------------------------------------------

def test_engine_zero_stage_wraps_optimizer():
    from paddle_tpu.distributed.auto_parallel import ProcessMesh
    from paddle_tpu.distributed.auto_parallel.engine import Engine

    net = _mlp(seed=4)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    pm = ProcessMesh(np.arange(DP), dim_names=["dp"])
    eng = Engine(model=net, loss=lambda out, y: ((out - y) ** 2).mean(),
                 optimizer=opt, process_mesh=pm, zero_stage=1)
    eng._apply_strategy()
    assert isinstance(eng._optimizer, ShardedOptimizer)
    assert eng._optimizer._inner_opt is opt

    class _DS:
        def __init__(self, n=DP * 4):
            rng = np.random.RandomState(5)
            self.x = rng.randn(n, 16).astype(np.float32)
            self.y = rng.randn(n, 16).astype(np.float32)

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return len(self.x)

    hist = eng.fit(_DS(), batch_size=DP * 2, epochs=1, prefetch=0)
    assert np.isfinite(hist["loss"][-1])
    for store in opt._accumulators.values():
        for acc in store.values():
            if getattr(acc, "ndim", 0) >= 2 and acc.shape[0] % DP == 0:
                assert _local_bytes(acc) == acc.nbytes // DP


def test_hapi_prepare_zero_knob():
    from paddle_tpu.hapi import Model

    mesh = build_mesh({"dp": DP})
    net = _mlp(seed=6)
    rep = NamedSharding(mesh, P())
    for p in net.parameters():
        p._value = jax.device_put(p._value, rep)
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=net.parameters())
    model = Model(net)
    model.prepare(optimizer=opt, loss=paddle.nn.MSELoss(),
                  zero={"axis": "dp", "mesh": mesh, "quantize": "int8"})
    assert isinstance(model._optimizer, ShardedOptimizer)
    assert model._optimizer._inner_opt is opt
    assert model._optimizer._quantize == "int8"
    # fit() stages each batch split over the zero axis (the default put
    # would land it whole on device 0); a batch dp does not divide is
    # replicated over the mesh
    x, y, short = (np.zeros((2 * DP, 16), np.float32),
                   np.zeros((2 * DP, 16), np.float32),
                   np.zeros((DP + 1, 16), np.float32))
    staged = next(iter(model._device_loader([(x, y, short)])))
    assert staged[0].sharding == NamedSharding(mesh, P("dp"))
    assert staged[0].addressable_shards[0].data.shape == (2, 16)
    assert staged[2].sharding == NamedSharding(mesh, P())
