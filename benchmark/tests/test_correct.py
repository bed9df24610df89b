"""``correct`` has to be able to come out false. Each test skips the
harness's look for a chip (``--rehearse`` sizes on the CPU) and drives the
rest of a run with the timed path broken underneath, or puts the control
in the program's place."""
import numpy as np
import pytest

import run as bench
from paddle_tpu.ops import pallas


def ctx_of(workload, seed=21, seconds=1.0, **over):
    args = bench.parser().parse_args(
        ["--workload", workload, "--seed", str(seed), "--seconds",
         str(seconds), "--rehearse"])
    ctx = bench.make_ctx(args, seed)
    ctx.cell = {**ctx.cell, **over}
    return ctx


def drive(ctx, hooks=None):
    import importlib

    driver = importlib.import_module(f"drivers.{ctx.cell['driver']}")
    with pallas.interpret_mode():
        return driver.run(ctx, hooks)


TRAIN = "gpt2_124m.train_b24_s1024"
#: limits of the order the cell holds on the chip, for the tiny CPU model
TRAIN_LIMITS = {"loss_gap": 2e-4, "grad_norm_gap": 0.03,
                "change_norm_gap": 0.03}


def unchanged_state(step, model, opt):
    """A step that returns its state unchanged."""
    import jax.numpy as jnp

    def call(ids, labels):
        params = [(p, jnp.copy(p._value)) for p in model.parameters()]
        accs = {n: {k: jnp.copy(v) for k, v in st.items()}
                for n, st in opt._accumulators.items()}
        loss = step(ids, labels)
        for p, v in params:
            p._value = v
        for n, st in opt._accumulators.items():
            for k in st:
                st[k] = accs[n][k] if k in accs.get(n, {}) \
                    else jnp.zeros_like(st[k])
        return loss

    return call


def half_batch(step, model, opt):
    """Half of the batch left out, the mean taken over the rest."""
    import jax.numpy as jnp

    def call(ids, labels):
        h = ids.shape[0] // 2
        return step(jnp.concatenate([ids[:h], ids[:h]]),
                    jnp.concatenate([labels[:h], labels[:h]]))

    return call


def test_sound_training_run_is_correct():
    out = drive(ctx_of(TRAIN, limits=TRAIN_LIMITS))
    assert out["correct"], out["compared"]


@pytest.mark.parametrize("fault", [unchanged_state, half_batch])
def test_training_fault_is_not_correct(fault):
    out = drive(ctx_of(TRAIN, limits=TRAIN_LIMITS),
                {"wrap_step": lambda s, model, opt: fault(s, model, opt)})
    assert not out["correct"], out["compared"]
    assert any(v["value"] > v["limit"] for v in out["compared"].values())


def test_training_control_reads_above_the_program():
    """The reference a precision lower (fp8) in the program's place: at
    this size it reads over the limits that the sound run above keeps."""
    import importlib

    ctx = ctx_of(TRAIN, limits=TRAIN_LIMITS)
    ctx.control = True
    out = importlib.import_module("drivers.train").control(ctx)
    assert out["compared"]["half_batch.correct"]["value"] == 0.0
    fp8 = {k: v["value"] for k, v in out["compared"].items()
           if k.startswith("fp8.") and k != "fp8.correct"}
    assert all(np.isfinite(v) for v in fp8.values())


SERVE = "gpt2_large.serve_chat_steady"


def altered_tokens(eng):
    """Every decoded token altered where it is produced."""
    real = eng.decode_once

    def decode_once(last_tokens):
        return (real(last_tokens) + 1) % 500

    eng.decode_once = decode_once
    return eng


def test_sound_serving_run_is_correct():
    out = drive(ctx_of(SERVE, seconds=3.0,
                       limits={"served_logit_gap": 0.05}))
    assert out["correct"], out["compared"]
    assert out["compared"]["served_logit_gap"]["tokens"] > 20


def test_serving_control_reads_above_the_program():
    """The token that fp8 puts first, at the positions served: it lies
    further under the reference's best than the served one does."""
    ctx = ctx_of(SERVE, seconds=3.0, limits={"served_logit_gap": 0.05})
    ctx.control = True
    out = drive(ctx)
    c = out["compared"]
    assert c["fp8.served_logit_gap"]["value"] > 3 * max(
        c["served_logit_gap"]["value"], 1e-3)


def test_altered_token_is_not_correct():
    out = drive(ctx_of(SERVE, seconds=3.0,
                       limits={"served_logit_gap": 0.05}),
                {"wrap_engine": altered_tokens})
    assert not out["correct"], out["compared"]
    assert out["compared"]["served_logit_gap"]["value"] > 0.05
