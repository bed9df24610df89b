"""Training cells: ``CompiledStep`` fed by ``DeviceLoader``, as a trainer's
loop drives them (construction copied from ``chip_smoke.py``).

Set-up builds one object, the compiled step with its state, drives it from
the seed through its first three steps (reading the loss of each, the first
gradient from Adam's first moment, the parameters' change after the third)
and hands that same object and the same loader to the window. The window
dispatches steps back to back, reading each loss two steps late as a logging
trainer does, and closes on ``block_until_ready`` of the last loss. The
reference follows the same three steps once the window has closed, the peak
memory has been read and the program's state is freed.

Cell parameters (``traffic``): batch_per_chip, seq, reference_block_rows.
One chip: the four-chip ZeRO path is not built here until a PR proves it on
the chips (PERF.md, Open questions).
"""
from __future__ import annotations

import collections
import gc
import statistics
import time

import numpy as np

from harness import heap, loadgen, trace_reduce

FOLLOWED_STEPS = 3
WARM_STEPS = 3  # further steps before the window opens
IN_FLIGHT = 2   # losses read this many steps late


def build(ctx, hooks):
    import paddle_tpu as paddle
    from paddle_tpu.io import DeviceLoader
    from paddle_tpu.jit.functionalize import CompiledStep

    sizes = ctx.sizes
    model = ctx.model.build(sizes, ctx.seed)
    hp = ctx.config["optimizer"]
    opt = paddle.optimizer.AdamW(
        learning_rate=hp["learning_rate"], beta1=hp["beta1"],
        beta2=hp["beta2"], epsilon=hp["epsilon"],
        weight_decay=hp["weight_decay"], parameters=model.parameters(),
        multi_precision=hp["multi_precision"])

    def train_step(ids, labels):
        loss = model.loss(ids, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = CompiledStep(train_step, stateful=[model, opt], donate_state=True,
                        donate_inputs=True)
    step = hooks.get("wrap_step", lambda s, **_: s)(step, model=model, opt=opt)
    traffic = ctx.traffic
    batch = traffic["batch_per_chip"] * ctx.chips
    fed = []  # the first batches, kept for the reference

    def batches():
        for b in loadgen.train_batches(ctx.seed, sizes["vocab_size"], batch,
                                       traffic["seq"]):
            if len(fed) < FOLLOWED_STEPS:
                fed.append((b[0].copy(), b[1].copy()))
            yield b

    loader = DeviceLoader(batches())
    return model, opt, step, loader, fed, batch


def _leaf_sumsq(xs, ys, split):
    """Sum of squares of each ``x - y`` (``y`` may be None); three sums, by
    thirds of the last axis, where ``split`` says so."""
    import jax.numpy as jnp

    out = []
    for x, y, three in zip(xs, ys, split):
        d = x.astype(jnp.float32)
        if y is not None:
            d = d - y.astype(jnp.float32)
        if three:
            d = d.reshape(d.shape[:-1] + (3, d.shape[-1] // 3))
            out.extend(jnp.sum(jnp.square(d[..., j, :])) for j in range(3))
        else:
            out.append(jnp.sum(jnp.square(d)))
    return jnp.stack(out)


def _state_norms(model, opt, what, seed, sizes, W):
    """Per-leaf norms from the optimizer's state: ``grad`` from Adam's first
    moment after one step (m1 = (1 - beta1) g), ``change`` from the float32
    masters (or the float32 leaf itself) against the seeded start."""
    names, arrays, start, split = [], [], [], []
    init = W.split(W.make(seed, sizes, sizes["dtype"])) if what == "change" \
        else None
    masters = opt._accumulators.get("master_weight", {})
    for name, p in model.named_parameters():
        key = opt._pkey(p)
        split.append(W.is_split(name))
        names.extend([f"{name}.{part}" for part in W.PARTS] if split[-1]
                     else [name])
        if what == "grad":
            arrays.append(opt._accumulators["moment1"][key])
            start.append(None)
        else:
            arrays.append(masters.get(key, p._value))
            start.append(init[name])
    import jax

    ss = np.sqrt(np.asarray(jax.jit(_leaf_sumsq, static_argnums=(2,))(
        arrays, start, tuple(split))))
    scale = 1.0 / (1.0 - opt._beta1) if what == "grad" else 1.0
    return {n: float(v) * scale for n, v in zip(names, ss)}


def worst_leaf_gap(prog, ref, keep=None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger; and the leaf."""
    med = statistics.median(ref.values())
    gaps = {n: abs(prog[n] - ref[n]) / max(ref[n], med)
            for n in ref if keep is None or n in keep}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def compare(got, ref, limits):
    """The numbers compared, each beside its limit."""
    med = statistics.median(ref["grad_norms"].values())
    moved = {n for n, g in ref["grad_norms"].items() if g >= 1e-3 * med}
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                   ref["losses"]))
    grad, grad_leaf = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    change, change_leaf = worst_leaf_gap(got["change_norms"],
                                         ref["change_norms"], keep=moved)
    out = {"loss_gap": {"value": loss, "limit": limits["loss_gap"]},
           "grad_norm_gap": {"value": grad, "limit": limits["grad_norm_gap"],
                             "leaf": grad_leaf},
           "change_norm_gap": {"value": change,
                               "limit": limits["change_norm_gap"],
                               "leaf": change_leaf,
                               "leaves_left_out": len(ref["grad_norms"])
                               - len(moved)}}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in out.values())
    return ok, out


def control(ctx):
    """The reference in the program's place, a precision lower (fp8), and
    with a fault planted (half of the batch left out): what each reads by
    the cell's own comparison. No program, no window."""
    import itertools

    sizes, tr = ctx.sizes, ctx.traffic
    fed = list(itertools.islice(loadgen.train_batches(
        ctx.seed, sizes["vocab_size"], tr["batch_per_chip"] * ctx.chips,
        tr["seq"]), FOLLOWED_STEPS))
    args = (ctx.seed, sizes, sizes["dtype"], fed, ctx.config["optimizer"],
            tr["reference_block_rows"])
    reference = ctx.model.reference
    ref = reference.train(*args)
    compared = {}
    for name, kw in (("fp8", {"lowp": "fp8"}),
                     ("half_batch", {"fault": "half_batch"})):
        ok, numbers = compare(reference.train(*args, **kw), ref,
                              ctx.cell["limits"])
        compared.update({f"{name}.{k}": v for k, v in numbers.items()})
        compared[f"{name}.correct"] = {"value": float(ok), "limit": 0.0}
    return {"values": {}, "facts": {}, "attempted": 1, "failed": 0,
            "correct": False, "compared": compared,
            "memory_peak_bytes": ctx.memory_peak()}


def run(ctx, hooks=None):
    import jax

    if ctx.control:
        return control(ctx)
    hooks = hooks or {}
    sizes = ctx.sizes
    model, opt, step, loader, fed, batch = build(ctx, hooks)
    t_built = time.perf_counter()
    it = iter(loader)
    span = jax.profiler.TraceAnnotation

    # the first steps, through the window's own call and feed
    got = {"losses": []}
    for i in range(FOLLOWED_STEPS):
        loss = step(*next(it))
        got["losses"].append(float(np.asarray(loss._value)))
        if i == 0:
            got["grad_norms"] = _state_norms(model, opt, "grad", ctx.seed,
                                             sizes, ctx.model.weights)
    got["change_norms"] = _state_norms(model, opt, "change", ctx.seed, sizes,
                                       ctx.model.weights)
    for _ in range(WARM_STEPS):
        loss = step(*next(it))
    jax.block_until_ready(loss._value)
    t_warm = time.perf_counter()
    ctx.log(f"set-up: build+weights {t_built - ctx.t_import:.1f}s, first "
            f"steps and warm-up {t_warm - t_built:.1f}s; losses "
            f"{got['losses']}")

    tokens_per_step = batch * ctx.traffic["seq"]
    pending = collections.deque()

    def one_step():
        with span("bench:loader_next"):
            ids, labels = next(it)
        with span("bench:step_dispatch"):
            pending.append(step(ids, labels)._value)
        if len(pending) > IN_FLIGHT:
            with span("bench:loss_readback"):
                np.asarray(pending.popleft())

    def drain():
        with span("bench:loss_readback"):
            while pending:
                np.asarray(pending.popleft())

    one_step()
    drain()  # the window opens on an idle device
    length = ctx.trace_seconds if ctx.trace else ctx.seconds
    heap.settle()  # no full collection inside the window
    setup_s = time.perf_counter() - ctx.t_start
    if ctx.trace:
        # a traced run measures a sub-window of a few seconds, profiler on
        trace_reduce.start(ctx.trace_dir)
    t0 = time.perf_counter()
    steps = 0
    with span("bench:window"), heap.Pauses() as pauses:
        while time.perf_counter() - t0 < length or steps < 3:
            one_step()
            steps += 1
        drain()
    window = time.perf_counter() - t0
    ctx.log(f"window: {steps} steps in {window:.3f}s; {pauses.facts()}")
    if ctx.trace:
        jax.profiler.stop_trace()
    loader.shutdown()

    from paddle_tpu.profiler import telemetry
    tm = telemetry.get_telemetry()
    compiles = dict(tm.compile_counts())
    peak = ctx.memory_peak()
    del model, opt, step, loader, it, loss
    gc.collect()

    t_ref = time.perf_counter()
    ref = ctx.model.reference.train(
        ctx.seed, sizes, sizes["dtype"], fed, ctx.config["optimizer"],
        ctx.traffic["reference_block_rows"])
    ok, compared = compare(got, ref, ctx.cell["limits"])
    ctx.log(f"reference: {time.perf_counter() - t_ref:.1f}s; reference "
            f"losses {ref['losses']}")
    failed = 0 if compiles.get("train_step", 1) == 1 else steps
    if failed:
        ctx.log(f"train_step compiled {compiles} times: inside the window")
    return {
        "values": {
            "setup_s": setup_s,
            "train_tokens_per_s_per_chip":
                steps * tokens_per_step / window / ctx.chips},
        "facts": {"window_s": window, "steps": steps,
                  "tokens": steps * tokens_per_step, "batch": batch,
                  "seq": ctx.traffic["seq"], "got": got, "ref": ref},
        "attempted": steps, "failed": failed,
        "correct": ok and not failed, "compared": compared,
        "memory_peak_bytes": peak,
    }
