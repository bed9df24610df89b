"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of GPT-2 124M (vocab 50304, h768, L12, 12 heads,
bf16 + fp32 LayerNorm, AdamW with fp32 masters), weights random from a seed:

* train: ``CompiledStep(train_step, stateful=[model, opt], donate_state=True,
  donate_inputs=True)`` fed by ``io.DeviceLoader``, b24 s1024, a few steps
  on one repeated batch — loss finite, first loss near ln(vocab), falling;
* kernels: every Pallas kernel the run selects against the repo's own XLA
  formulation of that op at the shapes used (values, stated tolerance);
* serve: the same architecture through ``serving.Scheduler`` twice — the
  engine as its constructor defaults give it, and the speed-v2 engine
  (``spec_k=4, prefill_chunk=128``) — a dozen seeded requests in all, each
  engine's request set replayed through the same executables (tokens must
  repeat exactly), every fault / fallback / recompile counter at 0;
* expert decoders: the benchmark's Solar Open 2 and Laguna cuts through
  ``Scheduler`` at their published widths (delta-rule state; rotary
  positions with rings of 512 K/V rows beside full-length rows), the routes
  their traced steps took, their kernels against the XLA formulations;
* dp4: when four devices are visible, the same trainer under
  ``build_mesh({"dp": 4})`` + ``ShardedOptimizer`` (fp32 wire, then int8),
  with per-device shard evidence and loss parity against the one-chip leg.
  With fewer devices it says "not run", never "ok".

Run with no arguments it needs a TPU and exits non-zero without one.
``--rehearse`` is the explicit tiny CPU rehearsal (``JAX_PLATFORMS=cpu``,
Pallas interpreted) for debugging the script itself; it proves nothing
about the chip. It prints versions, device, compile seconds per step and
``peak_bytes_in_use``; no rate and no utilization. The last line of stdout
is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import gc
import json
import math
import os
import re
import sys
import time

import numpy as np

Size = collections.namedtuple(
    "Size", "vocab hidden layers heads max_len batch seq")
#: the training cell's shapes (``gpt2_124m.train_b24_s1024``)
FULL = Size(vocab=50304, hidden=768, layers=12, heads=12, max_len=1024,
            batch=24, seq=1024)
#: --rehearse: same sequence lengths (so the attention router takes the same
#: routes) at a width and depth the CPU interpreter finishes in a minute
TINY = Size(vocab=512, hidden=128, layers=2, heads=2, max_len=1024,
            batch=4, seq=1024)

TRAIN_STEPS = 6
#: (label, engine arguments, prompt lengths, new tokens per request). The
#: default engine gets a prompt over 512 tokens so the 1024 bucket takes the
#: cached flash kernel at sq1024; on the speed-v2 engine prompts <= 128 take
#: the short buckets (einsum) and longer ones stream through
#: serve_prefill_chunk (cached kernel at sq128 x sk1024); decode and verify
#: (sq 1 and 5) take the decode-shaped kernel on both: nothing is left for
#: the blockwise scan (the rehearsal interprets the same kernels).
SERVE_LEGS = (
    ("default", {}, [12, 100, 600, 7, 90, 13], [16, 12, 24, 16, 8, 20]),
    ("speed-v2", {"spec_k": 4, "prefill_chunk": 128},
     [9, 100, 300, 700, 120, 14], [16, 12, 24, 16, 8, 20]),
)
#: what ``--legs`` may name, in the order they run
LEGS = ("train", "kernels", "serve", "delta_rule", "ring",
        "column_kernel", "row_dma", "dp4")
#: kernel vs XLA formulation in fp32/highest: max|a-b| / max|b|. Forward
#: outputs are one bf16 rounding apart; gradients accumulate bf16 products
#: over 1024 keys (attention) or 24k rows (LayerNorm dgamma/dbeta).
FWD_TOL = 1e-2
GRAD_TOL = 3e-2
#: dp4 loss against the one-chip leg on the same data, per step: same math,
#: a different reduction order over bf16 (fp32 wire); int8 also rounds the
#: gathered weights
DP_FP32_RTOL = 1e-2
DP_INT8_RTOL = 2e-2


def say(msg):
    print(msg, flush=True)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def ops_traced():
    """Count framework ops by registry name while they are traced: which
    attention / LayerNorm route each compiled step actually took."""
    from paddle_tpu.ops import dispatch

    seen = collections.Counter()
    orig = dispatch.apply_op

    def counting(name, *a, **k):
        seen[name] += 1
        return orig(name, *a, **k)

    dispatch.apply_op = counting
    try:
        yield seen
    finally:
        dispatch.apply_op = orig


class CacheStats:
    """Persistent-compilation-cache traffic, from jax's own monitoring
    events: a warm second run must show requests == hits."""

    def __init__(self):
        import jax

        self.n = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event.startswith("/jax/compilation_cache/"):
            self.n[event.rsplit("/", 1)[1]] += 1

    def line(self, cache_dir):
        return (f"compile cache: dir={cache_dir} "
                f"requests={self.n['compile_requests_use_cache']} "
                f"hits={self.n['cache_hits']} "
                f"compiled_and_written={self.n['cache_misses']}")


# ---------------------------------------------------------------------------
# builders (tools/tpu_aot_preflight.py compiles the same objects ahead of time)
# ---------------------------------------------------------------------------
def build_model(size):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(
        vocab_size=size.vocab, hidden_size=size.hidden,
        num_layers=size.layers, num_heads=size.heads,
        max_position_embeddings=size.max_len, hidden_dropout=0.0,
        attention_dropout=0.0)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    for _, sub in model.named_sublayers():
        if type(sub).__name__ == "LayerNorm":
            sub.to(dtype="float32")  # fp32 LayerNorm for stability
    return model


def build_trainer(size, mesh=None, quantize=None):
    """The GPT train step (AdamW with fp32 master weights). With ``mesh``:
    parameters replicated over it and the update sharded over ``dp`` by
    ``ShardedOptimizer`` (ZeRO)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.jit.functionalize import CompiledStep

    model = build_model(size)
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        for p in model.parameters():
            p._value = jax.device_put(p._value, rep)
    opt = paddle.optimizer.AdamW(
        learning_rate=1e-4, parameters=model.parameters(),
        multi_precision=True)
    stepper = opt
    if mesh is not None:
        from paddle_tpu.distributed.sharding import ShardedOptimizer

        stepper = ShardedOptimizer(opt, axis="dp", mesh=mesh,
                                   quantize=quantize)

    def train_step(ids, labels):
        loss = model.loss(ids, labels)
        loss.backward()
        stepper.step()
        stepper.clear_grad()
        return loss

    # stateful threads the INNER optimizer: the ZeRO wrapper owns no arrays
    step = CompiledStep(train_step, stateful=[model, opt], donate_state=True,
                        donate_inputs=True)
    return model, opt, step


def build_engine(size, **kw):
    from paddle_tpu.serving import GenerationEngine

    return GenerationEngine(build_model(size), max_batch=8,
                            max_len=size.max_len, **kw)


def build_delta_rule_engine(tiny, max_batch, max_len, **kw):
    """The benchmark's Solar Open 2 cut through the normal constructor: the
    published widths (the config's defaults), the first 4 layers ``G K K
    K``, 40 of the 320 routed experts, 24,576 vocabulary rows; ``tiny``
    keeps the kinds, the 128-wide heads and two K/V heads (so the same
    routes are taken) at a size the interpreter finishes."""
    import paddle_tpu as paddle
    from paddle_tpu.models import SolarOpen2Config, SolarOpen2ForCausalLM
    from paddle_tpu.serving import GenerationEngine

    cut = dict(num_hidden_layers=4, gqa_layers=(0,))
    if tiny:
        cfg = SolarOpen2Config(
            vocab_size=512, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, kda_num_heads=8, kda_gate_rank=16,
            n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=128, held_experts=(0, 1, 2, 3), **cut)
    else:
        cfg = SolarOpen2Config(vocab_size=24576,
                               held_experts=tuple(range(40)), **cut)
    paddle.seed(0)
    return GenerationEngine(SolarOpen2ForCausalLM(cfg), max_batch=max_batch,
                            max_len=max_len, **kw)


def build_ring_engine(tiny, max_batch, max_len, **kw):
    """The benchmark's Laguna cut through the normal constructor: the
    published widths (the config's defaults), the first 13 layers ``F S S S
    F S S S F S S S F`` (4 full, 9 window of 512), 32 of the 256 routed
    experts, 12,544 vocabulary rows; ``tiny`` keeps the kinds, the window,
    the 128-wide heads and two K/V heads (so the same routes are taken) at
    a size the interpreter finishes."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LagunaConfig, LagunaForCausalLM
    from paddle_tpu.serving import GenerationEngine

    if tiny:
        cfg = LagunaConfig(
            vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=13,
            num_attention_heads_per_layer=(4, 8, 8, 8) * 10,
            num_key_value_heads=2, num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=128, shared_expert_intermediate_size=128,
            held_experts=(0, 1, 2, 3))
    else:
        cfg = LagunaConfig(vocab_size=12544, num_hidden_layers=13,
                           held_experts=tuple(range(32)))
    paddle.seed(0)
    return GenerationEngine(LagunaForCausalLM(cfg), max_batch=max_batch,
                            max_len=max_len, **kw)


def train_batches(size):
    """One seeded batch of random tokens with next-token labels, repeated:
    every copy is a fresh host array because the step donates its inputs.
    (Labels equal to the inputs would start far below ln(vocab): the tied
    head scores a position's own token highest at initialization.)"""
    a = np.random.RandomState(0).randint(
        0, size.vocab, (size.batch, size.seq + 1)).astype(np.int32)
    return [(a[:, :-1].copy(), a[:, 1:].copy()) for _ in range(TRAIN_STEPS)]


def run_steps(step, loader):
    """Drive the step over the loader; returns (losses, first-call seconds,
    mean seconds of the later calls). Each loss is read back, so a call's
    time includes its execution."""
    losses, times = [], []
    for ids, labels in loader:
        t0 = time.perf_counter()
        loss = step(ids, labels)
        losses.append(float(np.asarray(loss._value)))
        times.append(time.perf_counter() - t0)
    return losses, times[0], sum(times[1:]) / max(1, len(times) - 1)


def mosaic_operand_shapes(hlo_text):
    """Operand shapes of every Mosaic custom call in a compiled program's
    per-device HLO, one tuple of ``dtype[dims]`` strings per call."""
    return [tuple(re.findall(r"(?:bf16|f32|s32|u32)\[[\d,]*\]",
                             line.split("custom-call(")[1]))
            for line in hlo_text.splitlines()
            if "tpu_custom_call" in line and "custom-call(" in line]


def peak_bytes(device):
    """``peak_bytes_in_use`` + ``peak_bytes_reserved``: on this libtpu the
    first counts buffers only, the second the programs' temporaries."""
    stats = device.memory_stats()
    if not stats:
        return "not reported"
    return (f"{stats.get('peak_bytes_in_use')} in use + "
            f"{stats.get('peak_bytes_reserved')} reserved")


# ---------------------------------------------------------------------------
# legs
# ---------------------------------------------------------------------------
def leg_train(size):
    import jax

    from paddle_tpu.io import DeviceLoader
    from paddle_tpu.profiler import telemetry

    telemetry.reset()
    model, opt, step = build_trainer(size)
    with ops_traced() as seen:
        losses, first_s, later_s = run_steps(
            step, DeviceLoader(train_batches(size)))
    say(f"train: b{size.batch} s{size.seq} L{size.layers} h{size.hidden} "
        f"losses {[round(v, 4) for v in losses]}")
    say(f"train: first call (trace+compile+run) {first_s:.1f}s, later calls "
        f"{later_s:.3f}s each; peak bytes {peak_bytes(jax.devices()[0])}")
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - math.log(size.vocab)) < 0.5,
          f"first loss {losses[0]:.3f} not near ln(vocab)="
          f"{math.log(size.vocab):.3f}")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"loss not falling on a repeated batch: {losses}")
    counts = telemetry.get_telemetry().compile_counts()
    check(counts.get("train_step") == 1,
          f"train_step compiled {counts.get('train_step')} times")
    check(seen["flash_sdpa"] == size.layers
          and seen["fused_layer_norm"] == 2 * size.layers + 1
          and not seen["sdpa"] and not seen["layer_norm_op"]
          and not seen["blockwise_sdpa"],
          f"train step did not take the Pallas routes: {dict(seen)}")
    say(f"train: routes flash_sdpa x{seen['flash_sdpa']}, fused_layer_norm "
        f"x{seen['fused_layer_norm']}; ok")
    return losses


def _rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def leg_kernels(size):
    """Each Pallas kernel the train and serve legs select, against the XLA
    formulation of the same op on the same inputs upcast to fp32 at HIGHEST
    matmul precision."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.attention import (LengthMask, _sdpa_flash,
                                                    _sdpa_flash_cached,
                                                    _sdpa_flash_decode,
                                                    _sdpa_raw)
    from paddle_tpu.nn.functional.norm import (_layer_norm_pallas,
                                               _layer_norm_raw)

    bf, f32 = jnp.bfloat16, jnp.float32
    b, s, h = size.batch, size.seq, size.heads
    d = size.hidden // h
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 32))

    def rnd(shape, dtype):
        return jax.random.normal(next(keys), shape, f32).astype(dtype)

    def up(x):
        return x.astype(f32) if jnp.issubdtype(x.dtype, jnp.floating) else x

    def compare(name, kernel, reference, args, n_grad):
        """Forward always; gradients w.r.t. the first ``n_grad`` args under a
        fixed random cotangent. Returns the kernel's output."""
        out = jax.jit(kernel)(*args)
        ct = rnd(out.shape, f32)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(reference)(*map(up, args))
        errs = {"fwd": _rel_err(out, ref)}
        if n_grad:
            def scalar(fn):
                return lambda *a: jnp.sum(fn(*a).astype(f32) * ct)

            argnums = tuple(range(n_grad))
            g = jax.jit(jax.grad(scalar(kernel), argnums))(*args)
            with jax.default_matmul_precision("highest"):
                gr = jax.jit(jax.grad(scalar(reference), argnums))(
                    *map(up, args))
            for i, (x, y) in enumerate(zip(g, gr)):
                errs[f"d{i}"] = _rel_err(x, y)
        say(f"kernels: {name}: " + " ".join(
            f"{k}={v:.2e}" for k, v in errs.items()))
        check(np.all(np.isfinite(np.asarray(out, np.float32))),
              f"{name}: kernel output not finite")
        for k, v in errs.items():
            tol = FWD_TOL if k == "fwd" else GRAD_TOL
            check(v <= tol, f"{name}: {k} error {v:.3e} over tolerance {tol}")
        return out

    compare(f"fused_layer_norm [{b},{s},{size.hidden}] bf16",
            lambda x, g, be: _layer_norm_pallas.raw(x, g, be),
            lambda x, g, be: _layer_norm_raw.raw(
                x, g, be, begin_axis=2, has_w=True, has_b=True),
            (rnd((b, s, size.hidden), bf), 1.0 + 0.1 * rnd((size.hidden,), f32),
             0.1 * rnd((size.hidden,), f32)), 3)
    compare(f"flash_sdpa causal [{b},{s},{h},{d}] bf16",
            lambda q, k, v: _sdpa_flash.raw(q, k, v, causal=True,
                                            packed=True),
            lambda q, k, v: _sdpa_raw.raw(q, k, v, causal=True),
            tuple(rnd((b, s, h, d), bf) for _ in range(3)), 3)
    # the cached kernel as serving reaches it: one request's queries over
    # its full cache row — the 1024 prefill bucket and a 128-token chunk
    sk = size.max_len
    for sq, off, klen in ((sk, 0, 600), (128, 256, None)):
        q_pos = (off + jnp.arange(sq, dtype=jnp.int32))[None, :]
        kv_len = None if klen is None else jnp.asarray([klen], jnp.int32)
        mask = LengthMask(q_pos, kv_len)

        def cached(q, k, v, mask=mask):
            return _sdpa_flash_cached.raw(q, k, v, mask.q_pos, mask.kv_len)

        def dense(q, k, v, mask=mask, sk=sk):
            return _sdpa_raw.raw(q, k, v, mask.additive(sk, q.dtype))

        compare(f"flash_sdpa_cached sq{sq} sk{sk} b1 bf16", cached, dense,
                (rnd((1, sq, h, d), bf), rnd((1, sk, h, d), bf),
                 rnd((1, sk, h, d), bf)), 0)
    # the decode kernel as the serving cells' decode step reaches it (32
    # slots of 1024 positions, GPT-2 large's 20 heads of 64; the rehearsal
    # keeps its own heads): every slot's one query row (verify: spec_k + 1
    # rows) over its own cache row, 10 slots live, each at a length of its
    # own (the first position, a block boundary, the last positions, the
    # rest), and 22 that hold no request (no valid key: q_pos -1), which the
    # kernel neither fetches nor visits. Live rows: the dense reference's
    # within a rounding, and BIT FOR BIT those of the kernel that visited
    # every slot (tests/flash_decode_slot_grid.py); dead rows: zeros
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "tests"))
    from flash_decode_slot_grid import flash_attention_decode_slot_grid
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas.flash_decode import _decode_block

    db = 32
    wh = 20 if size is FULL else h
    lens = jnp.asarray(np.random.RandomState(0).randint(16, sk // 2, (db,)),
                       jnp.int32).at[:3].set(jnp.asarray([0, 255, sk - 5]))
    live = np.zeros((db,), bool)
    live[[0, 1, 2, 5, 6, 9, 13, 14, 20, 31]] = True
    for sq in (1, 5):
        mask = LengthMask(jnp.where(
            live[:, None], lens[:, None] + jnp.arange(sq, dtype=jnp.int32),
            -1))
        args = (rnd((db, sq, wh, d), bf), rnd((db, sk, wh, d), bf),
                rnd((db, sk, wh, d), bf))

        def decode(q, k, v, mask=mask):
            return _sdpa_flash_decode.raw(q, k, v, mask.q_pos)

        def dense(q, k, v, mask=mask, sk=sk):
            out = _sdpa_raw.raw(q, k, v, mask.additive(sk, q.dtype))
            return jnp.where(live[:, None, None, None], out, 0.0)

        name = f"flash_sdpa_decode sq{sq} sk{sk} b{db} {wh}x{d} bf16"
        got = np.asarray(compare(f"{name}, 22 slots dead", decode, dense,
                                 args, 0), np.float32)
        want = np.asarray(jax.jit(
            lambda q, k, v, mask=mask: flash_attention_decode_slot_grid(
                q, k, v, mask.q_pos,
                block_k=_decode_block(sk, wh * d, 2),
                interpret=pallas.interpret_requested()))(*args), np.float32)
        same = bool(np.array_equal(got[live], want[live]))
        zeros = not got[~live].any()
        say(f"kernels: {name}: live rows equal to the slot-grid kernel's: "
            f"{same}; dead rows zero: {zeros}")
        check(same, f"{name}: a live row differs from the slot-grid kernel")
        check(zeros, f"{name}: a dead slot's rows are not zero")


def _periodic_prompt(rng, vocab, n):
    """A motif tiled to ``n`` tokens: the n-gram draft proposer always finds
    an earlier occurrence, so speculative verify runs from the first tick."""
    motif = rng.randint(0, vocab, (int(rng.randint(4, 8)),))
    return [int(t) for t in np.tile(motif, n // len(motif) + 1)[:n]]


def bucketed_prompts(eng, prompt_lens):
    """The prefill buckets these prompts compile: prompts longer than the
    chunk stream through serve_prefill_chunk instead
    (scheduler._admit_one); the rest take one bucketed prefill each."""
    from paddle_tpu.serving.kv_cache import pick_bucket

    return {pick_bucket(n, eng.prefill_buckets) for n in prompt_lens
            if not (eng.prefill_chunk and n > eng.prefill_chunk
                    and eng.chunked_prefill_fits(n))}


def leg_serve(size, label, engine_kw, prompt_lens, new_tokens):
    import jax

    from paddle_tpu.profiler import telemetry
    from paddle_tpu.serving import Request, Scheduler

    telemetry.reset()
    tm = telemetry.get_telemetry()
    eng = build_engine(size, **engine_kw)
    if jax.default_backend() == "tpu":
        check(not eng.freeze_weights,
              "on the chip the weights must ride as donated state")
    rng = np.random.RandomState(2)
    prompts = [_periodic_prompt(rng, size.vocab, n) for n in prompt_lens]

    def serve_all():
        sched = Scheduler(eng)
        reqs = [sched.submit(Request(prompt=p, max_new_tokens=n))
                for p, n in zip(prompts, new_tokens)]
        sched.run()
        return reqs

    t0 = time.perf_counter()
    with ops_traced() as seen:
        first = serve_all()
    cold_s = time.perf_counter() - t0
    compiles_after_first = dict(tm.compile_counts())
    t0 = time.perf_counter()
    again = serve_all()  # same executables: tokens must repeat exactly
    warm_s = time.perf_counter() - t0

    for r, n in zip(first + again, new_tokens * 2):
        check(r.finish_reason in ("eos", "length"),
              f"{label}: request {r.rid} ended {r.finish_reason!r}")
        check(len(r.tokens) == n and all(0 <= t < size.vocab
                                         for t in r.tokens),
              f"{label}: request {r.rid} tokens malformed: {r.tokens}")
    for a, b in zip(first, again):
        check(a.tokens == b.tokens,
              f"{label}: replay through the same executables diverged: "
              f"{a.tokens} vs {b.tokens}")
    counters = tm.counters()
    for name in ("serve.errors", "serve.oom_evictions",
                 "serve.degraded_steps", "serve.spec_fallback_ticks",
                 "serve.timeouts", "serve.shed"):
        check(not counters.get(name),
              f"{label}: {name} = {counters.get(name)}")
    check(tm.recompile_count == 0,
          f"{label}: recompile_count {tm.recompile_count}")
    counts = tm.compile_counts()
    check(counts == compiles_after_first,
          f"{label}: the replay compiled again: {compiles_after_first} -> "
          f"{counts}")
    expect = {"serve_prefill": len(bucketed_prompts(eng, prompt_lens)),
              "serve_decode": 1}
    if eng.spec_k:
        expect["serve_verify"] = 1
        check(counters.get("serve.spec_ticks", 0) > 0,
              f"{label}: no speculative tick ran")
    if eng.prefill_chunk:
        expect["serve_prefill_chunk"] = 1
        check(counters.get("serve.prefill_chunks", 0) > 0,
              f"{label}: no prefill chunk ran")
    for name, n in expect.items():
        got = counts.get(name, 0)
        # a speculative engine may never need its plain decode step
        ok = got <= n if (name == "serve_decode" and eng.spec_k) else got == n
        check(ok, f"{label}: {name} compiled {got} times, expected {n}")
    check(seen["flash_sdpa_cached"] and seen["flash_sdpa_decode"]
          and not seen["blockwise_sdpa"]
          and seen["sdpa"] and seen["fused_layer_norm"]
          and not seen["layer_norm_op"],
          f"{label}: a serving route did not run, or the blockwise scan "
          f"did: {dict(seen)}")
    routes = {k.rsplit(".", 1)[1] for k, v in counters.items()
              if k.startswith("kv.row_write_route.") and v}
    check(routes == {"column_kernel"},
          f"{label}: the decode step's rows were written by {routes}, not "
          f"by the column kernel alone")
    say(f"serve[{label}]: {len(first)} requests x2 (prompts {prompt_lens}), "
        f"all eos|length, replay identical; compiles {counts}; ops traced "
        f"flash_sdpa_cached x{seen['flash_sdpa_cached']} flash_sdpa_decode "
        f"x{seen['flash_sdpa_decode']} blockwise_sdpa "
        f"x{seen['blockwise_sdpa']} sdpa x{seen['sdpa']}; row write "
        f"{sorted(routes)}")
    say(f"serve[{label}]: seconds in calls that compiled, per step "
        f"(trace, lower, backend, first run) "
        f"{ {k: [round(x, 1) for x in v.values()] for k, v in tm.compile_seconds().items()} }")
    say(f"serve[{label}]: first pass (with compiles) {cold_s:.1f}s, replay "
        f"{warm_s:.1f}s; spec_ticks {counters.get('serve.spec_ticks', 0)} "
        f"prefill_chunks {counters.get('serve.prefill_chunks', 0)}; "
        f"peak bytes {peak_bytes(jax.devices()[0])}; ok")


def leg_delta_rule(tiny):
    """A decoder with delta-rule state, gated grouped-KV attention and gated
    experts through ``Scheduler``: which routes its decode step compiled
    to, and the state kernel against the XLA step at the served shape."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import kda
    from paddle_tpu.profiler import telemetry
    from paddle_tpu.serving import Request, Scheduler

    slots = 4 if tiny else 128
    tm = telemetry.get_telemetry()
    before = dict(tm.counters())
    eng = build_delta_rule_engine(tiny, max_batch=slots, max_len=256)
    sched = Scheduler(eng)
    rng = np.random.default_rng(0)
    vocab = eng.model.cfg.vocab_size
    reqs = [sched.submit(Request(prompt=rng.integers(0, vocab, n).tolist(),
                                 max_new_tokens=m))
            for n, m in ((9, 6), (70, 4), (140, 5))]
    sched.run()
    check(all(len(r.tokens) == r.max_new_tokens for r in reqs),
          "delta-rule engine: a request was not served in full")
    took = {k: v - before.get(k, 0) for k, v in tm.counters().items()
            if k.startswith(("kda.step_route", "kv.row_write_route",
                             "attn.decode_route")) and v != before.get(k, 0)}
    say(f"delta-rule engine: routes of the traced steps {took}")
    traces = took.get("kv.row_write_route.row_dma", 0)
    check(traces >= 1 and set(took) == {
        "kda.step_route.kernel", "kv.row_write_route.row_dma",
        "attn.decode_route.einsum_grouped"}
        and took["kda.step_route.kernel"] == 3 * traces,
        f"delta-rule engine: expected the state kernel in 3 layers, the row "
        f"DMA and the grouped einsum, got {took}")
    del eng, sched
    gc.collect()
    H = 8 if tiny else 64
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q, k, v = (jax.random.normal(keys[i], (slots, H, 128)) for i in range(3))
    args = (unit(q) * 128 ** -0.5, unit(k), v,
            -jax.nn.softplus(jax.random.normal(keys[3], (slots, H, 128))),
            2 * jax.nn.sigmoid(jax.random.normal(keys[4], (slots, H))))
    S = jax.random.normal(keys[5], (slots, H, 128, 128))
    want = jax.jit(kda._step_xla)(*args, S)
    got = jax.jit(kda.kda_step, donate_argnums=5)(*args, S + 0.0)
    for name, a, b in zip(("read-out", "state"), got, want):
        err = _rel_err(a, b)
        say(f"kda_step b{slots} h{H} 128x128 {name}: rel err {err:.2e}")
        check(err < 1e-4, f"kda_step {name} off the XLA step by {err:.2e}")


def leg_ring(tiny):
    """A decoder with rotary positions whose window layers keep a ring,
    through ``Scheduler``: a prompt past the window in a bucket that runs
    the banded kernel, decoding that wraps the ring; which routes the
    traced steps took; and the banded kernel against the scan at a served
    shape."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional import attention as A
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_cached
    from paddle_tpu.profiler import telemetry
    from paddle_tpu.serving import Request, Scheduler

    slots = 4 if tiny else 48
    tm = telemetry.get_telemetry()
    before = dict(tm.counters())
    eng = build_ring_engine(tiny, max_batch=slots, max_len=2048)
    rings = [tuple(k.shape) for k in eng.cache.ks
             if k is not None and k.shape[1] == 512]
    check(len(rings) == 9 and eng.ring_windows == [512],
          f"ring engine: expected 9 rings of 512 rows, got {rings}")
    sched = Scheduler(eng)
    rng = np.random.default_rng(0)
    vocab = eng.model.cfg.vocab_size
    reqs = [sched.submit(Request(prompt=rng.integers(0, vocab, n).tolist(),
                                 max_new_tokens=m))
            for n, m in ((9, 6), (500, 20), (1100, 5))]
    sched.run()
    check(all(len(r.tokens) == r.max_new_tokens for r in reqs),
          "ring engine: a request was not served in full")
    took = {k: v - before.get(k, 0) for k, v in tm.counters().items()
            if k.startswith(("attn.cache_route", "kv.row_write_route",
                             "attn.decode_route", "attn.prefill_band"))
            and v != before.get(k, 0)}
    say(f"ring engine: routes of the traced steps {took}")
    traces = took.get("attn.cache_route.full", 0) // 4
    check(traces >= 1 and took.get("attn.cache_route.ring") == 9 * traces
          and took.get("kv.row_write_route.row_dma") == 13 * traces
          and took.get("attn.decode_route.einsum_grouped") == 13 * traces
          and took.get("attn.prefill_band.banded", 0) >= 9
          and not any(k.startswith(("kv.row_write_route.column",
                                    "kv.row_write_route.dus",
                                    "attn.decode_route.flash"))
                      for k in took),
          f"ring engine: expected 4 full and 9 ring layers a decode trace, "
          f"the row DMA and the grouped einsum in all 13, the banded prefill "
          f"in the window layers of the long bucket, got {took}")
    del eng, sched
    gc.collect()
    h, d, n = (4, 128, 1024) if tiny else (64, 128, 2048)
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = (jax.random.normal(keys[i], (1, n, h, d), jnp.bfloat16)
               for i in range(3))
    pos = jnp.arange(n, dtype=jnp.int32)[None]
    klen = jnp.asarray([n - 100], jnp.int32)
    got = jax.jit(lambda q, k, v: flash_attention_cached(
        q, k, v, pos, klen, window=512))(q, k, v)
    want = jax.jit(lambda q, k, v: A._bw_fwd_banded(
        q, k, v, pos, klen, 512, d ** -0.5, 256, 256))(q, k, v)
    err = _rel_err(got[:, :n - 100], want[:, :n - 100])
    say(f"flash_banded_fwd s{n} h{h} window 512: rel err {err:.2e}")
    check(err < FWD_TOL, f"banded kernel off the scan by {err:.2e}")


def leg_column_kernel(tiny):
    """The decode step's row write where XLA:TPU keeps ``max_len`` on the
    lanes (``ops/pallas/kv_row_write.py``) as the two ``gpt2_large`` cells'
    decode steps reach it (32 slots of 1,024 positions, 20 heads of 64),
    under the engine's mask of live slots: 7 of 32 live, each at a position
    of its own (the first, a column's last and first, the last that fits,
    past the end: clamped), one row a slot and verify's five. Live slots
    must come back bit for bit what the vmapped ``dynamic_update_slice``
    gives, dead ones as they were; with every slot live the whole buffers
    must equal it, and with none live all but one slot must be as they
    were. Then the 36 layers' writes of a decode step at 7 and at 32 of 32
    live, on the clock."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.kv_row_write import kv_row_write
    from paddle_tpu.serving.kv_cache import _row_update

    b, max_len, heads, d = (8, 256, 2, 64) if tiny else (32, 1024, 20, 64)
    shape = (b, max_len, heads, d)
    live7 = np.zeros((b,), bool)
    live7[[0, 3, 5, 9, 14, 20, 31] if b == 32 else [0, 3, 5]] = True

    def rnd(key, shape):
        return jax.random.normal(jax.random.PRNGKey(key), shape,
                                 jnp.float32).astype(jnp.bfloat16)

    # donated, as the decode step hands its cache over: the kernel's outputs
    # are pinned to HBM and alias these very buffers
    write = jax.jit(lambda kv, new, starts, live: kv_row_write(
        tuple(kv), tuple(new), starts, live), donate_argnums=0)
    want_of = jax.jit(lambda kv, new, starts: [
        _row_update(x, n, starts) for x, n in zip(kv, new)])
    for rows in (1, 5):
        starts = jnp.asarray(np.random.RandomState(rows).randint(
            0, max_len, b), jnp.int32).at[:5].set(jnp.asarray(
                [0, 127, 128, max_len - rows, max_len + 7]))
        few = f"{live7.sum()} of {b} live"
        for label, live in ((few, live7),
                            ("all live", np.ones((b,), bool)),
                            ("none live", np.zeros((b,), bool))):
            new = [rnd(10 + i, (b, rows, heads, d)) for i in range(2)]
            before = [rnd(i, shape) for i in range(2)]
            want = want_of(before, new, starts)
            got = write([jnp.copy(x) for x in before], new, starts,
                        jnp.asarray(live))
            same = jax.jit(lambda xs, ys: jnp.stack([
                jnp.all(x == y, axis=(1, 2, 3))
                for x, y in zip(xs, ys)]).all(0))
            written = np.asarray(same(got, want))  # slot by slot
            left = np.asarray(same(got, before))
            live_ok = bool(written[live].all())
            dead_ok = (bool(left[~live].all()) if live.any()
                       else int((~left).sum()) <= 1
                       and bool(written[~left].all()))
            say(f"kernels: kv_row_write rows{rows} {list(shape)} bf16, "
                f"{label}: live slots equal to the dynamic_update_slice: "
                f"{live_ok}; dead slots as they were: {dead_ok} "
                f"({int((~left[~live]).sum())} of {int((~live).sum())} "
                f"rewritten)")
            check(live_ok, f"kv_row_write rows{rows} {label}: a live slot "
                           f"differs from _row_update")
            check(dead_ok, f"kv_row_write rows{rows} {label}: a dead slot "
                           f"was written")
            del new, before, want, got
            gc.collect()
    # the decode step's 36 layers of K and V alone, each slot at a position
    # of its own: 20 steps inside one program, so that the device's time is
    # what the clock reads
    layers, steps = (2, 2) if tiny else (36, 20)
    pos = jnp.asarray(np.random.RandomState(0).randint(0, max_len - steps, b),
                      jnp.int32)
    new = rnd(20, (b, 1, heads, d))
    us = {}
    few, every = f"{live7.sum()} of {b}", f"{b} of {b}"
    for label, live in ((few, live7), (every, np.ones((b,), bool))):
        live = jnp.asarray(live)

        def run(ks, vs, live=live):
            def one(i, kv):
                out = [kv_row_write((k, v), (new, new), pos + i, live)
                       for k, v in zip(*kv)]
                return [k for k, _ in out], [v for _, v in out]
            return jax.lax.fori_loop(0, steps, one, (ks, vs))

        run = jax.jit(run, donate_argnums=(0, 1))
        kv = run([jnp.zeros(shape, jnp.bfloat16) for _ in range(layers)],
                 [jnp.zeros(shape, jnp.bfloat16) for _ in range(layers)])
        jax.block_until_ready(kv)
        t0 = time.perf_counter()
        for _ in range(1 if tiny else 5):
            kv = run(*kv)
        jax.block_until_ready(kv)
        us[label] = ((time.perf_counter() - t0) * 1e6
                     / ((1 if tiny else 5) * steps * layers))
        del kv, run
        gc.collect()
    say(f"kernels: the decode step's {layers} column writes (b{b} x "
        f"{max_len}, {heads} x {d}, {steps} steps a program): "
        f"{us[few]:.1f} us a layer at {few} live, {us[every]:.1f} us at "
        f"{every}")


def leg_row_dma(tiny):
    """The decode step's row write where a row is contiguous
    (``ops/pallas/kv_row_dma.py``) as the three expert cells' decode steps
    reach it: the kernel and the vmapped ``dynamic_update_slice`` must
    agree in every element of K and V at the Laguna cut's full-length rows
    and rings and at the Solar Open 2 cut's cache, one row a slot and
    verify's five, each slot at a position of its own (the first, the last
    that fits, past the end: clamped). Then the Laguna cut's 26 writes of a
    decode step, both ways, on the clock."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.kv_row_dma import kv_row_dma
    from paddle_tpu.serving.kv_cache import _row_update

    shapes = ([(6, 256, 8, 128), (6, 32, 8, 128), (8, 128, 8, 128)] if tiny
              else [(48, 9216, 8, 128), (48, 512, 8, 128),
                    (128, 5120, 8, 128)])
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 64))

    def rnd(shape):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(
            jnp.bfloat16)

    write = jax.jit(lambda kv, new, starts: kv_row_dma(
        tuple(kv), tuple(new), starts), donate_argnums=0)
    want_of = jax.jit(lambda kv, new, starts: [
        _row_update(x, n, starts) for x, n in zip(kv, new)])
    for shape in shapes:
        b, max_len = shape[:2]
        for rows in (1, 5):
            starts = jnp.asarray(np.random.RandomState(rows).randint(
                0, max_len, b), jnp.int32).at[:3].set(jnp.asarray(
                    [0, max_len - rows, max_len + 7]))
            kv = [rnd(shape) for _ in range(2)]
            new = [rnd((b, rows) + shape[2:]) for _ in range(2)]
            want = want_of(kv, new, starts)
            # donated, as the decode step hands its cache over: the kernel
            # writes into these very buffers
            got = write(kv, new, starts)
            same = all(bool(jnp.array_equal(g, w)) for g, w in zip(got, want))
            say(f"kernels: kv_row_dma rows{rows} {list(shape)} bf16: equal "
                f"to the dynamic_update_slice: {same}")
            check(same, f"kv_row_dma rows{rows} {shape}: differs from "
                        f"_row_update")
            del kv, new, want, got
            gc.collect()
    # the Laguna cut's decode-step writes alone, 4 full-length layers and 9
    # rings, K and V, each slot at a position of its own: 20 steps inside
    # one program, so that the device's time is what the clock reads
    full, ring = shapes[0], shapes[1]
    layers = [full if i % 4 == 0 else ring for i in range(13)]
    b, steps = full[0], 20
    pos = jnp.asarray(np.random.RandomState(0).randint(0, ring[1], b),
                      jnp.int32)
    new = rnd((b, 1) + full[2:])
    writes = {
        "dus": lambda k, v, p: (_row_update(k, new, p),
                                _row_update(v, new, p)),
        "row_dma": lambda k, v, p: kv_row_dma((k, v), (new, new), p)}
    ms = {}
    for name, write in writes.items():
        def run(ks, vs, write=write):
            def one(i, kv):
                out = [write(k, v, (pos + i) % ring[1]) for k, v in zip(*kv)]
                return [k for k, _ in out], [v for _, v in out]
            return jax.lax.fori_loop(0, steps, one, (ks, vs))

        run = jax.jit(run, donate_argnums=(0, 1))
        kv = run([jnp.zeros(s, jnp.bfloat16) for s in layers],
                 [jnp.zeros(s, jnp.bfloat16) for s in layers])
        jax.block_until_ready(kv)
        t0 = time.perf_counter()
        for _ in range(1 if tiny else 5):
            kv = run(*kv)
        jax.block_until_ready(kv)
        ms[name] = ((time.perf_counter() - t0) * 1e3
                    / ((1 if tiny else 5) * steps))
        del kv, run
        gc.collect()
    say(f"kernels: the Laguna cut's 26 row writes a decode step "
        f"(b{b}, 4 x {full[1]} + 9 x {ring[1]} rows, {steps} steps a "
        f"program): dus {ms['dus']:.3f} ms, row_dma {ms['row_dma']:.3f} ms")


def leg_dp4(size, one_chip_losses):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.io import DeviceLoader
    from paddle_tpu.profiler import telemetry

    n = len(jax.devices())
    if n < 4:
        say(f"dp4: not run: {n} device(s)")
        return
    mesh = build_mesh({"dp": 4})
    rows = NamedSharding(mesh, P("dp"))
    for quantize, rtol in ((None, DP_FP32_RTOL), ("int8", DP_INT8_RTOL)):
        wire = quantize or "fp32"
        telemetry.reset()
        model, opt, step = build_trainer(size, mesh=mesh, quantize=quantize)
        batches = train_batches(size)
        staged = jax.device_put(batches[0][0], rows)
        batch_shards = sorted((s.device.id, s.data.shape)
                              for s in staged.addressable_shards)
        check(len(batch_shards) == 4 and all(
            shape == (size.batch // 4, size.seq) for _, shape in batch_shards),
            f"dp4[{wire}]: batch shards {batch_shards}")
        losses, first_s, later_s = run_steps(step, DeviceLoader(
            batches, place_fn=lambda a: jax.device_put(a, rows)))
        # the per-device program (lowered after the run, so the step's own
        # trace-and-compile count above stays the real one)
        mosaic = mosaic_operand_shapes(step.lower(
            *[jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rows)
              for a in batches[0]]).compile().as_text())
        local = {shape[5:].split(",")[0] for shapes in mosaic
                 for shape in shapes if shape.startswith("bf16[")}
        total = per_dev = 0
        for store in opt._accumulators.values():
            for v in store.values():
                if hasattr(v, "addressable_shards"):
                    total += v.nbytes
                    per_dev += v.addressable_shards[0].data.nbytes
        rel = max(abs(a - b) / abs(b)
                  for a, b in zip(losses, one_chip_losses))
        say(f"dp4[{wire}]: batch shards {batch_shards}; optimizer state "
            f"{per_dev} of {total} bytes on device 0 "
            f"({per_dev / total:.3f}); {len(mosaic)} Mosaic calls, leading "
            f"dims of their bf16 operands {sorted(local)}")
        say(f"dp4[{wire}]: losses {[round(v, 4) for v in losses]} vs one "
            f"chip, max rel diff {rel:.2e} (tolerance {rtol}); first call "
            f"{first_s:.1f}s, later {later_s:.3f}s; peak bytes per device "
            f"{[peak_bytes(d) for d in jax.devices()[:4]]}")
        check(per_dev / total < 0.30,
              f"dp4[{wire}]: optimizer state not sharded: {per_dev}/{total}")
        if jax.default_backend() == "tpu":
            rows_local = size.batch // 4
            check(mosaic and local <= {str(rows_local),
                                       str(rows_local * size.seq)},
                  f"dp4[{wire}]: Mosaic calls do not see the local batch: "
                  f"{sorted(local)}")
        check(all(math.isfinite(v) for v in losses) and rel <= rtol,
              f"dp4[{wire}]: loss parity {rel:.3e} over {rtol}: {losses} vs "
              f"{one_chip_losses}")
        check(telemetry.get_telemetry().compile_counts().get(
            "train_step") == 1, f"dp4[{wire}]: train_step recompiled")
        del model, opt, step
        gc.collect()
    say("dp4: ok")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal of the script (Pallas "
                         "interpreted); proves nothing about the chip")
    ap.add_argument("--legs", default=",".join(LEGS),
                    help=f"the legs to run, of {','.join(LEGS)} (dp4 "
                         f"needs train)")
    args = ap.parse_args(argv)
    legs = args.legs.split(",")
    if set(legs) - set(LEGS):
        sys.exit(f"chip_smoke: no leg {sorted(set(legs) - set(LEGS))}")

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if args.rehearse:
        if dev.platform != "cpu":
            sys.exit("chip_smoke: --rehearse is the CPU rehearsal; run it "
                     "with JAX_PLATFORMS=cpu")
    elif dev.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU: jax found {device}. Run it on the "
                 f"chip; `--rehearse` is the tiny CPU rehearsal.")

    from paddle_tpu.framework.compile_cache import enable_compile_cache
    from paddle_tpu.ops import pallas
    from paddle_tpu.profiler import devprof, telemetry

    import importlib.metadata

    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    stats = CacheStats()
    cache_dir = enable_compile_cache()
    telemetry.enable()
    devprof.enable_auto_harvest(False)  # no second lowering per step
    say(f"chip_smoke: jax {jax.__version__} jaxlib {jaxlib.__version__} "
        f"libtpu {libtpu} platform {dev.platform} device_kind {dev.device_kind!r} "
        f"count {device['count']}"
        + (" [CPU REHEARSAL]" if args.rehearse else ""))
    size = TINY if args.rehearse else FULL
    t_start = time.perf_counter()
    with (pallas.interpret_mode() if args.rehearse
          else contextlib.nullcontext()):
        check(args.rehearse or not pallas.interpret_requested(),
              "Pallas interpret mode on the chip path")
        losses = leg_train(size) if "train" in legs else None
        gc.collect()
        if "kernels" in legs:
            leg_kernels(size)
            gc.collect()
        for leg in SERVE_LEGS if "serve" in legs else ():
            leg_serve(size, *leg)
            gc.collect()
        for name, leg in (("delta_rule", leg_delta_rule), ("ring", leg_ring),
                          ("column_kernel", leg_column_kernel),
                          ("row_dma", leg_row_dma)):
            if name in legs:
                leg(args.rehearse)
                gc.collect()
        if "dp4" in legs and losses is not None:
            leg_dp4(size, losses)
    say(f"memory_stats of device 0: {dev.memory_stats()}")
    say(stats.line(cache_dir))
    say(f"chip_smoke: legs {','.join(legs)} passed in "
        f"{time.perf_counter() - t_start:.0f}s")
    result = {"ok": True, "device": device, "legs": legs}
    if args.rehearse:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
