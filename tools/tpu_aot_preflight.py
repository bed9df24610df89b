"""AOT pre-flight: compile the TPU programs here, where there is no TPU.

libtpu ships in this environment, so
``jax.experimental.topologies.get_topology_desc(platform="tpu",
topology_name="v5e:2x2")`` hands back four compile-only ``TPU v5 lite``
devices and ``jax.jit(f).lower(<ShapeDtypeStruct placed on them>).compile()``
runs the real XLA:TPU + Mosaic compilers under ``JAX_PLATFORMS=cpu``. What
it proves: the program lowers, every Pallas kernel passes Mosaic, and the
buffers fit. What it cannot: donation, readback, run-time HBM, numerics —
those are ``chip_smoke.py``'s, on the chip. It costs no chip budget.

    JAX_PLATFORMS=cpu python tools/tpu_aot_preflight.py           # kernels
    JAX_PLATFORMS=cpu python tools/tpu_aot_preflight.py --steps   # + the
        full-width GPT-2 124M train step (one chip; four chips dp/ZeRO)
        and the serving steps chip_smoke.py runs

One line per program: ``ok``/``FAIL``, compile seconds, and for the sharded
programs the operand shapes the per-device Mosaic calls see. Exit code 1 if
anything failed, 2 if the topology is unavailable.
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

# the repo root: paddle_tpu and chip_smoke.py import from there
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
from chip_smoke import mosaic_operand_shapes

TOPOLOGY = "v5e:2x2"


def topology_devices():
    from jax.experimental import topologies

    return topologies.get_topology_desc(
        platform="tpu", topology_name=TOPOLOGY).devices


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def cache_buffer_copies(text, batch):
    """XLA ops (not the kernels, not bitcasts) of a compiled program whose
    result is a whole bf16 K or V buffer of ``batch`` slots x 1024: a
    re-layout, or the staging through VMEM that XLA's memory-space
    assignment puts around an unpinned kernel."""
    return len(re.findall(
        rf"= \(*bf16\[{batch},(?:\d+,)*1024[,\d]*\]\S*,? [^=]*?"
        r"(?:copy|transpose|fusion|copy-start|slice-start)\(", text))


def pair_lists_built(text):
    """How often a compiled program builds the decode attention's list of
    live (slot, block) pairs (``flash_decode.live_pairs``: its cumulative
    sum is the one ``reduce-window`` the kernel's wrapper holds). The list
    depends on the step's positions alone, so once, however many layers."""
    return len(re.findall(
        r"reduce-window\([^\n]*op_name=\"[^\"]*jit\(_flash_decode\)", text))


def slot_lists_built(text):
    """How often a compiled program builds the row write's list of live
    slots (``kv_row_write``'s ``live_pairs`` over the engine's mask): once,
    however many layers."""
    return len(re.findall(
        r"reduce-window\([^\n]*op_name=\"[^\"]*jit\(_kv_row_write\)", text))


def decode_program_facts(text):
    """The things a compiled decode-shaped program at the serving cells'
    batch is held to, as one line of the report."""
    return (f"copies of a cache buffer: {cache_buffer_copies(text, 32)}; "
            f"lists of live (slot, block) pairs built: "
            f"{pair_lists_built(text)}; lists of live slots built: "
            f"{slot_lists_built(text)}")


def entry_ops(text):
    """The ENTRY computation of a compiled program as ``{name: (opcode,
    operand names, line)}``."""
    ops = {}
    for line in text[text.index("\nENTRY"):].splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?(%\S+) = .*? ([a-z][a-z\-]*)\((.*?)\)"
                     r"(?:, |$)", line)
        if m:
            ops[m.group(1)] = (m.group(2), re.findall(r"%[\w.\-]+",
                                                      m.group(3)), line)
    return ops


def row_dma_facts(text):
    """What a compiled decode-shaped program that writes its rows with
    ``kv_row_dma`` is held to, as one line: no ``while`` and no
    ``dynamic-update-slice`` left; every cache operand of every call is a
    donated parameter seen through bitcasts alone, and every output of every
    call reaches the program's outputs through bitcasts alone (no copy, no
    staging through VMEM, in or out); and how often XLA prefetches a written
    buffer for the attention that reads it next (a read, not a copy of the
    cache)."""
    ops = entry_ops(text)
    users = collections.defaultdict(list)
    for name, (_, operands, _) in ops.items():
        for o in operands:
            users[o].append(name)

    def source(name):
        while ops[name][0] == "bitcast":
            name = ops[name][1][0]
        return name

    def reaches_root(name):
        seen = [name]
        while seen:
            n = seen.pop()
            if "ROOT " in ops[n][2]:
                return True
            seen += [u for u in users[n]
                     if ops[u][0] in ("bitcast", "tuple")]
        return False

    calls = [n for n, (op, _, _) in ops.items()
             if op == "custom-call" and "kv_row_dma" in n]
    params = outs = buffers = 0
    for call in calls:
        cache_operands = ops[call][1][len(ops[call][1]) // 2 + 1:]
        buffers += len(cache_operands)
        params += sum(ops[source(o)][0] == "parameter"
                      for o in cache_operands)
        outs += sum(reaches_root(g) for g in users[call]
                    if ops[g][0] == "get-tuple-element")
    prefetches = sum(
        1 for n, (op, operands, _) in ops.items()
        if op in ("copy-start", "slice-start")
        and ops[source(operands[0])][0] == "get-tuple-element"
        and any(c in ops[source(operands[0])][1] for c in calls))
    return (f"row DMA calls {len(calls)}: cache operands that are donated "
            f"parameters {params} of {buffers}, outputs that are the "
            f"program's {outs} of {buffers}; while loops "
            f"{len(re.findall(r' while[(]', text))}, dynamic-update-slices "
            f"{len(re.findall(r'dynamic-update-slice[(]', text))}; "
            f"prefetches of a written buffer for its attention {prefetches}")


class Report:
    def __init__(self):
        self.failed = []

    def run(self, name, build):
        """``build()`` returns a ``jax.stages.Lowered``; compile and report."""
        t0 = time.perf_counter()
        try:
            compiled = build().compile()
        except Exception as e:  # noqa: BLE001 - the tool's job is to list them
            self.failed.append(name)
            msg = str(e).strip().splitlines()
            print(f"FAIL {name}: {type(e).__name__}: {msg[0] if msg else ''}",
                  flush=True)
            return None
        dt = time.perf_counter() - t0
        n = len(mosaic_operand_shapes(compiled.as_text()))
        print(f"ok   {name}: {dt:.1f}s compile, {n} Mosaic call(s)",
              flush=True)
        return compiled


# ---------------------------------------------------------------------------
# kernels, one GPT-2 shape each (h12 d64 s1024, bf16)
# ---------------------------------------------------------------------------
def kernel_programs(devs):
    from paddle_tpu.ops.pallas import (flash_attention, flash_attention_cached,
                                       fused_layer_norm)
    from paddle_tpu.ops.pallas.flash_attention_packed import (
        flash_attention_packed)
    from paddle_tpu.ops.partition import partition_scope

    one = SingleDeviceSharding(devs[0])
    b, s, h, d = 2, 1024, 12, 64
    bf = jnp.bfloat16

    def grad_of(fn, n):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
            argnums=tuple(range(n))))

    x3 = _sds((b, s, h * d), bf, one)
    x4 = _sds((b, s, h, d), bf, one)
    gam = _sds((h * d,), jnp.float32, one)
    yield "layer_norm fwd+bwd", lambda: grad_of(
        lambda x, g, be: fused_layer_norm(x, g, be), 3).lower(x3, gam, gam)
    yield "flash packed causal fwd+bwd", lambda: grad_of(
        lambda q, k, v: flash_attention_packed(q, k, v, h, causal=True),
        3).lower(x3, x3, x3)
    yield "flash packed dropout fwd+bwd", lambda: grad_of(
        lambda q, k, v, seed: flash_attention_packed(
            q, k, v, h, causal=True, dropout_p=0.1, dropout_seed=seed),
        3).lower(x3, x3, x3, _sds((2,), jnp.int32, one))
    yield "flash layout-swapping bias fwd+bwd", lambda: grad_of(
        lambda q, k, v, bias: flash_attention(q, k, v, bias, causal=True,
                                              bias_grad=False),
        3).lower(x4, x4, x4, _sds((b, 1, s, s), jnp.float32, one))
    # serving reaches the cached kernel at batch 1 (a 1024 bucket, a 128
    # chunk); batch 8 is what F.sdpa with a LengthMask gives any other caller
    for cb, sq in ((1, 1024), (8, 128)):
        q = _sds((cb, sq, h, d), bf, one)
        kv = _sds((cb, s, h, d), bf, one)
        yield (f"flash cached b{cb} sq{sq} sk{s}",
               lambda q=q, kv=kv, cb=cb, sq=sq: jax.jit(
                   flash_attention_cached).lower(
                       q, kv, kv, _sds((cb, sq), jnp.int32, one),
                       _sds((cb,), jnp.int32, one)))

    # serving reaches the decode kernel at the engine's batch: one query row
    # a slot (decode) or spec_k + 1 (verify) over each slot's whole cache row
    from paddle_tpu.ops.pallas.flash_decode import flash_attention_decode

    for sq in (1, 5):
        q = _sds((8, sq, h, d), bf, one)
        kv = _sds((8, s, h, d), bf, one)
        yield (f"flash decode b8 sq{sq} sk{s}",
               lambda q=q, kv=kv, sq=sq: jax.jit(flash_attention_decode).lower(
                   q, kv, kv, _sds((8, sq), jnp.int32, one)))

    # the decode step's row write at GPT-2 large's heads (20 x 64): the
    # kernel alone, one row a slot (decode) and spec_k + 1 (verify), and a
    # decode-shaped program around it with the cache DONATED: write, then
    # attend over what was written, both through the view the TPU keeps
    from paddle_tpu.ops.pallas.kv_row_write import kv_row_write

    wh = 20
    kvw = _sds((8, s, wh, d), bf, one)
    for rows in (1, 5):
        new = _sds((8, rows, wh, d), bf, one)
        yield (f"kv row write b8 s{rows} sk{s}",
               lambda new=new: jax.jit(
                   lambda k, v, kn, vn, pos: kv_row_write(
                       (k, v), (kn, vn), pos), donate_argnums=(0, 1)).lower(
                           kvw, kvw, new, new, _sds((8,), jnp.int32, one)))

    # ... at the serving cells' batch (32 slots: 84 MB a buffer) and over
    # eight layers, because what has to be ruled out needs both: XLA's
    # memory-space assignment moving whole cache buffers into the v5e's
    # 128 MiB of VMEM ahead of the kernel and copying its aliased result
    # out again (25 of a 36-layer decode step's 72 buffers, 5 ms a step on
    # the chip, before the kernel pinned its outputs to HBM)
    def write_then_attend(ks, vs, x, w, pos, live):
        out_k, out_v = [], []
        for k, v in zip(ks, vs):
            new = (x @ w).reshape(32, 1, wh, d)
            k, v = kv_row_write((k, v), (new, new), pos, live)
            x = flash_attention_decode(
                new, k, v, jnp.where(live, pos, -1)[:, None]).reshape(
                    32, wh * d)
            out_k.append(k)
            out_v.append(v)
        return x, out_k, out_v

    big = [_sds((32, s, wh, d), bf, one)] * 8
    yield "kv row write donated decode b32 x8", lambda: jax.jit(
        write_then_attend, donate_argnums=(0, 1)).lower(
            big, big, _sds((32, wh * d), bf, one),
            _sds((wh * d, wh * d), bf, one), _sds((32,), jnp.int32, one),
            _sds((32,), jnp.bool_, one))

    # the decode step's row write where a row is contiguous (heads of 128)
    # as the expert cells reach it: the kernel alone at the Laguna cut's
    # full-length rows and rings and at the Solar Open 2 cut's cache, one
    # row a slot and verify's five; then the Laguna cut's 13 layers at b48,
    # donated, each layer writing its rows then attending over them as the
    # grouped einsum does: what XLA stages through VMEM shows at full depth
    from paddle_tpu.ops.pallas.kv_row_dma import kv_row_dma
    from paddle_tpu.serving.kv_cache import DecodeView

    for cb, sk in ((48, 9216), (48, 512), (128, 5120)):
        buf = _sds((cb, sk, 8, 128), bf, one)
        for rows in (1, 5):
            new = _sds((cb, rows, 8, 128), bf, one)
            yield (f"kv row dma b{cb} s{rows} sk{sk}",
                   lambda buf=buf, new=new, cb=cb: jax.jit(
                       lambda k, v, kn, vn, pos: kv_row_dma(
                           (k, v), (kn, vn), pos),
                       donate_argnums=(0, 1)).lower(
                           buf, buf, new, new, _sds((cb,), jnp.int32, one)))

    def laguna_writes(ks, vs, x, w, pos):
        out_k, out_v = [], []
        for k, v in zip(ks, vs):
            rows = k.shape[1]
            new = (x @ w).reshape(48, 1, 8, 128)
            k, v, _ = DecodeView(k, v, pos % rows).update(new, new)
            k, v = k._value, v._value
            p = jax.nn.softmax(jnp.einsum(
                "bqhd,bkhd->bhqk", new, k).astype(jnp.float32), axis=-1)
            x = jnp.einsum("bhqk,bkhd->bqhd", p.astype(bf), v).reshape(
                48, 1024)
            out_k.append(k)
            out_v.append(v)
        return x, out_k, out_v

    laguna = [_sds((48, 9216 if i % 4 == 0 else 512, 8, 128), bf, one)
              for i in range(13)]
    yield "kv row dma donated decode b48 x13", lambda: jax.jit(
        laguna_writes, donate_argnums=(0, 1)).lower(
            laguna, laguna, _sds((48, 1024), bf, one),
            _sds((1024, 1024), bf, one), _sds((48,), jnp.int32, one))

    # the expert and state-space kernels at the widths the benchmark's
    # hybrid configuration serves: hidden 2688, expert width 1856 (no
    # multiple of 128: the stacks are [experts, 1856, 2688] both ways), 64
    # experts held, 64 slots of state [64, 64, 128]
    from paddle_tpu.ops.pallas import moe_grouped, ssm_step

    E, hid, wid = 64, 2688, 1856
    for tokens, tm in ((64, 16), (1024, 64)):
        tiles = -(-tokens * 6 // tm) + E
        ints = (_sds((tiles,), jnp.int32, one), _sds((1,), jnp.int32, one))
        stack = _sds((E, wid, hid), bf, one)
        yield (f"moe grouped up+down t{tokens} tm{tm}",
               lambda tiles=tiles, tm=tm, ints=ints, stack=stack: jax.jit(
                   lambda x, up, down, te, na: moe_grouped._grouped_call(
                       moe_grouped._grouped_call(x, up, te, na, tm, "relu2",
                                                 True, False),
                       down, te, na, tm, None, False, False)).lower(
                           _sds((tiles * tm, hid), bf, one), stack, stack,
                           *ints))
    f32 = jnp.float32
    slots, heads, hdim, groups, state = 64, 64, 64, 8, 128
    yield "ssm step b64 h64 p64 n128", lambda: jax.jit(
        lambda da, x, B, C, S: ssm_step._step_call(da, x, B, C, S,
                                                   False)).lower(
            _sds((slots, heads), f32, one),
            _sds((slots, hdim, heads), f32, one),
            _sds((slots, groups, state), f32, one),
            _sds((slots, groups, state), f32, one),
            _sds((slots, heads, hdim, state), f32, one))
    yield "ssm scan carry 8 blocks", lambda: jax.jit(
        lambda d, st, s0: ssm_step._carry_call(d, st, s0, False)).lower(
            _sds((1, 8, heads), f32, one),
            _sds((1, 8, heads, hdim, state), f32, one),
            _sds((1, heads, hdim, state), f32, one))

    # the delta-rule state kernel and the gated expert product at the
    # widths the benchmark's Solar Open 2 cut serves: hidden 4096, gated
    # experts of width 1280 (40 held, 8 a token), 128 slots of state [64,
    # 128, 128]; the state donated, as every serving step donates it
    from paddle_tpu.ops.pallas import kda_step

    E, hid, wid = 40, 4096, 1280
    for tokens, tm in ((128, 16), (4096, 128)):
        tiles = -(-tokens * 8 // tm) + E
        yield (f"gated experts up+down t{tokens} tm{tm}",
               lambda tiles=tiles, tm=tm: jax.jit(
                   lambda x, up, down, te, na: moe_grouped._grouped_call(
                       moe_grouped._gated_call(x, up, te, na, tm, False),
                       down, te, na, tm, None, False, False)).lower(
                           _sds((tiles * tm, hid), bf, one),
                           _sds((E, 2 * wid, hid), bf, one),
                           _sds((E, wid, hid), bf, one),
                           _sds((tiles,), jnp.int32, one),
                           _sds((1,), jnp.int32, one)))
    # the same two products at the widths the Laguna cut serves: hidden
    # 2048, gated experts of width 512 (32 held, 8 a token), a decode batch
    # of 48 slots and the 8,192 bucket
    E, hid, wid = 32, 2048, 512
    for tokens, tm in ((48, 16), (8192, 128)):
        tiles = -(-tokens * 8 // tm) + E
        yield (f"gated experts up+down t{tokens} tm{tm} h{hid} w{wid}",
               lambda tiles=tiles, tm=tm, E=E, hid=hid, wid=wid: jax.jit(
                   lambda x, up, down, te, na: moe_grouped._grouped_call(
                       moe_grouped._gated_call(x, up, te, na, tm, False),
                       down, te, na, tm, None, False, False)).lower(
                           _sds((tiles * tm, hid), bf, one),
                           _sds((E, 2 * wid, hid), bf, one),
                           _sds((E, wid, hid), bf, one),
                           _sds((tiles,), jnp.int32, one),
                           _sds((1,), jnp.int32, one)))
    # the banded cached kernel as the Laguna cut's window layers reach it:
    # one prompt in the 8,192 bucket, 64 query heads of 128, a window of 512
    q = _sds((1, 8192, 64, 128), bf, one)
    yield "flash banded b1 sq8192 h64 window 512", lambda: jax.jit(
        lambda q, k, v, qp, kl: flash_attention_cached(
            q, k, v, qp, kl, window=512)).lower(
                q, q, q, _sds((1, 8192), jnp.int32, one),
                _sds((1,), jnp.int32, one))
    slots, heads, hdim = 128, 64, 128
    nb = heads // kda_step.head_block(heads)
    yield "kda step b128 h64 128x128 donated", lambda: jax.jit(
        lambda c, v, S: kda_step._step_call(c, v, S, False),
        donate_argnums=2).lower(
            _sds((slots, nb, hdim, 4 * heads // nb), f32, one),
            _sds((slots, nb, heads // nb, hdim), f32, one),
            _sds((slots, heads, hdim, hdim), f32, one))

    # LN + flash inside one jit sharded over the 4-device mesh
    mesh = Mesh(np.array(devs), ("dp",))
    row = NamedSharding(mesh, P("dp"))
    rep = NamedSharding(mesh, P())
    gb = 8

    def block(x, g, be):
        with partition_scope((mesh, ("dp",))):
            y = fused_layer_norm(x, g, be)
            return flash_attention_packed(y, y, y, h, causal=True)

    yield "dp4: layer_norm + flash fwd+bwd", lambda: grad_of(block, 3).lower(
        _sds((gb, s, h * d), bf, row), _sds((h * d,), jnp.float32, rep),
        _sds((h * d,), jnp.float32, rep))
    qs = _sds((gb, 128, h, d), bf, row)
    kvs = _sds((gb, s, h, d), bf, row)

    def cached(q, k, v, qp, kl):
        with partition_scope((mesh, ("dp",))):
            return flash_attention_cached(q, k, v, qp, kl)

    yield "dp4: flash cached b8", lambda: jax.jit(cached).lower(
        qs, kvs, kvs, _sds((gb, 128), jnp.int32, row),
        _sds((gb,), jnp.int32, row))

    def decode(q, k, v, qp):
        with partition_scope((mesh, ("dp",))):
            return flash_attention_decode(q, k, v, qp)

    yield "dp4: flash decode b8", lambda: jax.jit(decode).lower(
        _sds((gb, 1, h, d), bf, row), kvs, kvs, _sds((gb, 1), jnp.int32, row))

    def write(k, v, kn, vn, pos):
        with partition_scope((mesh, ("dp",))):
            return kv_row_write((k, v), (kn, vn), pos)

    news = _sds((gb, 1, h, d), bf, row)
    yield "dp4: kv row write b8", lambda: jax.jit(
        write, donate_argnums=(0, 1)).lower(
            kvs, kvs, news, news, _sds((gb,), jnp.int32, row))

    def write_rows(k, v, kn, vn, pos):
        with partition_scope((mesh, ("dp",))):
            return kv_row_dma((k, v), (kn, vn), pos)

    rows128 = _sds((gb, 512, 8, 128), bf, row)
    new128 = _sds((gb, 1, 8, 128), bf, row)
    yield "dp4: kv row dma b8", lambda: jax.jit(
        write_rows, donate_argnums=(0, 1)).lower(
            rows128, rows128, new128, new128, _sds((gb,), jnp.int32, row))


# ---------------------------------------------------------------------------
# the full-width steps chip_smoke.py runs
# ---------------------------------------------------------------------------
def lower_step(step, args, place_state, place_arg):
    """Lower a CompiledStep for the topology: state and arguments become
    ShapeDtypeStructs placed by ``place_state(array)``/``place_arg(array)``."""
    def abstract(place):
        return lambda a: _sds(a.shape, a.dtype, place(a))

    state = jax.tree_util.tree_map(abstract(place_state),
                                   step.spec.snapshot())
    args = jax.tree_util.tree_map(
        abstract(place_arg), jax.tree_util.tree_map(jnp.asarray, args))
    dyn_donated, dyn_kept, static = step._prepare(args, {})
    return step._jitted.lower(state, dyn_donated, dyn_kept, static)


def step_programs(devs):
    one = SingleDeviceSharding(devs[0])
    model, opt, step = chip_smoke.build_trainer(chip_smoke.FULL)
    ids = np.zeros((chip_smoke.FULL.batch, chip_smoke.FULL.seq), np.int32)
    yield "train_step b24 s1024 (1 chip)", lambda: lower_step(
        step, (ids, ids), lambda a: one, lambda a: one)

    # The topology's devices compile but hold no data, so the ZeRO trainer
    # cannot really place its state on them: while it is built, a
    # device_put onto the topology mesh leaves the array where it is and
    # only records the sharding it asked for, which is what lowering needs.
    mesh = Mesh(np.array(devs), ("dp",))
    wanted = {}
    real_put = jax.device_put

    def recording_put(x, device=None, **kw):
        if isinstance(device, NamedSharding) and device.mesh is mesh:
            wanted[id(x)] = device
            return x
        return real_put(x, device, **kw)

    for quantize in (None, "int8"):
        wanted.clear()  # ids of the previous trainer's arrays are stale
        jax.device_put = recording_put
        try:
            model, opt, step = chip_smoke.build_trainer(
                chip_smoke.FULL, mesh=mesh, quantize=quantize)
        finally:
            jax.device_put = real_put
        rep = NamedSharding(mesh, P())
        yield (f"train_step b24 s1024 (dp4 ZeRO, wire "
               f"{quantize or 'fp32'})"), lambda step=step: lower_step(
                   step, (ids, ids),
                   lambda a, wanted=dict(wanted): wanted.get(id(a), rep),
                   lambda a: NamedSharding(mesh, P("dp")))

    # freeze_weights=False is what "auto" resolves to on the chip: the
    # weights ride as donated state (on this CPU host "auto" would fold
    # them into the executables as constants — another program)
    place = (lambda a: one)
    for label, kw, prompt_lens, _ in chip_smoke.SERVE_LEGS:
        eng = chip_smoke.build_engine(chip_smoke.FULL, freeze_weights=False,
                                      **kw)
        yield f"serve_decode ({label})", lambda eng=eng: lower_step(
            eng.decode_step, eng.example_decode_args([1]), place, place)
        for bucket in sorted(chip_smoke.bucketed_prompts(eng, prompt_lens)):
            yield (f"serve_prefill bucket {bucket} ({label})",
                   lambda eng=eng, bucket=bucket: lower_step(
                       eng.prefill_step,
                       (np.zeros((1, bucket), np.int32), np.int32(1),
                        np.int32(0), eng._example_cache([0])), place, place))
        if eng.verify_step is not None:
            yield f"serve_verify ({label})", lambda eng=eng: lower_step(
                eng.verify_step, eng.example_verify_args([1]), place, place)
        if eng.chunk_step is not None:
            yield f"serve_prefill_chunk ({label})", lambda eng=eng: lower_step(
                eng.chunk_step, eng.example_chunk_args([0]), place, place)
    # the benchmark's gpt2-large as its two cells serve it: 36 layers, 32
    # slots of 1,024 positions, the decode step (row write and attention
    # kernels on all 72 donated buffers; the 8-layer program above is too
    # small to show what XLA stages through VMEM at this depth)
    large = chip_smoke.Size(vocab=50304, hidden=1280, layers=36, heads=20,
                            max_len=1024, batch=0, seq=0)
    from paddle_tpu.serving import GenerationEngine

    eng = GenerationEngine(chip_smoke.build_model(large), max_batch=32,
                           max_len=1024, freeze_weights=False)
    yield "serve_decode (gpt2-large b32 x 1024)", lambda eng=eng: lower_step(
        eng.decode_step, eng.example_decode_args([1]), place, place)
    del eng
    # the benchmark's Solar Open 2 cut as its cell serves it: 128 slots of
    # 5,120 positions, the decode step and the largest bucket its traffic
    # uses (6.6 GB of parameters and 4.4 GB of cache are built on this host)
    eng = chip_smoke.build_delta_rule_engine(False, max_batch=128,
                                             max_len=5120,
                                             freeze_weights=False)
    yield "serve_decode (delta-rule cut b128 x 5120)", lambda: lower_step(
        eng.decode_step, eng.example_decode_args([1]), place, place)
    yield "serve_prefill bucket 4096 (delta-rule cut)", lambda: lower_step(
        eng.prefill_step, (np.zeros((1, 4096), np.int32), np.int32(1),
                           np.int32(0), eng._example_cache([0])), place,
        place)


    del eng
    # the benchmark's Laguna cut as its cell serves it: 48 slots of 9,216
    # positions (4 full-length layers, 9 rings of 512), the decode step and
    # the largest bucket its traffic uses (3.6 GB of parameters and 8.2 GB
    # of cache are built on this host)
    eng = chip_smoke.build_ring_engine(False, max_batch=48, max_len=9216,
                                       freeze_weights=False)
    eng.cache = None  # the example arguments bring their own 8.2 GB
    yield "serve_decode (ring cut b48 x 9216)", lambda: lower_step(
        eng.decode_step, eng.example_decode_args([1]), place, place)
    yield "serve_prefill bucket 8192 (ring cut)", lambda: lower_step(
        eng.prefill_step, (np.zeros((1, 8192), np.int32), np.int32(1),
                           np.int32(0), eng._example_cache([0])), place,
        place)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", action="store_true",
                    help="also compile the full-width chip_smoke.py steps")
    args = ap.parse_args(argv)
    try:
        devs = topology_devices()
    except Exception as e:  # noqa: BLE001 - no libtpu / unknown topology
        print(f"topology {TOPOLOGY} unavailable: {type(e).__name__}: {e}")
        return 2
    print(f"jax {jax.__version__}; compiling for {len(devs)} x "
          f"{devs[0].device_kind} ({TOPOLOGY})", flush=True)
    # The process's own backend is the CPU, so the routers would pick the
    # XLA formulations; the programs below are lowered for the topology's
    # TPU devices, where the kernels are what runs.
    from paddle_tpu.ops import pallas

    pallas.is_available = lambda: True

    rep = Report()
    for name, build in kernel_programs(devs):
        compiled = rep.run(name, build)
        if compiled is not None and name.startswith("flash decode"):
            # the kernel takes the cache in the layout the TPU keeps it in:
            # a program that had to re-lay a K or V buffer out would hold a
            # copy of it among its temporaries
            print(f"       temporaries "
                  f"{compiled.memory_analysis().temp_size_in_bytes} bytes",
                  flush=True)
        if compiled is not None and name.startswith("kv row write"):
            # in place: no temporary as large as a cache buffer, and under
            # donation the kernel's outputs ARE the donated parameters (the
            # transposes and reshapes around it are bitcasts, no copy stands
            # between parameter, kernel and result)
            ma = compiled.memory_analysis()
            print(f"       temporaries {ma.temp_size_in_bytes} bytes, "
                  f"aliased {ma.alias_size_in_bytes} of "
                  f"{ma.argument_size_in_bytes} argument bytes", flush=True)
            if "donated" in name:
                text = compiled.as_text()
                header = text[:text.index("\n")]
                pairs = re.findall(r"\{(\d+)\}: \((\d+), \{\}", header)
                print(f"       outputs aliased to parameters: "
                      f"{' '.join(f'{o}<-{i}' for o, i in pairs) or 'none'}; "
                      f"{decode_program_facts(text)}", flush=True)
        if compiled is not None and name.startswith("kv row dma"):
            ma = compiled.memory_analysis()
            print(f"       {ma.temp_size_in_bytes} temporary bytes, "
                  f"{ma.alias_size_in_bytes} of {ma.argument_size_in_bytes} "
                  f"argument bytes aliased; "
                  f"{row_dma_facts(compiled.as_text())}", flush=True)
        if compiled is not None and name.startswith(("gated experts",
                                                     "kda step")):
            # no copy of an expert stack (0.84 GB) or of the states (0.54
            # GB): the stacks are read where they lie, the state in place
            ma = compiled.memory_analysis()
            print(f"       {ma.temp_size_in_bytes} temporary bytes; "
                  f"{ma.alias_size_in_bytes} of {ma.argument_size_in_bytes} "
                  f"argument bytes aliased", flush=True)
        if compiled is not None and name.startswith("moe grouped"):
            # both products contract over the stacks' minor dimension as the
            # TPU keeps them: a program that had to re-lay a stack out would
            # hold 0.6 GB of it here
            print(f"       re-laid-out {compiled.memory_analysis().temp_size_in_bytes} "
                  f"bytes", flush=True)
        if compiled is not None and name.startswith("dp4"):
            for shapes in sorted(set(
                    mosaic_operand_shapes(compiled.as_text()))):
                print(f"       per-device Mosaic operands: "
                      f"{' '.join(shapes)}", flush=True)
    if args.steps:
        for name, build in step_programs(devs):
            compiled = rep.run(name, build)
            if compiled is not None:
                ma = compiled.memory_analysis()
                print(f"       temp {ma.temp_size_in_bytes / 2**30:.2f} GiB, "
                      f"arguments {ma.argument_size_in_bytes / 2**30:.2f} GiB",
                      flush=True)
            if compiled is not None and "gpt2-large" in name:
                print(f"       {decode_program_facts(compiled.as_text())}",
                      flush=True)
            if compiled is not None and "delta-rule" in name:
                # XLA ops (not the kernels, not bitcasts) whose result is a
                # whole expert stack or every slot's state: a copy of one
                copies = re.findall(
                    r"= (?:bf16\[40,(?:2560|1280),4096\]|"
                    r"f32\[128,64,128,128\])\S* "
                    r"(?:copy|transpose|fusion|copy-start)\(",
                    compiled.as_text())
                print(f"       copies of an expert stack or of the states: "
                      f"{len(copies)}", flush=True)
            if compiled is not None and "ring cut" in name:
                # XLA ops of the program's own list (inside a fusion an
                # operand is no buffer) whose result is a whole K or V
                # buffer (full-length or ring) or an expert stack, but for
                # the prefill's in-place dynamic-update-slice: a copy of
                # one; and how much of the arguments the outputs alias (the
                # donated cache, the ring written in place)
                text = compiled.as_text()
                copies = [name for name in re.findall(
                    r"(%\S+) = bf16\[(?:48,(?:9216|512),8,128|"
                    r"32,(?:1024|512),2048)\]\S* "
                    r"(?:copy|transpose|fusion|copy-start)\(",
                    text[text.index("\nENTRY"):])
                    if "dynamic-update-slice" not in name]
                print(f"       copies of a cache buffer or of an expert "
                      f"stack: {len(copies)}; aliased "
                      f"{ma.alias_size_in_bytes / 2**30:.2f} GiB",
                      flush=True)
                if "serve_decode" in name:
                    print(f"       {row_dma_facts(text)}", flush=True)
    if rep.failed:
        print(f"{len(rep.failed)} program(s) failed: {rep.failed}")
        return 1
    print("all programs compiled")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
