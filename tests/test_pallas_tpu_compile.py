"""AOT-compile every Pallas kernel for a TPU v5e from the CPU suite.

libtpu ships in the test environment, so the real XLA:TPU + Mosaic
compilers run against a compile-only ``v5e:2x2`` topology
(``tools/tpu_aot_preflight.py``). This is what catches a kernel that only
the interpreter accepts: the CPU mesh tests run the XLA formulations
(``pallas.is_available()`` is false there) or interpret mode, and neither
enforces Mosaic's tiling rules or its refusal to be partitioned by GSPMD.

Runs in a subprocess: libtpu's initialisation must not disturb this
session's forced-CPU backend.
"""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def preflight():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "tpu_aot_preflight.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    if proc.returncode == 2:
        pytest.skip(proc.stdout.strip().splitlines()[-1])
    return proc


def test_every_kernel_compiles_for_v5e(preflight):
    out = preflight.stdout
    assert preflight.returncode == 0, out + preflight.stderr[-2000:]
    assert "TPU v5 lite" in out
    lines = [l for l in out.splitlines() if l.startswith(("ok ", "FAIL"))]
    assert lines and not [l for l in lines if l.startswith("FAIL")], out
    for program in ("layer_norm fwd+bwd", "flash packed causal fwd+bwd",
                    "flash packed dropout fwd+bwd",
                    "flash layout-swapping bias fwd+bwd"):
        assert any(program in l for l in lines), (program, out)


def test_cached_kernel_compiles_at_batch_1_and_8(preflight):
    # batch 8 was refused by the Pallas TPU lowering while kv_len rode a
    # per-batch (1, 1) SMEM block
    out = preflight.stdout
    assert "ok   flash cached b1 sq1024 sk1024" in out, out
    assert "ok   flash cached b8 sq128 sk1024" in out, out


def test_decode_kernel_compiles_and_copies_no_cache_buffer(preflight):
    # decode (one query row a slot) and verify (spec_k + 1 rows) pass Mosaic,
    # and the kernel's view of K and V is the layout XLA:TPU already keeps
    # them in: no temporary as large as a cache buffer (a re-layout would
    # be a full copy of every layer's K and V in every decode step)
    import re

    out = preflight.stdout
    assert "ok   flash decode b8 sq1 sk1024" in out, out
    assert "ok   flash decode b8 sq5 sk1024" in out, out
    assert "ok   dp4: flash decode b8" in out, out
    temps = [int(t) for t in re.findall(r"temporaries (\d+) bytes\n", out)]
    assert len(temps) == 2, out
    assert max(temps) < 8 * 1024 * 12 * 64 * 2, temps  # one bf16 cache buffer


def test_row_write_kernel_compiles_and_writes_the_donated_buffer_in_place(
        preflight):
    # the decode step's K/V row write (one row a slot, and verify's
    # spec_k + 1) passes Mosaic alone and under the dp4 mesh, where each
    # device's call sees its 8 / 4 = 2 slots. In a decode-shaped program of
    # eight layers at the serving cells' batch, with the cache donated, the
    # view the kernel takes is the layout XLA:TPU keeps: no temporary as
    # large as a cache buffer; every kernel output aliases its donated
    # parameter; and no copy of a cache buffer, neither a re-layout nor the
    # staging through VMEM that XLA's memory-space assignment puts around an
    # unpinned kernel (it showed on the chip first: 5 ms a decode step)
    import re

    out = preflight.stdout
    for program in ("kv row write b8 s1 sk1024", "kv row write b8 s5 sk1024",
                    "kv row write donated decode b32 x8",
                    "dp4: kv row write b8"):
        assert f"ok   {program}" in out, out
    temps = [int(t) for t in re.findall(r"temporaries (\d+) bytes, aliased",
                                        out)]
    assert len(temps) == 3 and max(temps) < 8 * 1024 * 20 * 64 * 2, (temps,
                                                                      out)
    donated = out[out.index("ok   kv row write donated decode b32 x8"):]
    aliased, arguments = map(int, re.search(
        r"aliased (\d+) of (\d+) argument bytes", donated).groups())
    assert 16 * 32 * 1024 * 20 * 64 * 2 == aliased <= arguments, donated[:600]
    pairs = " ".join(f"{i + 1}<-{i}" for i in range(16))
    assert f"outputs aliased to parameters: {pairs};" in donated, donated[:600]
    assert "copies of a cache buffer: 0" in donated, donated[:600]
    # the attention kernel's list of live (slot, block) pairs depends on the
    # step's positions alone, the row write's list of live slots on the
    # engine's mask alone: each built once, not once a layer
    assert ("lists of live (slot, block) pairs built: 1; lists of live "
            "slots built: 1\n") in donated, donated[:600]
    operands = [l for l in out.splitlines()
                if "per-device Mosaic operands" in l]
    assert any(l.split(": ")[1].startswith("s32[2] ")
               and l.endswith(" bf16[2,768,1024] bf16[2,768,1024]")
               for l in operands), out


def test_row_dma_writes_the_donated_buffers_in_place_at_full_depth(
        preflight):
    # the decode step's row write where a row is contiguous (heads of 128):
    # the kernel alone at the expert cells' caches, one row a slot and
    # verify's five; then the Laguna cut's 13 layers (4 x 9,216 rows and 9
    # rings of 512) at b48, donated, each layer writing then attending.
    # There: no while loop and no dynamic-update-slice left; every cache
    # operand of every call is a donated parameter and every output one of
    # the program's, through bitcasts alone (no copy, no staging of a ring
    # through VMEM around the call: PR 33 found that only at full depth);
    # every output aliased to its donated parameter
    import re

    out = preflight.stdout
    lines = out.splitlines()
    for cb, sk in ((48, 9216), (48, 512), (128, 5120)):
        for rows in (1, 5):
            at = [l.startswith(f"ok   kv row dma b{cb} s{rows} sk{sk}:")
                  for l in lines].index(True)
            assert ("row DMA calls 1: cache operands that are donated "
                    "parameters 2 of 2, outputs that are the program's 2 of "
                    "2; while loops 0, dynamic-update-slices 0"
                    in lines[at + 1]), lines[at + 1]
            temp, aliased = map(int, re.search(
                r"(\d+) temporary bytes, (\d+) of", lines[at + 1]).groups())
            assert temp == 0 and aliased == 2 * cb * sk * 8 * 128 * 2
    facts = lines[[l.startswith("ok   kv row dma donated decode b48 x13:")
                   for l in lines].index(True) + 1]
    assert ("row DMA calls 13: cache operands that are donated parameters "
            "26 of 26, outputs that are the program's 26 of 26; while loops "
            "0, dynamic-update-slices 0" in facts), facts
    aliased = int(re.search(r"bytes, (\d+) of", facts).group(1))
    assert aliased == 2 * 48 * (4 * 9216 + 9 * 512) * 8 * 128 * 2, facts
    assert ("ok   dp4: kv row dma b8" in out
            and "per-device Mosaic operands: s32[2] bf16[2,1,8,128] "
                "bf16[2,1,8,128] bf16[1024,8,128] bf16[1024,8,128]" in out)


def test_sharded_step_compiles_and_kernels_see_the_local_batch(preflight):
    # "Mosaic kernels cannot be automatically partitioned": LN + flash
    # inside one jit over the 4-device mesh must partition themselves, and
    # each device's kernels must get 8 / 4 = 2 batch rows (2 * 1024 LN rows)
    out = preflight.stdout
    assert "ok   dp4: layer_norm + flash fwd+bwd" in out, out
    assert "ok   dp4: flash cached b8" in out, out
    operands = [l for l in out.splitlines()
                if "per-device Mosaic operands" in l]
    assert any("bf16[2,1024,768]" in l for l in operands), out
    assert any("bf16[2048,768]" in l for l in operands), out
    assert any("bf16[2,12,64,1024]" in l for l in operands), out
    assert not any("bf16[8," in l or "bf16[8192," in l
                   for l in operands), out


def test_expert_and_state_kernels_compile_at_the_served_widths(preflight):
    # the grouped expert product (an expert width that is no multiple of
    # 128, decode- and prefill-sized tiles) and the recurrence's two kernels
    # pass Mosaic, and no kernel is handed a copy of the 0.6 GB expert
    # stack: XLA:TPU keeps [64, 1856, 2688] with 2688 minor, which is the
    # order both products ask for
    import re

    out = preflight.stdout
    for program in ("moe grouped up+down t64 tm16",
                    "moe grouped up+down t1024 tm64",
                    "ssm step b64 h64 p64 n128", "ssm scan carry 8 blocks"):
        assert f"ok   {program}" in out, out
    temps = [int(t) for t in re.findall(r"re-laid-out (\d+) bytes", out)]
    assert len(temps) == 2 and max(temps) < 64 * 1856 * 2688, (temps, out)


def test_delta_rule_and_gated_expert_kernels_compile_at_the_served_widths(
        preflight):
    # the gated expert product (one block holds the same rows of the gate
    # and of the up matrix; decode- and prefill-sized tiles) and the
    # delta-rule state kernel pass Mosaic at hidden 4096, width 1280, 128
    # slots of [64, 128, 128]; no kernel is handed a copy of a 0.84 GB
    # expert stack, and the donated states (0.54 GB) are written in place
    import re

    out = preflight.stdout
    lines = out.splitlines()
    temps = {}
    for program in ("gated experts up+down t128 tm16",
                    "gated experts up+down t4096 tm128",
                    "kda step b128 h64 128x128 donated"):
        at = next((i for i, l in enumerate(lines)
                   if l.startswith(f"ok   {program}")), None)
        assert at is not None, out
        temps[program] = [int(n) for n in re.findall(
            r"(\d+) temporary bytes; (\d+) of (\d+) argument bytes aliased",
            lines[at + 1])[0]]
    stack = 40 * 2560 * 4096 * 2
    assert all(t[0] < stack // 4 for t in temps.values()), temps
    state = 128 * 64 * 128 * 128 * 4
    temp, aliased, _ = temps["kda step b128 h64 128x128 donated"]
    assert aliased == state and temp < state // 8, temps
