"""The Mamba-2 recurrence's two memory-bound pieces as Pallas TPU kernels.

``ssm_step_fwd`` — decode: every slot's state ``S [H, P, N]`` (float32,
2 MB at 64 x 64 x 128) is read once, updated ``S = dA S + (dt x) (x) B``,
contracted with ``C`` and written back in place. One grid step per slot;
the heads are unrolled so that every slice is static. The per-head column
``dt x [P, 1]`` comes from the transposed input ``[P, H]`` (a lane slice),
the rows ``B``, ``C`` ``[1, N]`` from their group's sublane, the decay from
SMEM; ``y`` is assembled as ``[P, H]`` and transposed back outside.

``ssm_scan_carry`` — prefill: the recurrence between the blocks of the
chunked scan, ``S_in[c + 1] = decay[c] S_in[c] + states[c]``, the running
state kept in VMEM while the block states stream through once.

Both are bound by the bytes of the state. Their backward passes are those
of the XLA formulations in ``nn/functional/ssm.py`` (serving pulls none).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: heads of one grid step of the carry kernel
CARRY_HEADS = 8
#: in + out state blocks, double-buffered, may hold this much of VMEM
STATE_BYTES = 12 << 20


def supports_step(x_shape, b_shape):
    """Shape gate: whole (8, 128) tiles of ``[P, N]`` per head, the heads
    divide into the groups, one slot's state fits VMEM four times."""
    _, H, P = x_shape
    _, G, N = b_shape
    return (P % 8 == 0 and N % 128 == 0 and H % G == 0
            and 4 * H * P * N * 4 <= STATE_BYTES)


def _step_kernel(da_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref, o_ref, *,
                 heads, per_group):
    bi = pl.program_id(0)
    xt = xdt_ref[0]                                   # [P, H]
    lane = jax.lax.broadcasted_iota(jnp.int32, xt.shape, 1)
    y = jnp.zeros(xt.shape, F32)
    for h in range(heads):
        g = h // per_group
        S = da_ref[bi * heads + h] * s_ref[0, h] \
            + xt[:, h:h + 1] * b_ref[0, g:g + 1, :]   # [P, N]
        o_ref[0, h] = S
        y_h = jnp.sum(S * c_ref[0, g:g + 1, :], axis=-1, keepdims=True)
        y = jnp.where(lane == h, y_h, y)
    y_ref[0] = y


@functools.partial(jax.jit, static_argnums=(5,))
def _step_call(da, xdt_t, B, C, S, interpret):
    b, H, P, N = S.shape
    G = B.shape[1]
    kernel = functools.partial(_step_kernel, heads=H, per_group=H // G)
    return pl.pallas_call(
        kernel,
        name="ssm_step_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b,),
            in_specs=[
                pl.BlockSpec((1, P, H), lambda i, da: (i, 0, 0)),
                pl.BlockSpec((1, G, N), lambda i, da: (i, 0, 0)),
                pl.BlockSpec((1, G, N), lambda i, da: (i, 0, 0)),
                pl.BlockSpec((1, H, P, N), lambda i, da: (i, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, P, H), lambda i, da: (i, 0, 0)),
                pl.BlockSpec((1, H, P, N), lambda i, da: (i, 0, 0, 0)),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, P, H), F32),
                   jax.ShapeDtypeStruct(S.shape, F32)],
        # operand 4 counts the scalar-prefetch argument: the state
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(6 * S.size), bytes_accessed=int(8 * S.size),
            transcendentals=0),
    )(da.reshape(b * H), xdt_t, B, C, S)


@jax.custom_vjp
def ssm_step_pallas(x, dt, A, B, C, S):
    """``ssm_step`` of ``nn/functional/ssm.py`` through the kernel."""
    from . import interpret_requested

    da = jnp.exp(dt * A)                              # [b, H]
    xdt_t = jnp.swapaxes(dt[..., None] * x, 1, 2)     # [b, P, H]
    y_t, S = _step_call(da, xdt_t, B, C, S, bool(interpret_requested()))
    return jnp.swapaxes(y_t, 1, 2), S


def _step_vjp_fwd(*args):
    return ssm_step_pallas(*args), args


def _step_vjp_bwd(args, g):
    from ...nn.functional.ssm import _step_xla

    return jax.vjp(_step_xla, *args)[1](g)


ssm_step_pallas.defvjp(_step_vjp_fwd, _step_vjp_bwd)


def supports_carry(states_shape):
    _, _, H, P, N = states_shape
    return P % 8 == 0 and N % 128 == 0 and H % CARRY_HEADS == 0


def _carry_kernel(decay_ref, st_ref, s0_ref, in_ref, last_ref, s_ref, *,
                  heads, blocks):
    bi, hi, ci = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[:] = s0_ref[0]

    in_ref[0, 0] = s_ref[:]
    base = (bi * blocks + ci) * heads + hi * CARRY_HEADS
    for j in range(CARRY_HEADS):
        s_ref[j] = decay_ref[base + j] * s_ref[j] + st_ref[0, 0, j]

    @pl.when(ci == blocks - 1)
    def _finish():
        last_ref[0] = s_ref[:]


@functools.partial(jax.jit, static_argnums=(3,))
def _carry_call(decay, states, S0, interpret):
    b, nc, H, P, N = states.shape
    hb = CARRY_HEADS
    kernel = functools.partial(_carry_kernel, heads=H, blocks=nc)
    blk = lambda i, h, c, d: (i, c, h, 0, 0)
    head = lambda i, h, c, d: (i, h, 0, 0)
    return pl.pallas_call(
        kernel,
        name="ssm_scan_carry",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, H // hb, nc),
            in_specs=[pl.BlockSpec((1, 1, hb, P, N), blk),
                      pl.BlockSpec((1, hb, P, N), head)],
            out_specs=[pl.BlockSpec((1, 1, hb, P, N), blk),
                       pl.BlockSpec((1, hb, P, N), head)],
            scratch_shapes=[pltpu.VMEM((hb, P, N), F32)],
        ),
        out_shape=[jax.ShapeDtypeStruct(states.shape, F32),
                   jax.ShapeDtypeStruct(S0.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(decay.reshape(b * nc * H), states, S0)


@jax.custom_vjp
def ssm_scan_carry_pallas(decay, states, S0):
    """``(S_in [b, nc, H, P, N], S_last [b, H, P, N])``."""
    from . import interpret_requested

    return tuple(_carry_call(decay, states, S0,
                             bool(interpret_requested())))


def _carry_vjp_fwd(*args):
    return ssm_scan_carry_pallas(*args), args


def _carry_vjp_bwd(args, g):
    from ...nn.functional.ssm import _carry_xla

    return jax.vjp(_carry_xla, *args)[1](g)


ssm_scan_carry_pallas.defvjp(_carry_vjp_fwd, _carry_vjp_bwd)
