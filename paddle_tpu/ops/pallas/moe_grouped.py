"""Grouped expert product as a Pallas TPU kernel: rows sorted by expert, one
matrix product over the experts held here.

The rows of ``xs [M, K]`` are (token, expert) pairs, laid out so that every
tile of ``tm`` rows belongs to ONE expert (each expert's rows start on a
tile boundary; ``nn/layer/experts.py`` builds that layout and pads with
rows nobody reads). ``tile_expert[t]`` names tile ``t``'s expert, so the
kernel is a plain tiled matmul whose weight block is picked by a
scalar-prefetched index: ``out[t] = act(xs[t] @ w[tile_expert[t]])``.
``transpose_rhs`` takes ``w [E, N, K]`` (each expert's matrix out-major, as
it is published) and contracts over its LAST dimension: the TPU keeps a
``[E, 2688, 1856]`` array with the 128-aligned 2688 minor whatever its
logical order, and a kernel that asked for the other order would be handed
a 630 MB copy of the weights every call.
Tiles past ``n_active`` are padding: their index maps repeat the last live
block (nothing is fetched again) and they write zeros.

A decode step has a handful of rows per expert, so the kernel is bound by
the bytes of the weights of the experts that were hit, each read once
(twice where an expert's rows span two tiles); a prefill bucket is bound by
the MXU. ``activation="relu2"`` squares the rectified result in the
epilogue (the experts' ``down(relu(up(x))**2)``). ``activation="swiglu"``
(``transpose_rhs`` only) takes ``w [E, 2 N, K]``, each expert's gate matrix
stacked on its up matrix, both out-major: one weight block holds the same
``tn`` rows of both, two accumulators take the two products and the epilogue
writes ``silu(gate) * up`` (device op ``moe_grouped_swiglu``), so a gated
expert's first layer is one pass over the rows and one over its weights.

The backward pass is that of the XLA formulation (a gather of the tiles'
weights and a batched product), which is also what runs off the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
#: one weight block (it is double-buffered) may hold this much of VMEM
BLOCK_BYTES = 4 << 20


def _divisors_128(n):
    return [d for d in range(128, n, 128) if n % d == 0] + [n]


def pick_blocks(K, N, itemsize=2):
    """``(tk, tn)``: the largest weight block within ``BLOCK_BYTES`` whose
    sides are the whole dimension or a 128-aligned divisor of it (wide
    before deep: a full row of ``N`` keeps the epilogue in one step)."""
    fits = [(tk * tn, tn, tk) for tk in _divisors_128(K)
            for tn in _divisors_128(N) if tk * tn * itemsize <= BLOCK_BYTES]
    if not fits:
        return 0, 0
    _, tn, tk = max(fits)
    return tk, tn


def supports_grouped(tm, K, N, itemsize=2):
    return tm % 16 == 0 and pick_blocks(K, N, itemsize)[0] > 0


def _act(a, activation):
    if activation == "relu2":
        return jnp.square(jnp.maximum(a, 0.0))
    if activation == "swiglu":  # [..., gate | up]
        n = a.shape[-1] // 2
        return jax.nn.silu(a[..., :n]) * a[..., n:]
    return a


def _kernel(te_ref, na_ref, x_ref, w_ref, o_ref, acc_ref, *, nk, activation,
            transpose_rhs):
    mi, ki = pl.program_id(0), pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(mi < na_ref[0])
    def _accumulate():
        acc_ref[:] += jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=F32)

    @pl.when(ki == nk - 1)
    def _store():
        o_ref[...] = _act(acc_ref[:], activation).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _grouped_call(xs, w, tile_expert, n_active, tm, activation,
                  transpose_rhs, interpret):
    M, K = xs.shape
    N = w.shape[1 if transpose_rhs else 2]
    tk, tn = pick_blocks(K, N, w.dtype.itemsize)
    nk, nn = K // tk, N // tn

    def live(mi, na):
        return mi < na[0]

    def x_map(mi, ni, ki, te, na):
        on = live(mi, na)
        return (jnp.where(on, mi, jnp.maximum(na[0] - 1, 0)),
                jnp.where(on, ki, nk - 1))

    def w_map(mi, ni, ki, te, na):
        on = live(mi, na)
        kn = (jnp.where(on, ki, nk - 1), jnp.where(on, ni, nn - 1))
        return (te[mi],) + (kn[::-1] if transpose_rhs else kn)

    def o_map(mi, ni, ki, te, na):
        return (mi, ni)

    kernel = functools.partial(_kernel, nk=nk, activation=activation,
                               transpose_rhs=transpose_rhs)
    return pl.pallas_call(
        kernel,
        name="moe_grouped_" + (activation or "plain"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm, nn, nk),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((1, tn, tk) if transpose_rhs
                                   else (1, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=[pltpu.VMEM((tm, tn), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(2 * M * K * N),
            bytes_accessed=int(w.size * w.dtype.itemsize
                               + (M * K + M * N) * xs.dtype.itemsize),
            transcendentals=0),
    )(tile_expert, n_active, xs, w)


def _gated_kernel(te_ref, na_ref, x_ref, w_ref, o_ref, gate_ref, up_ref, *,
                  nk):
    mi, ki = pl.program_id(0), pl.program_id(2)
    dims = (((1,), (1,)), ((), ()))

    @pl.when(ki == 0)
    def _zero():
        gate_ref[:] = jnp.zeros_like(gate_ref)
        up_ref[:] = jnp.zeros_like(up_ref)

    @pl.when(mi < na_ref[0])
    def _accumulate():
        x = x_ref[...]
        gate_ref[:] += jax.lax.dot_general(x, w_ref[0, 0], dims,
                                           preferred_element_type=F32)
        up_ref[:] += jax.lax.dot_general(x, w_ref[0, 1], dims,
                                         preferred_element_type=F32)

    @pl.when(ki == nk - 1)
    def _store():
        gate = gate_ref[:]
        o_ref[...] = (gate * jax.nn.sigmoid(gate) * up_ref[:]).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(4, 5))
def _gated_call(xs, w, tile_expert, n_active, tm, interpret):
    """``silu(xs @ gate^T) * (xs @ up^T)`` a tile, ``w [E, 2 N, K]``."""
    M, K = xs.shape
    E, N = w.shape[0], w.shape[1] // 2
    tk, tn = pick_blocks(K, N, 2 * w.dtype.itemsize)
    nk, nn = K // tk, N // tn

    def x_map(mi, ni, ki, te, na):
        on = mi < na[0]
        return (jnp.where(on, mi, jnp.maximum(na[0] - 1, 0)),
                jnp.where(on, ki, nk - 1))

    def w_map(mi, ni, ki, te, na):
        on = mi < na[0]
        return (te[mi], 0, jnp.where(on, ni, nn - 1),
                jnp.where(on, ki, nk - 1))

    return pl.pallas_call(
        functools.partial(_gated_kernel, nk=nk),
        name="moe_grouped_swiglu",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // tm, nn, nk),
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((1, 2, tn, tk), w_map)],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda mi, ni, ki, te, na: (mi, ni)),
            scratch_shapes=[pltpu.VMEM((tm, tn), F32),
                            pltpu.VMEM((tm, tn), F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * M * K * N),
            bytes_accessed=int(w.size * w.dtype.itemsize
                               + (M * K + M * N) * xs.dtype.itemsize),
            transcendentals=int(M * N)),
    )(tile_expert, n_active, xs, w.reshape(E, 2, N, K))


def supports_gated(tm, K, N, itemsize=2):
    """The gated product's gate: one block holds ``tn`` rows of BOTH
    matrices, so it prices an element at twice its size."""
    return supports_grouped(tm, K, N, 2 * itemsize)


def grouped_matmul_xla(xs, w, tile_expert, tm, activation=None,
                       transpose_rhs=False):
    """The same product as XLA sees it: each tile against a gathered copy
    of its expert's matrix (off the TPU, and the kernel's backward pass)."""
    M, K = xs.shape
    out = jnp.einsum("tmk,tnk->tmn" if transpose_rhs else "tmk,tkn->tmn",
                     xs.reshape(M // tm, tm, K), w[tile_expert],
                     preferred_element_type=F32)
    return _act(out, activation).reshape(M, -1).astype(xs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def grouped_matmul_pallas(xs, w, tile_expert, n_active, tm, activation=None,
                          transpose_rhs=False):
    """``act(xs[tile] @ w[tile_expert[tile]])`` for every tile of ``tm``
    rows; ``n_active`` (int32 ``[1]``) tiles are live."""
    from . import interpret_requested

    if activation == "swiglu":
        if not transpose_rhs:
            raise ValueError("the gated product takes its stack out-major "
                             "(transpose_rhs=True)")
        return _gated_call(xs, w, tile_expert, n_active, int(tm),
                           bool(interpret_requested()))
    return _grouped_call(xs, w, tile_expert, n_active, int(tm), activation,
                         bool(transpose_rhs), bool(interpret_requested()))


def _vjp_fwd(xs, w, tile_expert, n_active, tm, activation, transpose_rhs):
    out = grouped_matmul_pallas(xs, w, tile_expert, n_active, tm, activation,
                                transpose_rhs)
    return out, (xs, w, tile_expert, n_active)


def _vjp_bwd(tm, activation, transpose_rhs, res, g):
    xs, w, tile_expert, n_active = res
    _, pull = jax.vjp(
        lambda a, b: grouped_matmul_xla(a, b, tile_expert, tm, activation,
                                        transpose_rhs), xs, w)
    zero = lambda a: np.zeros(a.shape, jax.dtypes.float0)
    return (*pull(g), zero(tile_expert), zero(n_active))


grouped_matmul_pallas.defvjp(_vjp_fwd, _vjp_bwd)
