"""Pallas TPU kernel: fused DFedSGPSM inner-loop update (Algorithm 1, 9-11 + 5).

    v' = alpha * v + g          (momentum)
    x' = x  - eta * v'          (descent)
    z' = x' / w                 (push-sum de-bias for the next iteration)

Unfused, these are 3 elementwise passes = 5 HBM reads + 3 writes of the full
model; fused it is 3 reads + 3 writes in a single pass — the update becomes
strictly HBM-bandwidth-bound at its floor.  Scalars (alpha, eta, 1/w) ride in
as a tiny (3,) operand broadcast to every grid step.  The row-banked kernel
(``fused_update_bank_pallas``) writes x' and v' alone: 3 reads + 2 writes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_update_pallas", "fused_update_bank_pallas"]


def _kernel(s_ref, x_ref, v_ref, g_ref, xo_ref, vo_ref, zo_ref):
    alpha, eta, w_inv = s_ref[0], s_ref[1], s_ref[2]
    v_new = alpha * v_ref[...] + g_ref[...].astype(jnp.float32)
    x_new = x_ref[...].astype(jnp.float32) - eta * v_new
    vo_ref[...] = v_new
    xo_ref[...] = x_new.astype(xo_ref.dtype)
    zo_ref[...] = (x_new * w_inv).astype(zo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def fused_update_pallas(
    x: jax.Array,  # (D,) current client params (flat)
    v: jax.Array,  # (D,) momentum buffer, float32
    g: jax.Array,  # (D,) perturbed gradient
    alpha,
    eta,
    w,
    block: int = 65536,
    interpret: bool = False,
):
    (d,) = x.shape
    d_pad = max(((d + block - 1) // block) * block, block)

    def pad(t, dt):
        if d_pad == d:
            return t.astype(dt)
        return jnp.zeros((d_pad,), dt).at[:d].set(t.astype(dt))

    scalars = jnp.stack(
        [jnp.float32(alpha), jnp.float32(eta), 1.0 / jnp.float32(w)])
    if interpret and d_pad == d == block:
        from repro.kernels.interpret import run_single_block

        return run_single_block(
            _kernel, [scalars, x, v.astype(jnp.float32), g],
            [x.dtype, jnp.float32, x.dtype])
    x_new, v_new, z_new = pl.pallas_call(
        _kernel,
        grid=(d_pad // block,),
        in_specs=[
            pl.BlockSpec((3,), lambda i: (0,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d_pad,), x.dtype),
            jax.ShapeDtypeStruct((d_pad,), jnp.float32),
            jax.ShapeDtypeStruct((d_pad,), x.dtype),
        ],
        interpret=interpret,
        name="fused_update",
    )(scalars, pad(x, x.dtype), pad(v, jnp.float32), pad(g, x.dtype))
    return x_new[:d], v_new[:d], z_new[:d]


# ---------------------------------------------------------------------------
# Row-banked variant: the whole (n_clients, D) flat parameter bank in one
# call.  The kernel writes only x' and v', one grid step per
# (block_n, block_d) tile of the caller's unpadded banks; the de-biased z'
# is plain XLA outside it, so a caller that drops z' never pays for it.
# ---------------------------------------------------------------------------

# Every stream of the kernel (x, v, g in; x', v' out) is double-buffered in
# VMEM; together they take at most this much: about 1 MB an f32 block of
# 100 rows, so the fixed cost of a grid step is a few percent of its
# transfer.
_VMEM_BUDGET = 12 * 2**20
# The v5e's whole VMEM, which the kernel reserves while it runs.  Left
# free, XLA keeps a bank that fits there (MNIST's 80 MB) in VMEM across the
# kernel, staged by copies outside it; with the reservation every stream of
# the kernel is in HBM, and its device time covers all of its traffic.
_VMEM_BYTES = 128 * 2**20
# Narrowest column panel before the rows are split: below it the fixed
# cost of a grid step, not HBM, sets the pace.
_MIN_BLOCK_D = 512


def _bank_tiles(n: int, d: int, x_dtype, g_dtype) -> tuple[int, int]:
    """The (block_n, block_d) tile for an (n, d) bank, from its shape and
    dtypes alone: every row in one block while a ``_MIN_BLOCK_D``-wide
    panel of them fits the budget (a full-dimension block is legal at any
    n), else a multiple of the sublane tile; then the widest multiple of
    128 lanes that fits, or all of d."""
    xs, gs = jnp.dtype(x_dtype).itemsize, jnp.dtype(g_dtype).itemsize
    row_align = 8 * (4 // min(xs, gs, 4))  # sublane tile: 8 f32, 16 bf16
    col_bytes = 2 * (2 * xs + gs + 2 * 4)  # x, x', g, v, v'; two buffers

    def rows(b):  # VMEM pads a block's rows to the sublane tile
        return -(-b // row_align) * row_align

    block_n = n
    if rows(n) * _MIN_BLOCK_D * col_bytes > _VMEM_BUDGET:
        block_n = max(row_align, _VMEM_BUDGET // (_MIN_BLOCK_D * col_bytes)
                      // row_align * row_align)
    block_d = _VMEM_BUDGET // (rows(block_n) * col_bytes) // 128 * 128
    return block_n, (d if block_d >= d else block_d)


def _bank_kernel(s_ref, x_ref, v_ref, g_ref, xo_ref, vo_ref):
    alpha, eta = s_ref[0], s_ref[1]
    v_new = alpha * v_ref[...] + g_ref[...].astype(jnp.float32)
    x_new = x_ref[...].astype(jnp.float32) - eta * v_new
    vo_ref[...] = v_new
    xo_ref[...] = x_new.astype(xo_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def fused_update_bank_pallas(
    X: jax.Array,  # (n, D) flat client-parameter bank
    V: jax.Array,  # (n, D) momentum bank, float32
    G: jax.Array,  # (n, D) per-client (perturbed) gradients
    alpha,
    eta,
    w: jax.Array,  # (n,) per-client push-sum weights
    block_n: int | None = None,
    block_d: int | None = None,
    interpret: bool = False,
):
    """``(x', v', z')`` for the whole bank; tiles from :func:`_bank_tiles`
    unless given."""
    n, d = X.shape
    auto_n, auto_d = _bank_tiles(n, d, X.dtype, G.dtype)
    block_n = auto_n if block_n is None else block_n
    block_d = auto_d if block_d is None else block_d
    # The XLA glue around the kernel is the round's ``update_pad`` phase,
    # named for the profiler; the kernel itself is not in it.
    with jax.named_scope("update_pad"):
        scalars = jnp.stack([jnp.float32(alpha), jnp.float32(eta)])
        w_inv = (1.0 / w.astype(jnp.float32))[:, None]
    if interpret and (block_n, block_d) == (n, d):
        from repro.kernels.interpret import run_single_block

        x_new, v_new = run_single_block(
            _bank_kernel, [scalars, X, V.astype(jnp.float32), G],
            [X.dtype, jnp.float32])
    else:
        # D is not a reduction axis: the ragged last tiles compute on lanes
        # and rows that the output writes discard, so no bank is ever
        # copied to a padded shape.  x' and v' overwrite x and v in place
        # (each tile is read before it is written): inside the solver's
        # scan, XLA would otherwise copy both carries around the kernel.
        tile = pl.BlockSpec((block_n, block_d), lambda i, j: (i, j))
        x_new, v_new = pl.pallas_call(
            _bank_kernel,
            grid=(pl.cdiv(n, block_n), pl.cdiv(d, block_d)),
            in_specs=[pl.BlockSpec((2,), lambda i, j: (0,)), tile, tile,
                      tile],
            out_specs=[tile, tile],
            out_shape=[
                jax.ShapeDtypeStruct((n, d), X.dtype),
                jax.ShapeDtypeStruct((n, d), jnp.float32),
            ],
            input_output_aliases={1: 0, 2: 1},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_BYTES),
            interpret=interpret,
            name="fused_update_bank",
        )(scalars, X, V.astype(jnp.float32), G)
    with jax.named_scope("update_pad"):
        z_new = (x_new.astype(jnp.float32) * w_inv).astype(X.dtype)
    return x_new, v_new, z_new
