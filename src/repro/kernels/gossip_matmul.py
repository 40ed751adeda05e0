"""Pallas TPU kernel: push-sum gossip mixing  Y = P @ X.

P is the (n, n) column-stochastic mixing matrix, X the client-stacked flat
parameter matrix (n, D).  n is small (#clients, padded to the 128 MXU lane
width) while D is huge (model size), so the tiling keeps the full P row-band
resident in VMEM and streams X in (n, block_d) column panels — one MXU
matmul per grid step, no accumulation loop needed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gossip_matmul_pallas"]


def _kernel(p_ref, x_ref, o_ref):
    # HIGHEST: Mosaic's default contraction precision may take bf16 passes
    # for f32 operands; push-sum mixes the bank (and its weights, see
    # ``repro.core.pushsum.gossip_weights``) in full f32.
    o_ref[...] = jnp.dot(
        p_ref[...], x_ref[...], preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def gossip_matmul_pallas(
    P: jax.Array,
    X: jax.Array,
    block_n: int = 128,
    block_d: int = 512,
    interpret: bool = False,
):
    n, D = X.shape
    n_pad = max(((n + block_n - 1) // block_n) * block_n, block_n)
    d_pad = max(((D + block_d - 1) // block_d) * block_d, block_d)
    if interpret and (n_pad, d_pad) == (n, D) and (block_n, block_d) == (n, D):
        # Single unpadded block: run the kernel body directly (same traced
        # jnp, no per-block slicing, fuses into the caller's jit).
        from repro.kernels.interpret import run_single_block

        return run_single_block(_kernel, [P, X], [X.dtype])
    # Skip the pad copies when already tile-aligned (always true in the
    # interpret path, which picks exact block sizes).
    Pp = P if n_pad == n else jnp.zeros(
        (n_pad, n_pad), P.dtype).at[:n, :n].set(P)
    Xp = X if (n_pad, d_pad) == (n, D) else jnp.zeros(
        (n_pad, d_pad), X.dtype).at[:n, :D].set(X)

    out = pl.pallas_call(
        _kernel,
        grid=(n_pad // block_n, d_pad // block_d),
        in_specs=[
            pl.BlockSpec((block_n, n_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((n_pad, block_d), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_pad, d_pad), X.dtype),
        interpret=interpret,
        name="gossip_matmul",
    )(Pp, Xp)
    return out if (n_pad, d_pad) == (n, D) else out[:n, :D]
