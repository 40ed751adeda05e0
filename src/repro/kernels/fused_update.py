"""Pallas TPU kernel: fused DFedSGPSM inner-loop update (Algorithm 1, 9-11 + 5).

    v' = alpha * v + g          (momentum)
    x' = x  - eta * v'          (descent)
    z' = x' / w                 (push-sum de-bias for the next iteration)

Unfused, these are 3 elementwise passes = 5 HBM reads + 3 writes of the full
model; fused it is 3 reads + 3 writes in a single pass — the update becomes
strictly HBM-bandwidth-bound at its floor.  Scalars (alpha, eta, 1/w) ride in
as a tiny (3,) operand broadcast to every grid step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

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
# call, with a per-client push-sum weight column.  Same fused arithmetic,
# one grid step per (block_n, block_d) tile.
# ---------------------------------------------------------------------------

def _bank_kernel(s_ref, wi_ref, x_ref, v_ref, g_ref, xo_ref, vo_ref, zo_ref):
    alpha, eta = s_ref[0], s_ref[1]
    v_new = alpha * v_ref[...] + g_ref[...].astype(jnp.float32)
    x_new = x_ref[...].astype(jnp.float32) - eta * v_new
    vo_ref[...] = v_new
    xo_ref[...] = x_new.astype(xo_ref.dtype)
    zo_ref[...] = (x_new * wi_ref[...]).astype(zo_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_d", "interpret"))
def fused_update_bank_pallas(
    X: jax.Array,  # (n, D) flat client-parameter bank
    V: jax.Array,  # (n, D) momentum bank, float32
    G: jax.Array,  # (n, D) per-client (perturbed) gradients
    alpha,
    eta,
    w: jax.Array,  # (n,) per-client push-sum weights
    block_n: int = 8,
    block_d: int = 512,
    interpret: bool = False,
):
    n, d = X.shape
    n_pad = max(((n + block_n - 1) // block_n) * block_n, block_n)
    d_pad = max(((d + block_d - 1) // block_d) * block_d, block_d)
    aligned = (n_pad, d_pad) == (n, d)

    def pad(t, dt):
        if aligned:
            return t.astype(dt)
        return jnp.zeros((n_pad, d_pad), dt).at[:n, :d].set(t.astype(dt))

    # The copies around the kernel are the round's ``update_pad`` phase,
    # named for the profiler; the kernel itself is not in it.
    with jax.named_scope("update_pad"):
        scalars = jnp.stack([jnp.float32(alpha), jnp.float32(eta)])
        # Padded rows carry weight 1 so the de-bias never divides by zero.
        w_inv = jnp.ones((n_pad, 1), jnp.float32).at[:n, 0].set(
            1.0 / w.astype(jnp.float32))
    if interpret and aligned and (block_n, block_d) == (n, d):
        from repro.kernels.interpret import run_single_block

        return run_single_block(
            _bank_kernel,
            [scalars, w_inv, X, V.astype(jnp.float32), G],
            [X.dtype, jnp.float32, X.dtype])
    with jax.named_scope("update_pad"):
        operands = (scalars, w_inv, pad(X, X.dtype), pad(V, jnp.float32),
                    pad(G, X.dtype))
    x_new, v_new, z_new = pl.pallas_call(
        _bank_kernel,
        grid=(n_pad // block_n, d_pad // block_d),
        in_specs=[
            pl.BlockSpec((2,), lambda i, j: (0,)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
            pl.BlockSpec((block_n, block_d), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, d_pad), X.dtype),
            jax.ShapeDtypeStruct((n_pad, d_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, d_pad), X.dtype),
        ],
        interpret=interpret,
        name="fused_update_bank",
    )(*operands)
    if aligned:
        return x_new, v_new, z_new
    with jax.named_scope("update_pad"):
        return x_new[:n, :d], v_new[:n, :d], z_new[:n, :d]
