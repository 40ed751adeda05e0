"""Pallas TPU kernel: sparse neighbor-indexed gossip  Y[i] = sum_l w[i,l] X[idx[i,l]].

The dense ``gossip_matmul`` pays O(n^2 * D) for a mixing matrix whose
columns hold only ``k_out + 1`` nonzeros; this kernel consumes the
fixed-shape ``(n, k_max)`` neighbor lists of
``repro.core.topology.NeighborList`` directly and does O(n * k_max * D)
work: a row gather plus weighted accumulate per neighbor slot.

Tiling mirrors ``gossip_matmul``: n (#clients) is small, D (model size) is
huge, so the grid streams X in ``(n, block_d)`` column panels.  The
neighbor lists ride in as scalar-prefetch operands (SMEM), and the Mosaic
body (``_row_kernel``) loops over receivers, reading each sender row of
the resident panel with a dynamic sublane slice ``x_ref[pl.ds(src, 1)]``.
Mosaic refuses a vectorized row gather (``jnp.take`` along the sublane
axis) inside a kernel, so the vectorized jnp body (``_kernel``) is kept for
the executors that run as plain traced jnp.

Two kernel bodies, four executors — all sharing the slot-by-slot f32
accumulation order, selected by ``repro.comm.plan.resolve_backend``:
``gossip_gather_pallas`` (``_row_kernel``; Mosaic on TPU, the Pallas
interpreter off it), ``gossip_gather_panels`` (CPU column panels of
``_kernel``), ``gossip_gather_xla`` (``_kernel`` over the whole bank — the
partitionable GSPMD all-gather lowering), and ``gossip_gather_halo`` (the
``shard_map`` halo exchange shipping only each shard's plan rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

__all__ = ["gossip_gather_pallas", "gossip_gather_panels",
           "gossip_gather_xla", "gossip_gather_halo"]


def _kernel(idx_ref, wgt_ref, x_ref, o_ref):
    x = x_ref[...]
    idx = idx_ref[...]
    wgt = wgt_ref[...].astype(jnp.float32)
    k_max = wgt.shape[1]
    # Static unroll over neighbor slots: slot l contributes one vectorized
    # row gather + axpy.  Accumulating slot-by-slot keeps the live
    # intermediate at one (n, block_d) panel instead of the (n, k_max,
    # block_d) tensor a take+einsum would materialize.
    acc = wgt[:, 0, None] * jnp.take(x, idx[:, 0], axis=0).astype(jnp.float32)
    for l in range(1, k_max):
        acc += wgt[:, l, None] * jnp.take(
            x, idx[:, l], axis=0
        ).astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _row_kernel(idx_ref, wgt_ref, x_ref, o_ref, *, k_max):
    # idx_ref / wgt_ref: the flattened (n * k_max,) neighbor lists in SMEM.
    # One receiver per loop iteration: slot l reads sender row idx[i, l] of
    # the resident (n, block_d) panel and accumulates in ``_kernel``'s
    # slot order, in f32.
    def receiver(i, carry):
        base = i * k_max
        acc = wgt_ref[base] * x_ref[pl.ds(idx_ref[base], 1), :].astype(
            jnp.float32)
        for l in range(1, k_max):
            acc += wgt_ref[base + l] * x_ref[
                pl.ds(idx_ref[base + l], 1), :
            ].astype(jnp.float32)
        o_ref[pl.ds(i, 1), :] = acc.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, x_ref.shape[0], receiver, 0)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def gossip_gather_pallas(
    idx: jax.Array,  # (n, k_max) int32 sender indices (receiver-side)
    wgt: jax.Array,  # (n, k_max) float32 mixing weights
    X: jax.Array,  # (n, D) client-stacked flat parameter bank
    block_d: int = 512,
    interpret: bool = False,
):
    n, D = X.shape
    k_max = idx.shape[1]
    # Mosaic slices single rows only out of 32-bit tiles (a bf16 tile packs
    # two rows per sublane).  Narrower banks are widened for the mix; that
    # is exact, since every row is widened to f32 before its multiply.
    Xw = X if X.dtype.itemsize == 4 else X.astype(jnp.float32)
    # D is not a reduction axis: the ragged last panel computes on padding
    # that the output write discards, so X is never copied to a padded
    # width.
    out = pl.pallas_call(
        functools.partial(_row_kernel, k_max=k_max),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(D, block_d),),
            in_specs=[pl.BlockSpec((n, block_d), lambda j, i_, w_: (0, j))],
            out_specs=pl.BlockSpec((n, block_d), lambda j, i_, w_: (0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, D), Xw.dtype),
        interpret=interpret,
        name="gossip_gather",
    )(idx.astype(jnp.int32).reshape(-1),
      wgt.astype(jnp.float32).reshape(-1), Xw)
    return out.astype(X.dtype)


def gossip_gather_xla(idx: jax.Array, wgt: jax.Array, X: jax.Array):
    """GSPMD *all-gather* executor for the same kernel body: the whole-bank
    single-block form, i.e. plain traced jnp with no loop/slice structure.

    Under a row-sharded bank the partitioner sees ``k_max`` ordinary row
    gathers and lowers them to one full all-gather of ``X`` followed by
    shard-local takes — O(n · D) received per device per mix, regardless
    of how sparse the neighbor lists are.  That is the baseline the
    dispatch rule (``repro.comm.plan.resolve_backend``) falls back to for
    dense operators and for sampled families when the halo executor is not
    forced; :func:`gossip_gather_halo` is the O(k · D) replacement that
    ships only the rows the plan says each shard reads.  The panel
    executor's ``fori_loop`` + ``dynamic_slice`` structure defeats the
    partitioner's analysis (and the interpret pallas_call grid cannot be
    partitioned at all), so sharded all-gather callers route here.  The
    slot accumulation order is the kernel's own, so results are bitwise
    identical to the other executors.
    """
    from repro.kernels.interpret import run_single_block

    return run_single_block(
        _kernel, [idx, wgt.astype(jnp.float32), X], [X.dtype]
    )


def _halo_accumulate(idx_s, wgt_s, x_s, halo, pos, me, m):
    """The kernel body's slot-by-slot f32 accumulation, per shard: slot l
    reads either the shard-local row or its halo slot (``pos`` maps
    (source shard, source-local offset) -> halo row; zero-weight slots may
    resolve to an arbitrary halo row — they contribute exactly 0.0).  The
    accumulation order is ``_kernel``'s own, so per shard the result
    matches the all-gather executor's float32 sequence."""
    k_max = idx_s.shape[1]
    src = idx_s // m
    off = idx_s % m
    acc = None
    for l in range(k_max):
        local = src[:, l] == me
        v_local = jnp.take(x_s, off[:, l], axis=0)
        v_halo = jnp.take(halo, pos[src[:, l], off[:, l]], axis=0)
        v = jnp.where(local[:, None], v_local, v_halo).astype(jnp.float32)
        term = wgt_s[:, l].astype(jnp.float32)[:, None] * v
        acc = term if acc is None else acc + term
    return acc.astype(x_s.dtype)


def gossip_gather_halo(idx: jax.Array, wgt: jax.Array, X: jax.Array, *,
                       mesh, axis: str, plan):
    """Halo-exchange executor: the same mix under ``shard_map``, shipping
    only the remote rows each shard's receivers actually read (the
    ``repro.comm.plan.CommPlan``) instead of all-gathering the bank.

    Static plans (ring / exponential / exponential-cycle) run one
    ``ppermute`` per :class:`~repro.comm.plan.ShiftLeg` — exact O(k) rows
    per shard, zero index traffic.  Dynamic plans (sampled families) run a
    fixed-capacity request/response ``all_to_all`` pair: each shard
    scatters the rows it needs into a per-source bitmap, ships the padded
    request lists, serves the gathers, and ships the payload back; a
    dropped / churned / delayed-away edge has weight 0 and requests
    nothing.  Either way the per-shard accumulation is ``_kernel``'s
    slot-by-slot f32 order, so the result matches the all-gather executor
    per shard.
    """
    s, m = plan.n_shards, plan.m
    if s == 1 or mesh is None or axis not in mesh.axis_names:
        return gossip_gather_xla(idx, wgt, X)

    if plan.static:

        def body(idx_s, wgt_s, x_s):
            me = jax.lax.axis_index(axis)
            bufs = []
            # pos[(src shard, src-local offset)] -> halo row; the extra
            # column m absorbs nothing here (static offsets are exact).
            pos = jnp.zeros((s, m + 1), jnp.int32)
            base = 0
            for leg in plan.legs:
                offs = jnp.asarray(leg.offsets, jnp.int32)
                payload = jnp.take(x_s, offs, axis=0)
                bufs.append(jax.lax.ppermute(
                    payload, axis,
                    [(p, (p + leg.delta) % s) for p in range(s)],
                ))
                # The rows just received came from shard me - delta.
                pos = pos.at[(me - leg.delta) % s, offs].set(
                    base + jnp.arange(offs.shape[0], dtype=jnp.int32)
                )
                base += len(leg.offsets)
            halo = (jnp.concatenate(bufs, axis=0) if bufs
                    else jnp.zeros((1, x_s.shape[1]), x_s.dtype))
            return _halo_accumulate(idx_s, wgt_s, x_s, halo, pos, me, m)

    else:
        H = plan.capacity

        def body(idx_s, wgt_s, x_s):
            me = jax.lax.axis_index(axis)
            src = idx_s // m
            off = idx_s % m
            remote = (wgt_s != 0.0) & (src != me)
            # Which of each source shard's m rows do my receivers read?
            need = jnp.zeros((s, m), jnp.int32).at[src, off].add(
                remote.astype(jnp.int32)) > 0
            # Fixed-shape dedup: row p = the (padded) offsets I request
            # from shard p; the fill value m marks an unused request slot.
            req = jax.vmap(
                lambda row: jnp.nonzero(row, size=H, fill_value=m)[0]
            )(need).astype(jnp.int32)
            req_in = jax.lax.all_to_all(req, axis, 0, 0, tiled=True)
            payload = jnp.take(
                x_s, jnp.clip(req_in, 0, m - 1).reshape(-1), axis=0
            ).reshape(s, H, x_s.shape[1])
            halo = jax.lax.all_to_all(payload, axis, 0, 0, tiled=True)
            # Reverse map: fill-value writes land in the throwaway column
            # m, real offsets get their flat halo row s*H-indexed.
            pos = jnp.zeros((s, m + 1), jnp.int32).at[
                jnp.arange(s, dtype=jnp.int32)[:, None], req
            ].set(jnp.arange(s * H, dtype=jnp.int32).reshape(s, H))
            return _halo_accumulate(
                idx_s, wgt_s, x_s, halo.reshape(s * H, -1), pos, me, m
            )

    spec = PartitionSpec(axis)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(idx, wgt, X)


@functools.partial(jax.jit, static_argnames=("panel",))
def gossip_gather_panels(
    idx: jax.Array, wgt: jax.Array, X: jax.Array, panel: int = 8192
):
    """CPU executor for the same kernel body: a ``fori_loop`` of
    ``(n, panel)`` column blocks, each run through ``run_single_block``.

    The whole-bank single-block form is the fast path when the gather
    reads a jit *parameter*, but composed after a producer (the local
    solver) XLA CPU materializes every per-slot gather into its own
    fresh (n, D) temp — measured ~5x slower than the gather's streaming
    floor on 2-core boxes, dominated by first-touch writes.  Blocking
    over D keeps every intermediate at ``(n, panel)`` (cache-resident,
    one reused buffer) and writes the output exactly once via in-place
    ``dynamic_update_slice``; per-element results are bitwise identical
    to the single-block form (the slot accumulation order is unchanged
    and D is not a reduction axis).  The final ragged panel is computed
    from the last ``panel`` columns — the overlap rewrites identical
    values — so no pad copy of ``X`` is ever made.
    """
    from repro.kernels.interpret import run_single_block

    n, D = X.shape
    wgt = wgt.astype(jnp.float32)

    def block(xp):
        return run_single_block(_kernel, [idx, wgt, xp], [X.dtype])

    if D <= panel:
        return block(X)

    def body(p, out):
        xp = jax.lax.dynamic_slice(X, (0, p * panel), (n, panel))
        return jax.lax.dynamic_update_slice(out, block(xp), (0, p * panel))

    out = jax.lax.fori_loop(0, D // panel, body, jnp.zeros_like(X))
    if D % panel:
        xp = jax.lax.dynamic_slice(X, (0, D - panel), (n, panel))
        out = jax.lax.dynamic_update_slice(out, block(xp), (0, D - panel))
    return out
