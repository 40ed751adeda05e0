"""Jitted public wrappers for the Pallas kernels.

Off the TPU (``on_tpu()`` false: the CPU test suite) every wrapper defaults
to interpreted kernels — the kernel body runs as traced jnp on the host; on
a TPU backend the same call sites compile to Mosaic.  Nothing falls back
from Mosaic to the interpreter: ``chip_smoke.py`` checks that the compiled
round holds each kernel as a ``tpu_custom_call``.
"""
from __future__ import annotations

import jax

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_update import (
    fused_update_bank_pallas,
    fused_update_pallas,
)
from repro.kernels.gossip_gather import gossip_gather_pallas
from repro.kernels.gossip_matmul import gossip_matmul_pallas

__all__ = [
    "gossip_matmul",
    "gossip_gather",
    "gossip_mix",
    "gossip_mix_sparse",
    "use_sparse_gossip",
    "fused_update",
    "fused_update_bank",
    "flash_attention",
    "on_tpu",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# Below this many elements the per-call overhead of the interpret-mode
# kernel dominates on CPU and the plain einsum wins; on TPU the Mosaic
# kernel is always the right choice.  One threshold, one place.
_GOSSIP_KERNEL_MIN_ELEMS = 1 << 20

# Sparse-vs-dense representation dispatch: the O(n * k_max * D) gather
# wins once the neighbor lists are materially sparser than the dense
# matrix AND n is big enough that the O(n^2 * D) matmul is the round's
# dominant cost.  Below either bound the dense path stays — which pins
# the recorded golden configs (n <= 16) to the dense samplers bit-for-bit.
# The n floor is backend-aware: on TPU the Mosaic gather kernel wins from
# n=32, but on CPU the interpret-mode gather's per-row take overhead beats
# the heavily vectorized dense einsum until well past that — measured
# gossip-phase time at k_out=10 was 0.22x dense speed at n=32 and 0.78x
# at n=64 (round_bench scaling sweep), only crossing 1x around n=128.
# One rule, one place (the sparse twin of _GOSSIP_KERNEL_MIN_ELEMS).
_SPARSE_GOSSIP_MIN_CLIENTS_TPU = 32
_SPARSE_GOSSIP_MIN_CLIENTS_CPU = 128
_SPARSE_GOSSIP_MAX_DENSITY = 0.25


def use_sparse_gossip(n: int, k_max: int) -> bool:
    """THE density rule: neighbor-list gossip iff ``n`` is at least the
    backend's ``_SPARSE_GOSSIP_MIN_CLIENTS_*`` floor and ``k_max / n`` is
    at most ``_SPARSE_GOSSIP_MAX_DENSITY``.  Static shapes in, static bool
    out — callers decide the representation at trace time."""
    floor = (
        _SPARSE_GOSSIP_MIN_CLIENTS_TPU
        if on_tpu()
        else _SPARSE_GOSSIP_MIN_CLIENTS_CPU
    )
    return n >= floor and k_max <= _SPARSE_GOSSIP_MAX_DENSITY * n


def _is_halo(use_kernel) -> bool:
    """Is this ``use_kernel`` a ``repro.comm.plan.HaloBackend``?  Lazy
    import: the kernels layer must not depend on the comm layer at module
    load (comm builds on topology, which the kernels never import)."""
    if not isinstance(use_kernel, tuple):
        return False
    from repro.comm.plan import HaloBackend

    return isinstance(use_kernel, HaloBackend)


def gossip_mix(P, M, use_kernel: bool | None = None):
    """One mixing matmul ``M' = P @ M`` with centralized backend selection.

    Every gossip call site (flat bank, per-leaf pytree, pod replicas)
    routes through here.  ``use_kernel=None`` (the default everywhere)
    resolves automatically: the Pallas kernel on TPU, and on CPU only when
    ``M`` is large enough to amortize interpret-mode overhead — instead of
    each call site hard-coding its own boolean.  ``use_kernel="xla"``
    forces the plain-XLA einsum regardless of size: under GSPMD the
    partitioner must see ordinary HLO (no interpret-mode loop/slice
    structure) to shard the mixing correctly.  A halo backend degrades to
    the einsum too — a dense operator has no sparse row set to ship.
    """
    import jax.numpy as jnp

    if use_kernel is None:
        use_kernel = on_tpu() or M.size >= _GOSSIP_KERNEL_MIN_ELEMS
    elif use_kernel == "xla" or _is_halo(use_kernel):
        use_kernel = False
    if use_kernel:
        return gossip_matmul(P.astype(jnp.float32), M)
    out = jnp.einsum(
        "ij,jd->id", P, M.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    return out.astype(M.dtype)


def gossip_mix_sparse(idx, wgt, M, use_kernel: bool | None = None):
    """Sparse mixing ``M'[i] = sum_l wgt[i,l] * M[idx[i,l]]`` — the
    neighbor-list twin of :func:`gossip_mix`, same centralized backend
    rule: the Pallas gather kernel on TPU, on CPU only when ``M`` is big
    enough to amortize it (the kernel's slot-loop also avoids the
    reference path's ``(n, k_max, D)`` gather temporary, exactly when that
    temporary would hurt).  ``use_kernel="xla"`` forces
    :func:`~repro.kernels.gossip_gather.gossip_gather_xla` — the kernel
    body as plain traced jnp, same accumulation order, no loop/slice
    structure — so the GSPMD partitioner can turn the row gather into one
    full-bank all-gather.  A :class:`repro.comm.plan.HaloBackend` routes
    to :func:`~repro.kernels.gossip_gather.gossip_gather_halo` instead:
    the ``shard_map`` halo exchange shipping only the plan's remote rows
    per shard."""
    import jax.numpy as jnp

    if use_kernel is None:
        use_kernel = on_tpu() or M.size >= _GOSSIP_KERNEL_MIN_ELEMS
    elif use_kernel == "xla":
        from repro.kernels.gossip_gather import gossip_gather_xla

        return gossip_gather_xla(idx, wgt, M)
    elif _is_halo(use_kernel):
        from repro.kernels.gossip_gather import gossip_gather_halo

        return gossip_gather_halo(
            idx, wgt, M, mesh=use_kernel.mesh, axis=use_kernel.axis,
            plan=use_kernel.plan,
        )
    if use_kernel:
        return gossip_gather(idx, wgt.astype(jnp.float32), M)
    from repro.kernels.ref import gossip_gather_ref

    return gossip_gather_ref(idx, wgt, M)


def gossip_matmul(P, X, **kw):
    interpret = kw.setdefault("interpret", not on_tpu())
    if interpret:
        # Off-TPU, interpret mode executes the grid as a serial loop of
        # dynamic slices — per-step overhead dominates — and there are no
        # MXU tile-alignment constraints.  Collapse to a single pad-free
        # grid step covering the whole (n, D) bank.
        kw.setdefault("block_n", X.shape[0])
        kw.setdefault("block_d", X.shape[1])
    return gossip_matmul_pallas(P, X, **kw)


def gossip_gather(idx, wgt, X, **kw):
    interpret = kw.pop("interpret", not on_tpu())
    if interpret and "block_d" not in kw:
        # Off-TPU the vectorized jnp body (the Mosaic body's slot order)
        # runs as a fori_loop of (n, panel) column blocks: composed after
        # the local solver, the whole-bank gather makes XLA CPU
        # materialize one fresh (n, D) temp per neighbor slot (first-touch
        # writes dominate); panel blocking keeps every intermediate
        # cache-resident and bitwise identical.
        from repro.kernels.gossip_gather import gossip_gather_panels

        return gossip_gather_panels(idx, wgt, X, **kw)
    return gossip_gather_pallas(idx, wgt, X, interpret=interpret, **kw)


def fused_update(x, v, g, alpha, eta, w, **kw):
    interpret = kw.setdefault("interpret", not on_tpu())
    if interpret:
        kw.setdefault("block", x.shape[0])
    return fused_update_pallas(x, v, g, alpha, eta, w, **kw)


def fused_update_bank(X, V, G, alpha, eta, w, **kw):
    """Fused momentum/descent/de-bias over the whole (n, D) flat bank."""
    interpret = kw.setdefault("interpret", not on_tpu())
    if interpret:
        kw.setdefault("block_n", X.shape[0])
        kw.setdefault("block_d", X.shape[1])
    return fused_update_bank_pallas(X, V, G, alpha, eta, w, **kw)


def flash_attention(q, k, v, causal=True, window=0, **kw):
    kw.setdefault("interpret", not on_tpu())
    return flash_attention_pallas(q, k, v, causal=causal, window=window, **kw)
