"""Logical-axis → mesh-axis resolution (params & activations).

Models declare *logical* axes ("heads", "mlp", "embed", "batch", ...); this
module owns the mapping onto the production mesh ("data", "model"[, "pod"]).
A context manager activates a mesh + rule set; without one everything is a
no-op so the same model code runs on a laptop CPU.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.pdefs import PDef

__all__ = [
    "MODEL_AXES",
    "FSDP_AXES",
    "use_mesh",
    "active_mesh",
    "constrain",
    "in_manual_region",
    "spec_for",
    "sharding_for",
    "bank_row_pins",
]

# Logical axes eligible for tensor/expert parallelism, in priority order —
# the *first* divisible dim of a param gets the "model" mesh axis.
MODEL_AXES = ("expert", "vocab", "heads", "kv_heads", "mlp", "head_dim", "ssm_inner")
# Logical axes eligible for FSDP-style sharding over "data".
FSDP_AXES = ("embed", "ffpar", "frontend", "rank")
# Activation logical names handled by `constrain`.
ACT_RULES = {
    "batch": "data",
    "expert": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "head_dim": "head_dim_fallback",  # only used when heads were replicated
    "ssm_inner": "model",
    "seq": None,
    "embed": None,
}

_STATE: list = []  # stack of (mesh, fsdp: bool, head_dim_fallback: bool)


@contextlib.contextmanager
def use_mesh(mesh: Mesh, fsdp: bool = True):
    _STATE.append((mesh, fsdp))
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _STATE.pop()


def active_mesh() -> Optional[Mesh]:
    return _STATE[-1][0] if _STATE else None


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 0


def spec_for(pdef: PDef, mesh: Mesh, fsdp: bool = True,
             model_axes: tuple = None) -> P:
    """Resolve a parameter PDef to a PartitionSpec.

    At most one dim is sharded over "model" (first divisible logical axis in
    ``model_axes`` priority) and, when ``fsdp``, one over "data".
    """
    model_axes = MODEL_AXES if model_axes is None else model_axes
    model_n = _axis_size(mesh, "model")
    data_n = _axis_size(mesh, "data")
    spec: list = [None] * len(pdef.shape)

    def place(mesh_axis, mesh_n, candidates):
        if not mesh_n or mesh_axis in spec:
            return
        for logical in candidates:
            for i, (dim, name) in enumerate(zip(pdef.shape, pdef.axes)):
                if name == logical and spec[i] is None and dim % mesh_n == 0:
                    spec[i] = mesh_axis
                    return

    place("model", model_n, model_axes)
    # caches/activations: batch rides on "data" (takes priority over FSDP)
    place("data", data_n, ("batch",))
    if fsdp:
        place("data", data_n, FSDP_AXES)
    # long-context caches with unshardable batch: shard the sequence dim
    place("data", data_n, ("seq",))
    return P(*spec)


def sharding_for(pdef: PDef, mesh: Mesh = None, fsdp: bool = None):
    if mesh is None:
        if not _STATE:
            return None
        mesh, fsdp_active = _STATE[-1]
        fsdp = fsdp_active if fsdp is None else fsdp
    return NamedSharding(mesh, spec_for(pdef, mesh, True if fsdp is None else fsdp))


def bank_row_pins(mesh: Optional[Mesh], axis: str):
    """Row-sharding constraints for a flat client bank: ``(pin, pin_link)``.

    ``pin(x, lead=0)`` asserts that dim ``lead`` of ``x`` (the client-row
    dim) lives on mesh axis ``axis``, all other dims replicated — the
    GSPMD partitioner will otherwise happily rematerialize the bank
    replicated around ``ravel`` reshapes and concats, silently turning the
    sharded round into n copies of the single-device one.  ``pin_link``
    pins a LinkState carry: the ``(B, n, D)`` in-flight payload buffer and
    the ``(n, D)`` last-broadcast cache on their client dims; the small
    ``(B, n)`` mass buffer and the PRNG key are left to the partitioner.

    With ``mesh`` ``None`` (or the axis absent) both functions are
    identity, so unsharded callers compose through them bitwise unchanged.
    """
    if mesh is None or axis not in mesh.axis_names:
        return (lambda x, lead=0: x), (lambda link: link)

    def pin(x, lead: int = 0):
        spec = [None] * x.ndim
        spec[lead] = axis
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec))
        )

    def pin_link(link):
        if not link:  # the empty-carry () sentinel passes through
            return link
        upd = {}
        if not isinstance(link.bufx, tuple):
            upd["bufx"] = pin(link.bufx, lead=1)
        if not isinstance(link.last, tuple):
            upd["last"] = pin(link.last)
        return link._replace(**upd) if upd else link

    return pin, pin_link


def in_manual_region(mesh: Optional[Mesh] = None) -> bool:
    """Is the current trace inside a ``shard_map`` manual region over any
    axis of ``mesh`` (the active mesh when ``None``)?

    Inside such a region values are *per-shard* and GSPMD sharding
    constraints do not apply — ``with_sharding_constraint`` would raise.
    The probe is ``jax.lax.axis_index``: a mesh axis name is bound as a
    collective axis exactly inside the manual region (a plain jit, and
    ``vmap(spmd_axis_name=...)``, leave it unbound — constraints there are
    valid and wanted).
    """
    if mesh is None:
        mesh = active_mesh()
    if mesh is None:
        return False
    for name in mesh.axis_names:
        try:
            jax.lax.axis_index(name)
        except NameError:
            continue
        return True
    return False


def constrain(x, logical: tuple):
    """Activation sharding constraint by logical names (no-op without mesh).

    Inside a ``shard_map`` manual region (the halo gossip executor, or any
    model code a caller maps manually) the value is already per-shard and
    the constraint is explicitly skipped — detected by
    :func:`in_manual_region`, not by swallowing errors, so a genuinely
    malformed constraint (bad axis name, rank mismatch) still raises.
    """
    if not _STATE:
        return x
    mesh, _ = _STATE[-1]
    spec: list = [None] * x.ndim
    for i, name in enumerate(logical):
        if name is None:
            continue
        mesh_axis = ACT_RULES.get(name)
        if mesh_axis in (None, "head_dim_fallback"):
            continue
        n = _axis_size(mesh, mesh_axis)
        if n and x.shape[i] % n == 0 and mesh_axis not in spec:
            spec[i] = mesh_axis
    if in_manual_region(mesh):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))
