"""Production mesh construction (TPU v5e).

Single pod: 16x16 = 256 chips, axes (data, model).
Multi-pod:  2x16x16 = 512 chips, axes (pod, data, model) — the "pod" axis is
the DFL client axis: each pod holds one push-sum replica.

Defined as functions (not module constants) so importing never touches jax
device state; the dry-run forces 512 host devices *before* calling these.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_clients_mesh",
           "HARDWARE"]


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))

# TPU v5e constants used by the roofline model.
HARDWARE = {
    "chip": "tpu-v5e",
    "peak_flops_bf16": 197e12,  # FLOP/s per chip
    "hbm_bw": 819e9,  # B/s per chip
    "ici_bw": 50e9,  # B/s per link (~50 GB/s)
    "hbm_bytes": 16 * 2**30,
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices exist (tests/examples)."""
    return _make_mesh(shape, axes)


def make_clients_mesh(n_devices: int | None = None):
    """1-D mesh whose single ``clients`` axis row-shards the flat bank.

    ``n_devices`` defaults to every visible device (on CPU CI that is
    whatever ``--xla_force_host_platform_device_count`` forced).  The bank
    row count must be divisible by the axis size — ``make_program``
    validates that when handed this mesh.
    """
    if n_devices is None:
        n_devices = jax.device_count()
    return _make_mesh((n_devices,), ("clients",))
