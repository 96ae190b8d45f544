"""Mesh construction and the per-chip hardware peaks.

FUNCTIONS, not module constants: importing this module never touches
jax device state (required so smoke tests / benches see 1 CPU device while
the dry-run sees 512 placeholder devices).

Single pod: 16x16 = 256 chips ("data", "model").
Multi-pod:  2x16x16 = 512 chips ("pod", "data", "model") — DP across the
pod axis (cross-pod traffic is gradient all-reduce only).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: shardings propagate
    through GSPMD as the Sharder expects (the bare call defaults to
    ``Explicit`` axes, under which unannotated gathers and einsums over
    sharded operands raise ``ShardingTypeError``)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


#: jax ``device_kind`` of the chip the production mesh is built from
TARGET_KIND = "TPU v5 lite"

#: Published per-chip peaks, keyed by jax ``device_kind``.
#: TPU v5e — Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16,
#: 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect over
#: four links (50 GB/s per link).
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}


def device_peaks(kind: str | None = None) -> dict:
    """Peaks of `kind` (default: this process's first device).  A device
    that is not in the table is an error, never a borrowed default."""
    if kind is None:
        kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r} (known: "
            f"{sorted(DEVICE_PEAKS)}); add them to launch/mesh.DEVICE_PEAKS "
            "with their source, or pass peaks explicitly"
        ) from None
