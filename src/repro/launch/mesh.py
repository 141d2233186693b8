"""Production mesh construction. TPU v5e pod targets: 16×16 = 256 chips/pod
("data", "model"); multi-pod 2×16×16 = 512 chips ("pod", "data", "model").

A FUNCTION, not a module constant — importing this module must never touch
jax device state (the dry-run pins the device count before first jax init).
"""

from __future__ import annotations

import jax

# Published per-chip peaks (roofline denominators), keyed by
# ``jax.Device.device_kind``. Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect over four links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bw": 819e9,
                    "ici_link_bw": 50e9},
}


def peaks_for(device_kind: str) -> dict:
    """Peak rates of one chip of ``device_kind``; a device missing from
    ``PEAKS`` is an error, never another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with default Auto axes on every dimension."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         **kwargs)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def mesh_info(mesh) -> dict:
    return {"shape": [int(s) for s in mesh.devices.shape],
            "axes": list(mesh.axis_names)}
