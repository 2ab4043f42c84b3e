"""Production mesh definition (assigned): 16×16 single-pod, 2×16×16 multi-pod.

A FUNCTION, not a module constant — importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(shape=None, axes=None, devices=None):
    """Small mesh over ``devices`` (default: every device this process
    sees) — one host's chips, or the CPU devices of a test."""
    devices = jax.devices() if devices is None else list(devices)
    if shape is None:
        shape, axes = (len(devices), 1), ("data", "model")
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


# TPU v5e hardware model for the roofline (assigned constants).
HW = {
    "name": "tpu-v5e",
    "peak_flops_bf16": 197e12,     # per chip
    "hbm_bw": 819e9,               # bytes/s per chip
    "ici_bw": 50e9,                # bytes/s per link (~per direction)
    "chips_per_pod": 256,
}
