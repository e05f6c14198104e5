"""Mesh construction (FUNCTIONS — importing this module never touches jax
device state).

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; ``pod`` composes with
``data`` as the batch/FSDP axis (DESIGN.md §5) — the multi-pod dry-run
proves this axis shards.
One host:   `make_host_mesh` lays whatever devices the process sees out as
(data, model) — (1, 1) on one chip, (2, 2) on a four-chip host.

Every axis is ``Auto``: the sharding code annotates with
``with_sharding_constraint`` and leaves propagation to XLA, which
``Explicit`` axes (`jax.make_mesh`'s default since JAX 0.7) refuse.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """`jax.make_mesh` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(devices=None):
    """(data, model) mesh over this process's devices (default: all of
    ``jax.devices()``): ``model`` is the largest divisor of the device
    count not above its square root, so 1 -> (1, 1), 4 -> (2, 2),
    8 -> (4, 2)."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    model = max(d for d in range(1, math.isqrt(n) + 1) if n % d == 0)
    return make_mesh((n // model, model), ("data", "model"), devices=devices)


# TPU v5e hardware constants (roofline denominators; EXPERIMENTS.md §Roofline)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (~)
HBM_BYTES = 16 * 1024 ** 3      # 16 GiB per chip
