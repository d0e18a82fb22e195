"""Production mesh construction (required API: ``make_production_mesh``).

A FUNCTION, not a module constant — importing this module never touches jax
device state (device count is locked on first jax init; the dry-run must set
XLA_FLAGS before that).

Mesh layout (TPU v5e pods of 256 chips):
  single-pod:  (16, 16)        axes ('data', 'model')
  multi-pod:   (2, 16, 16)     axes ('pod', 'data', 'model')

'model' maps to the innermost ICI ring (highest-bandwidth collectives for TP),
'data' to the second ring (FSDP all-gathers / gradient reduce-scatters),
'pod' to the DCI/optical inter-pod links (data-parallel only: one gradient
all-reduce per step crosses pods).
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (CPU tests)."""
    devs = np.array(jax.devices()[: data * model]).reshape(data, model)
    return Mesh(devs, ("data", "model"))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(mesh.shape)


# Hardware constants for the roofline model (TPU v5e per chip).
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW_PER_LINK = 50e9        # bytes/s per link (~45-100 GB/s; spec midpoint)
HBM_BYTES = 16 * 1024**3      # 16 GiB
