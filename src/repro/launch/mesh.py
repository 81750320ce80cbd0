"""Production meshes.

Defined as functions (never module-level constants) so importing this
module never touches jax device state.  The single-pod mesh is one
16x16 v5e pod (256 chips); the multi-pod mesh adds a leading "pod" axis
(2 pods = 512 chips) — the paper's rack analogue (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_repair_mesh(r: int, w: int, devices):
    """``("pod", "node")`` mesh for the layered-repair SPMD program of a
    code with r racks of w nodes, over exactly ``devices``.

    The device count picks the layout: r*w devices hold one node each,
    r devices one rack each, and one device the whole stripe.
    """
    n = len(devices)
    shape = {r * w: (r, w), r: (r, 1), 1: (1, 1)}.get(n)
    if shape is None:
        raise ValueError(
            f"{n} devices fit no layout of {r} racks x {w} nodes: "
            f"give {r * w}, {r} or 1")
    return jax.sharding.Mesh(np.asarray(devices).reshape(shape), ("pod", "node"))


# Hardware constants (TPU v5e) for the roofline terms.
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link
