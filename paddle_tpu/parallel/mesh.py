"""Device mesh management.

The TPU-native replacement of the reference's device lists + NCCLContextMap
(``platform/nccl_helper.h:86``): a named ``jax.sharding.Mesh`` whose axes
carry the parallelism meaning (``data``, ``model``, ...). Collectives are
inserted by XLA/GSPMD from sharding annotations; there is no communicator
bootstrap — multi-host joins the same mesh after ``init_distributed``.
"""

from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax
import numpy as np
from jax.sharding import Mesh

__all__ = ["create_mesh", "get_mesh", "mesh_guard",
           "valid_sharding"]

_current_mesh: Optional[Mesh] = None


def create_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    """create_mesh({'data': 4, 'model': 2}) → 2D mesh over the first 8 devices.

    An axis size of -1 means "all remaining devices".
    """
    devices = list(devices if devices is not None else jax.devices())
    names = list(axes)
    sizes = list(axes.values())
    n_fixed = int(np.prod([s for s in sizes if s != -1]))
    for i, s in enumerate(sizes):
        if s == -1:
            sizes[i] = len(devices) // n_fixed
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError("mesh %s needs %d devices, have %d" % (axes, total, len(devices)))
    grid = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(grid, axis_names=tuple(names))


def get_mesh() -> Optional[Mesh]:
    return _current_mesh


def valid_sharding(spec, mesh) -> bool:
    """A Variable.sharding annotation applies iff every named axis exists on
    this mesh — the one predicate all sharding consumers share (the
    executor's placement, the init ops, the sparse optimizer ops)."""
    return spec is not None and all(
        a is None or a in mesh.axis_names for a in spec)


@contextlib.contextmanager
def mesh_guard(mesh: Mesh):
    global _current_mesh
    prev, _current_mesh = _current_mesh, mesh
    try:
        yield mesh
    finally:
        _current_mesh = prev
