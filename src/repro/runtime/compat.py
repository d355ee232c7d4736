"""Mesh construction and `shard_map` for jax 0.9.0, the one version supported.

This module is the ONLY place in the repo that calls JAX's mesh and
shard_map constructors.  Everything else goes through
`repro.runtime.dist`, which re-exports the entry points defined here.

Every mesh the repo builds has `Auto` axis types, whether it spans all
devices or a caller-given subset (a fleet replica's pool): `jax.make_mesh`
alone would give `Explicit` axes, so the single service and the fleet
would run on different kinds of mesh.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import AbstractMesh, AxisType, Mesh


def shard_map(
    f: Callable,
    mesh,
    in_specs,
    out_specs,
    *,
    check_vma: bool = True,
    axis_names: Optional[frozenset] = None,
):
    """`jax.shard_map`, manual over `axis_names` (default: every mesh
    axis), with the varying-manual-axes check `check_vma`."""
    kwargs = {"check_vma": check_vma}
    if axis_names is not None and frozenset(axis_names) != frozenset(mesh.axis_names):
        kwargs["axis_names"] = frozenset(axis_names)
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, **kwargs
    )


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Auto-typed mesh from an int shape tuple, on `devices` (default: all
    of them, in the contiguous order `jax.make_mesh` picks)."""
    shape = tuple(int(s) for s in axis_shapes)
    names = tuple(axis_names)
    if len(shape) != len(names):
        raise ValueError(f"shape {shape} vs axis names {names}")
    auto = (AxisType.Auto,) * len(shape)
    if devices is None:
        return jax.make_mesh(shape, names, axis_types=auto)
    devs = np.asarray(devices)
    need = int(np.prod(shape))
    if devs.size < need:
        raise ValueError(f"mesh {names}={shape} needs {need} devices, have {devs.size}")
    return Mesh(devs.reshape(-1)[:need].reshape(shape), names, axis_types=auto)


def abstract_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str]):
    """AbstractMesh (shape-only, no devices) from an int shape tuple."""
    shape = tuple(int(s) for s in axis_shapes)
    names = tuple(axis_names)
    return AbstractMesh(shape, names, axis_types=(AxisType.Auto,) * len(shape))


def axis_sizes(mesh) -> Dict[str, int]:
    """Axis-name -> size for Mesh and AbstractMesh."""
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}
