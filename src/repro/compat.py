"""The jax mesh / shard_map spellings, in one module.

The repo runs on one jax installation (0.9), where ``jax.shard_map`` takes
``check_vma`` and ``jax.make_mesh`` takes ``axis_types``.  Everything
in-repo (``launch/mesh.py``, ``kernels/ops.py``, examples, the subprocess
scripts in ``tests/test_substrate.py``) goes through these wrappers, so a
future API move changes this module only (rules CB001-CB003 of
``python -m repro.analysis`` keep the spellings here).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def auto_axis_types(n: int):
    """``(AxisType.Auto,) * n``."""
    return (AxisType.Auto,) * n


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              axis_types=None, devices=None):
    """``jax.make_mesh``; ``axis_types``/``devices`` pass through when given."""
    kwargs = {}
    if devices is not None:
        kwargs["devices"] = devices
    if axis_types is not None:
        kwargs["axis_types"] = axis_types
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names), **kwargs)


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map``; ``check`` maps to ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)
