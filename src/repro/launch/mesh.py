"""Production mesh builders.

Single pod: (16, 16) over ("data", "model") — 256 TPU v5e chips.
Multi-pod:  (2, 16, 16) over ("pod", "data", "model") — 512 chips.

Built as functions so importing this module never touches jax device
state; ``dryrun.py`` sets XLA_FLAGS for 512 host devices before any jax
import. The ``pod`` axis is pure data parallelism and doubles as the
federated *silo* axis (DESIGN.md §3).

Every mesh is built with ``AxisType.Auto`` axes: the model code places
arrays with ``NamedSharding``s and lets GSPMD propagate the rest. Since
jax 0.9 ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the
embedding gather of a sharded table raises ``ShardingTypeError``.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes (see module docstring)."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1x1 mesh on the local device — used by CPU tests for the shard_map
    code paths."""
    return make_mesh((1, 1), ("data", "model"))


MESH_NAMES = ("none", "host", "production")


def resolve_mesh(name):
    """Mesh named by a config/CLI string: ``None``/"none" -> no mesh,
    "host" -> 1x1 CPU-test mesh, "production" -> single-pod 16x16."""
    if name is None or name == "none":
        return None
    if name == "host":
        return make_host_mesh()
    if name == "production":
        return make_production_mesh()
    raise ValueError(f"unknown mesh {name!r}; known: {MESH_NAMES}")


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_axis(mesh) -> str:
    return "model"
