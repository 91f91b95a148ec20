"""Production mesh definition (assignment-mandated shapes).

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (the dry-run must set XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the serving and training
    code steers GSPMD with ``with_sharding_constraint``, which only accepts
    Auto axes (make_mesh defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_host_mesh(data: int = 1, model: int = 1):
    """``(data, model)`` mesh over the host's devices.

    Both axes are validated (>= 1), and a request for more devices than
    the host has is refused: a mesh smaller than asked for would run every
    shard of a "4-way" layout on fewer chips and report it as four.
    """
    if data < 1 or model < 1:
        raise ValueError(
            f"mesh axes must be >= 1, got (data={data}, model={model})")
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(
            f"make_host_mesh: requested (data={data}, model={model}) "
            f"needs {data * model} devices but the host has {n}. "
            f"Force more CPU devices with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N.")
    return _auto_mesh((data, model), ("data", "model"),
                      devices=jax.devices()[:data * model])


def make_serving_mesh(model: int = 1, data: int = 1):
    """Serving mesh: ('data', 'model') — a real 2-D request (DESIGN.md §17).

    ``model`` is the tensor-parallel width of one replica (replicated
    small batch, sharded packed weights + kv-head-sharded caches —
    serve/shard.py; the ``--model-parallel`` CLI knob); ``data`` is the
    replica-fleet axis: serve/router.Router carves the mesh into ``data``
    replica groups of ``model`` devices each (``replica_meshes``) and
    load-balances requests across them (the ``--data-parallel`` knob).
    Requests beyond the host's device count are refused, as in
    make_host_mesh.  Testable on CPU via
    XLA_FLAGS=--xla_force_host_platform_device_count=8 for (data=2,
    model=2) and beyond.
    """
    if model < 1:
        raise ValueError(f"model parallelism must be >= 1, got {model}")
    if data < 1:
        raise ValueError(f"data parallelism must be >= 1, got {data}")
    return make_host_mesh(data=data, model=model)


def replica_meshes(mesh):
    """Carve a ('data', 'model') mesh into per-replica (1, model) groups.

    Each replica group is a standalone Mesh over one data-row's devices —
    the serving engine's ShardPlan (tensor-parallel over 'model') applies
    to it unchanged, and placing a replica's params/caches onto its group
    is what makes the fleet data-parallel: replicas own disjoint devices.
    """
    if tuple(mesh.axis_names) != ("data", "model"):
        raise ValueError(
            f"expected a ('data', 'model') serving mesh, got axes "
            f"{tuple(mesh.axis_names)}")
    dev = mesh.devices
    return [jax.sharding.Mesh(dev[i:i + 1], ("data", "model"),
                              axis_types=(AxisType.Auto,) * 2)
            for i in range(dev.shape[0])]
