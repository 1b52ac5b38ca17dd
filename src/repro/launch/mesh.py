"""Production mesh definitions.

make_production_mesh is a FUNCTION (importing this module never touches jax
device state). Dry-run callers set XLA_FLAGS host-device-count before any
jax import; real launches get the same meshes over real TPU slices.

Both meshes use Auto axes: the sharding rules (dist/sharding.py) are
placement hints for the SPMD partitioner. JAX's own default, Explicit
axes, would make every op's output sharding part of its type.

Axes:
  pod   — data parallelism across pods (DCN); gradient all-reduce only
  data  — data parallelism within a pod (ICI)
  model — tensor/expert parallelism (heads / d_ff / vocab / experts)
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model: int = 1, devices=None):
    """(data, model) mesh over `devices` (default: every visible device)."""
    devices = jax.devices() if devices is None else list(devices)
    n = len(devices)
    model = max(1, min(model, n))
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2), devices=devices)


def kernels_for_mesh(qcfg, mesh):
    """Resolve the "auto" kernel dispatch for a program over `mesh`.

    XLA's SPMD partitioner cannot split a Mosaic (Pallas TPU) kernel: it
    refuses to lower one into a program over several devices unless a
    shard_map wraps it, and the fused paths have none yet. So on a mesh of
    more than one device "auto" means the jnp composition, which partitions.
    """
    if mesh.size == 1:
        return qcfg
    kw = {f: "off" for f in ("fused_matmul", "fused_attention")
          if getattr(qcfg, f) == "auto"}
    return qcfg.replace(**kw) if kw else qcfg


def _auto(n: int) -> tuple:
    return (jax.sharding.AxisType.Auto,) * n


def batch_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))
