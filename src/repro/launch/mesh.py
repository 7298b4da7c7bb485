"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the pod axis is pure
data parallelism over DCN (gradient reduce only — DESIGN.md section 5).

Defined as functions (never module-level constants) so importing this module
never touches jax device state. ``elastic_mesh`` re-factorises a degraded
device count after failures — the paper's virtual-node treatment applied to
the mesh itself (runbook in README)."""

from __future__ import annotations


import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "elastic_mesh", "mesh_axis_sizes"]


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, ep: int | None = None):
    """ep: carve a dedicated expert axis out of the data axis (EP meshes for
    MoE archs whose expert count doesn't divide the model axis; §Perf)."""
    if ep:
        per_pod_data = 256 // (ep * 16)
        if per_pod_data * ep * 16 != 256:
            raise ValueError(f"ep={ep} doesn't factor a 256-chip pod")
        if multi_pod:
            return _mk((2, ep, per_pod_data, 16),
                       ("pod", "expert", "data", "model"))
        return _mk((ep, per_pod_data, 16), ("expert", "data", "model"))
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def elastic_shape(n_devices: int, model_parallel: int = 16
                  ) -> tuple[int, int]:
    """(data, model) mesh shape covering <= n_devices after failures.

    Keeps the model axis fixed (TP degree is a property of the sharded
    weights) and shrinks the data axis — surviving hosts reload the
    checkpoint under the new mesh and PSTS rebalances the input work."""
    model = model_parallel
    while model > 1 and n_devices < model:
        model //= 2
    data = max(n_devices // model, 1)
    return data, model


def elastic_mesh(n_devices: int, model_parallel: int = 16):
    return _mk(elastic_shape(n_devices, model_parallel), ("data", "model"))


def mesh_axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
