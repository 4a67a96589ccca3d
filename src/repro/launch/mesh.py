"""Production meshes + the abstract mesh descriptor plan transfer keys on.

Meshes are defined as functions (never module-level constants) so
importing this module never touches jax device state.  Single pod:
16x16 = 256 chips (data, model); multi-pod: 2x16x16 = 512 chips with a
leading ``pod`` axis (DCN-connected in deployment) that joins the
FSDP/data sharding — the same rules scale to any pod count.

:class:`MeshSpec` is the device-free description of a mesh (DP x TP x pod
extents).  DVFS plan transfer (:mod:`repro.parallel.plan_transfer`) only
needs the extents — the per-device workload is ``global_batch / dp`` with
kernels sharded ``tp`` ways — so planning for a 256-chip pod never has to
instantiate 256 devices.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
from jax.sharding import AxisType


@dataclass(frozen=True)
class MeshSpec:
    """Abstract (device-free) mesh extents: data / model / pod axes."""

    dp: int = 1       # data-parallel extent (the "data" axis)
    tp: int = 1       # tensor/model-parallel extent (the "model" axis)
    pod: int = 1      # pod (DCN) extent; joins the data sharding

    def __post_init__(self):
        if min(self.dp, self.tp, self.pod) < 1:
            raise ValueError(f"mesh extents must be >= 1, got {self}")

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp * self.pod

    @property
    def data_extent(self) -> int:
        """Total data-sharding ways (pod axis joins the data axis)."""
        return self.dp * self.pod

    @classmethod
    def from_mesh(cls, mesh) -> "MeshSpec":
        """Extract the extents of a concrete ``jax`` mesh."""
        shape = dict(mesh.shape)
        return cls(dp=int(shape.get("data", 1)),
                   tp=int(shape.get("model", 1)),
                   pod=int(shape.get("pod", 1)))

    def describe(self) -> str:
        tag = f"dp{self.data_extent}_tp{self.tp}"
        return tag if self.pod == 1 else f"{tag}_pod{self.pod}"


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over host devices for tests/examples."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
