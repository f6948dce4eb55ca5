"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before any jax
device initialization.

Two mesh families:

- ``make_production_mesh`` — the serving/launch mesh: TPU v5e pods. Single
  pod = 256 chips as (data=16, model=16); multi-pod = 2 pods = 512 chips as
  (pod=2, data=16, model=16). Hardware constants for the roofline are in
  repro/utils/hlo.py.
- ``make_cohort_mesh`` — the FL-engine mesh: a leading ``cohort`` axis over
  which the CohortBank's slot dimension (and the round's flat participant
  rows) shard, so independent cohorts train on their own devices
  (ARCHITECTURE.md §④). An optional trailing ``model`` axis applies the
  ``tp`` policies of launch/sharding.py *within* a slot.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes. From jax 0.9 ``make_mesh``
    defaults to ``Explicit`` axes (sharding in types), under which the
    bank's slot scatter (``a.at[ii].set(a[ps])``) on a slot-sharded array
    raises ``ShardingTypeError``; the engine relies on the compiler to
    partition such ops, which is what ``Auto`` axes do."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(shape), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_cohort_mesh(n_shards: int, *, model: int = 1, devices=None):
    """Mesh with a leading ``cohort`` axis of size ``n_shards``.

    model > 1 adds a trailing ``model`` axis (tensor parallelism inside a
    cohort slot); n_shards * model devices are consumed in order. Built on
    demand (never at import) so dry-runs can set XLA_FLAGS first.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    need = n_shards * model
    if need > len(devices):
        raise ValueError(
            f"cohort mesh needs {need} devices ({n_shards} cohort x {model} "
            f"model), only {len(devices)} available"
        )
    if model > 1:
        return _auto_mesh(
            (n_shards, model), ("cohort", "model"), devices=devices[:need]
        )
    return _auto_mesh((n_shards,), ("cohort",), devices=devices[:need])


def cohort_size(mesh) -> int:
    """Size of the ``cohort`` axis (1 when the mesh has none)."""
    return mesh.shape["cohort"] if "cohort" in mesh.axis_names else 1


def data_axes(mesh) -> tuple:
    """The axes the batch/client dimension shards over."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def data_size(mesh) -> int:
    size = 1
    for a in data_axes(mesh):
        if a in mesh.axis_names:
            size *= mesh.shape[a]
    return size


def model_size(mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
