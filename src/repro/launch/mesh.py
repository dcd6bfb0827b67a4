"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — critical because the dry-run
process forces 512 host devices while every other process sees 1 CPU.

Axis semantics:
  pod    — pipeline/replica axis across pods (multi-pod only)
  data   — batch/FSDP axis (DP replicas = AMOEBA "number of SMs")
  model  — tensor/expert-parallel axis (per-group width = "SM size")

AMOEBA plans refactor (data x model) at a fixed chip count:
fused = model x2 / data /2 (scale-up), scale_out = the inverse.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

from repro.core.fusion import MeshPlan


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes, under which
    # with_sharding_constraint (shardctx.hint) is an assertion instead of
    # a hint; the model code is written for GSPMD-propagated Auto axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_plan_mesh(plan: MeshPlan):
    """Mesh for a named AMOEBA plan over the same chips."""
    return _auto_mesh(plan.shape, plan.axes)


def single_pod_plan(name: str = "base") -> MeshPlan:
    base = MeshPlan("base", data=16, model=16)
    if name == "base":
        return base
    from repro.core.fusion import plan_family
    return plan_family(base)[name]


def multi_pod_plan() -> MeshPlan:
    return MeshPlan("multi", data=16, model=16, pod=2)
