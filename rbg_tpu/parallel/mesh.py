"""Device mesh construction.

Axis convention (the framework's logical parallelism dims):

* ``dp`` — data parallel (batch fan-out; maps to the reference's
  ``role.replicas`` semantics at the orchestration layer,
  ``api/workloads/v1alpha2/rolebasedgroup_types.go:219``)
* ``tp`` — tensor parallel inside one ICI domain (reference analog:
  ``leaderWorkerPattern.size`` node groups, ``rolebasedgroup_types.go:335``)
* ``sp`` — sequence/context parallel (ring attention over ICI)
* ``ep`` — expert parallel (MoE experts split across devices)

Meshes are built so the innermost (fastest-varying) axis is ``tp`` — on real
TPU slices the default device order makes neighboring devices ICI-adjacent, so
tp collectives ride ICI while dp/sp/ep ride the outer topology.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "sp", "ep", "tp")


def make_mesh(
    dp: int = 1,
    tp: int = 1,
    sp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a ``Mesh`` with axes (dp, sp, ep, tp), tp innermost."""
    devices = list(devices) if devices is not None else jax.devices()
    want = dp * tp * sp * ep
    if want > len(devices):
        raise ValueError(
            f"mesh {dp}x{sp}x{ep}x{tp} needs {want} devices, have {len(devices)}")
    arr = np.asarray(devices[:want]).reshape(dp, sp, ep, tp)
    return Mesh(arr, AXES)


def mesh_from_spec(spec: Dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a mesh from a ``{"dp": 2, "tp": 4}``-style spec (as injected by the
    control plane's discovery config — see rbg_tpu.discovery)."""
    return make_mesh(
        dp=spec.get("dp", 1), tp=spec.get("tp", 1), sp=spec.get("sp", 1),
        ep=spec.get("ep", 1), devices=devices,
    )


def single_device_mesh() -> Mesh:
    return make_mesh(dp=1, tp=1, sp=1)
