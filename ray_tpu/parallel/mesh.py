"""Device-mesh formation for TPU slices.

The reference's collective "group" is an actor rendezvous that boots NCCL
(reference: python/ray/util/collective/collective.py:120-151,
collective_group/nccl_collective_group.py:127). TPU-native, a group is a
``jax.sharding.Mesh`` over the slice's devices; collectives are XLA ops over
ICI, with DCN handling the cross-slice (outer) axes. This module owns mesh
axis conventions and shape inference.

Axis conventions (outer → inner, matching ICI locality: the innermost axes
get the most bandwidth-hungry collectives):

- ``data``   — pure data parallelism (gradient psum; can span DCN)
- ``stage``  — pipeline parallelism (p2p activation ppermute; low bandwidth,
  placed outer so inner axes keep the dense-collective ICI links)
- ``fsdp``   — ZeRO-3 style parameter/optimizer sharding (all-gather weights)
- ``seq``    — sequence/context parallelism (ring attention ppermute)
- ``tensor`` — megatron-style tensor parallelism (activation collectives; ICI)
- ``expert`` — MoE expert parallelism (all_to_all)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER: Tuple[str, ...] = ("data", "stage", "fsdp", "seq", "tensor",
                               "expert")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Declarative mesh shape. -1 on at most one axis means "absorb the rest".

    This plays the role the reference's ``ScalingConfig`` plays for Train
    (reference: python/ray/air/config.py:101) but speaks mesh axes instead of
    worker counts.
    """

    data: int = -1
    stage: int = 1
    fsdp: int = 1
    seq: int = 1
    tensor: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {a: getattr(self, a) for a in AXIS_ORDER}
        wildcards = [a for a, s in sizes.items() if s == -1]
        if len(wildcards) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wildcards}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wildcards:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}")
            sizes[wildcards[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {fixed} devices, have {n_devices}")
        return sizes

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return AXIS_ORDER


def best_mesh_shape(
    n_devices: int,
    *,
    tensor: int = 1,
    fsdp: Optional[int] = None,
    seq: int = 1,
) -> MeshConfig:
    """Heuristic: put everything not explicitly requested on fsdp (memory wins
    on TPU — HBM per chip is small), leaving data=1 unless fsdp is capped."""
    if fsdp is None:
        fsdp = max(1, n_devices // (tensor * seq))
    return MeshConfig(data=-1, fsdp=fsdp, seq=seq, tensor=tensor)


def create_mesh(
    config: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    *,
    allow_split_physical_axes: bool = True,
) -> Mesh:
    """Build a Mesh honoring TPU physical topology when available.

    ``jax.experimental.mesh_utils.create_device_mesh`` lays logical axes onto
    the physical torus so that inner axes ride ICI neighbors; we fall back to
    a plain reshape for CPU/virtual device testing.
    """
    devices = list(devices if devices is not None else jax.devices())
    config = config or MeshConfig()
    sizes = config.resolve(len(devices))
    shape = tuple(sizes[a] for a in AXIS_ORDER)
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(
            shape, devices=devices,
            allow_split_physical_axes=allow_split_physical_axes)
    except Exception:
        if devices[0].platform == "tpu":
            raise  # on real TPU, losing torus placement is a silent perf bug
        dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)

