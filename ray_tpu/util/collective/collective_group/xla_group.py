"""XLA/device-mesh collective group — the TPU-native replacement for the
reference's NCCLGroup (reference:
python/ray/util/collective/collective_group/nccl_collective_group.py:127).

Design (SURVEY §2.5 / §5 "Distributed communication backend"):

- Within one group member (= one worker process = one TPU host), tensors may
  be ``jax.Array``s sharded over the member's **local device mesh**; the
  intra-member reduction lowers to ``jax.lax`` collectives over ICI via
  ``shard_map`` (see :meth:`_local_psum`).
- Across members, this class rides the host store (DCN control plane). On a
  real multi-host pod slice the preferred path is a *global* mesh formed by
  ``jax.distributed.initialize`` — then no per-op host hop exists at all and
  this group degenerates to rendezvous bookkeeping; see
  ``ray_tpu.train`` which uses exactly that path for gradient sync.

Results are returned as ``jax.Array``s placed with the input's sharding
(device_put), keeping the op functional.
"""

from __future__ import annotations

from typing import Any, List

import numpy as np

from ray_tpu.util.collective.collective_group.cpu_group import CPUGroup
from ray_tpu.util.collective.types import ReduceOp


def _is_jax(x) -> bool:
    try:
        import jax

        return isinstance(x, jax.Array)
    except Exception:
        return False


class XLAGroup(CPUGroup):
    @classmethod
    def backend(cls) -> str:
        return "xla"

    def _to_wire(self, tensor) -> np.ndarray:
        if tensor is None:
            return None
        if _is_jax(tensor):
            import jax

            # Pull once to host for the cross-member (DCN) hop. A fully
            # addressable array is a cheap device->host copy; on multi-host
            # meshes the caller should be using the global-mesh path instead.
            return np.asarray(jax.device_get(tensor))
        return np.asarray(tensor)

    def _from_wire(self, array: np.ndarray, like):
        if like is not None and _is_jax(like):
            import jax

            return jax.device_put(
                array.astype(like.dtype), like.sharding)
        return super()._from_wire(array, like)

    # -- device-native helpers --------------------------------------------

    @staticmethod
    def local_psum(tensor, mesh, axis: str):
        """Reduce a per-device value over one axis of the member's local mesh
        — pure ICI traffic via ``jax.lax.psum`` under ``shard_map``."""
        import jax
        from jax.sharding import PartitionSpec as P

        spec = P(axis)
        return jax.jit(
            jax.shard_map(
                lambda x: jax.lax.psum(x, axis),
                mesh=mesh, in_specs=(spec,), out_specs=P()))(tensor)

    def allreduce_sharded(self, tensor, mesh, axis: str,
                          op: ReduceOp = ReduceOp.SUM):
        """Hierarchical allreduce: ICI psum over the member's local mesh axis,
        then the cross-member combine (reference analog:
        nccl_collective_group allreduce_multigpu)."""
        local = self.local_psum(tensor, mesh, axis)
        from ray_tpu.util.collective.types import AllReduceOptions

        return self.allreduce(local, AllReduceOptions(reduceOp=op))
