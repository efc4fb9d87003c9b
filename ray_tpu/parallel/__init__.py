"""ray_tpu.parallel — mesh formation, sharding rules, and parallel train steps.

This is the TPU-native replacement for the parallelism strategies the reference
reaches through integrations (DDP via torch process groups, FSDP/ZeRO via
DeepSpeed — reference: python/ray/train/torch/config.py:91,129,
python/ray/train/lightning/_lightning_utils.py:56-126). Here every strategy is
a mesh axis: data parallel = ``data``, ZeRO-3/FSDP = ``fsdp``, tensor parallel
= ``tensor``, sequence/context parallel = ``seq``, expert parallel =
``expert`` — and XLA GSPMD inserts the collectives over ICI/DCN.
"""

from ray_tpu.parallel.mesh import (
    MeshConfig,
    create_mesh,
    best_mesh_shape,
)
from ray_tpu.parallel.sharding import (
    LogicalAxisRules,
    DEFAULT_RULES,
    logical_to_spec,
    shard_pytree,
    constrain,
    param_shardings,
)
from ray_tpu.parallel.train_step import (
    TrainState,
    create_train_state,
    make_train_step,
    make_eval_step,
)

__all__ = [
    "MeshConfig", "create_mesh", "best_mesh_shape",
    "LogicalAxisRules", "DEFAULT_RULES", "logical_to_spec", "shard_pytree",
    "constrain", "param_shardings",
    "TrainState", "create_train_state", "make_train_step", "make_eval_step",
]
