"""ray_tpu.models — JAX-native model families.

The reference ships no models of its own for Train (users bring torch models);
RLlib ships torch/tf model catalogs (reference: rllib/models/, 12.1k LoC).
TPU-native, the framework provides sharding-annotated JAX model families that
the Train/Serve/RLlib layers consume directly.

A decoder with routed experts (OLMoE) is a ``LlamaConfig`` with
``num_experts`` above 0: ``llama.py`` holds the one block, ``moe.py`` the
routed feed-forward it calls. A decoder with a layer pattern (LFM2: gated
short convolutions beside attention, leading dense layers) is a
``LlamaConfig`` with ``layer_types``: the same block, one stacked pytree a
kind of layer.
"""

from ray_tpu.models.llama import (
    LlamaConfig,
    init_decode_state,
    init_llama,
    llama_forward,
    llama_decode,
    llama_loss,
    llama_logical_axes,
)

__all__ = [
    "LlamaConfig", "init_llama", "llama_forward", "llama_decode",
    "init_decode_state", "llama_loss", "llama_logical_axes",
]
