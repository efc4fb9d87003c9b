"""A ``LlamaConfig`` from a dense decoder's configuration file. Jax is
imported where the object is built, on the worker's side, and not here.

The file keeps the model's published key names at its top level and what
the program is told beside the model under ``program`` (attention
implementation, activation and parameter types, rematerialisation, chunk
of the loss). No preset is added to ``models/llama.py``: the object is
built here and handed over.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness.loader import BOOKKEEPING_KEYS

MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "intermediate_size": "mlp_hidden", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
}
PROGRAM_KEYS = ("attn_impl", "remat", "remat_policy", "loss_chunk")
# published keys held to the one value that `models/llama.py` computes
CHECKED_KEYS = ("sliding_window", "tie_word_embeddings", "hidden_act",
                "attention_dropout")


def check_supported(m: Dict[str, Any]) -> None:
    """What the dense path of ``models/llama.py`` does not compute. Every
    key of the file is one this family maps, checks or keeps its books
    by: a key of another family (``num_experts``) is an error, not a dense
    model of the file's other sizes."""
    unknown = sorted(set(m) - set(MODEL_KEYS) - set(CHECKED_KEYS)
                     - set(BOOKKEEPING_KEYS))
    if unknown:
        raise ValueError(
            f"configuration {m.get('name')!r}: the dense decoder family "
            f"does not understand {unknown}")
    if m.get("attention_dropout", 0.0) != 0.0:
        raise ValueError(f"attention_dropout {m['attention_dropout']!r}: "
                         "models/llama.py has no dropout")
    if m.get("sliding_window") is not None:
        raise ValueError("models/llama.py has no sliding-window attention")
    if m.get("tie_word_embeddings"):
        raise ValueError("models/llama.py keeps a separate output head")
    if m.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {m['hidden_act']!r}: only silu")


def build_llama_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    check_supported(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
    return LlamaConfig(**kwargs)
