"""The OLMoE family: ``models/llama.py``'s one block with ``models/moe.py``'s
routed feed-forward and the query/key norms, at a configuration file's
sizes, served by ``serve/llm.py::LlamaGenerator``, checked against
``reference/olmoe.py``.

It gives what ``families/dense_decoder.py``'s docstring lists, and beside
it ``expert_ffn_flops`` and ``expert_ffn_bytes`` for the expert
feed-forward's share of its roofline. Importing this module imports no
jax: the harness process and the readers load it too.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness import flops
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "olmoe"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "intermediate_size": "mlp_hidden",  # read as ONE expert's width
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_eps",
    "num_experts": "num_experts", "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob",
    "router_aux_loss_coef": "router_aux_loss_coef",
}
# published keys held to the one value that the program computes
HELD = {"clip_qkv": None, "attention_bias": False, "rope_scaling": None,
        "hidden_act": "silu", "tie_word_embeddings": False,
        "attention_dropout": 0.0}
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key."""
    unknown = sorted(set(m) - set(MODEL_KEYS) - set(HELD)
                     - set(BOOKKEEPING_KEYS))
    if unknown:
        raise ValueError(f"configuration {m.get('name')!r}: the olmoe "
                         f"family does not understand {unknown}")
    missing = sorted(set(MODEL_KEYS) - set(m))
    if missing:
        raise ValueError(f"configuration {m.get('name')!r} lacks {missing}")
    for key, only in HELD.items():
        if key in m and m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: models/llama.py computes "
                             f"{only!r} only")
    if not 0 < m["num_experts_per_tok"] <= m["num_experts"]:
        raise ValueError("num_experts_per_tok must lie in 1..num_experts")
    if "expert_pairs_mean" not in getattr(LlamaGenerator, "STEP_COUNTERS",
                                          ()):
        raise ValueError("this checkout's serve/llm.py keeps no books of a "
                         "router's load: it cannot serve num_experts")


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
    # the norms over the query and key projections are in modeling_olmoe,
    # not a key of config.json: the family has them
    return LlamaConfig(qk_norm=True, **kwargs)


def training(m: Dict[str, Any]) -> Dict[str, Any]:
    from ray_tpu.models.llama import (
        init_llama, llama_logical_axes, llama_loss)

    cfg = build_config(m)
    return {"init": lambda key: init_llama(cfg, key),
            "logical_axes": llama_logical_axes(cfg),
            "loss": lambda p, b: llama_loss(p, b, cfg)}


def served_kwargs(m: Dict[str, Any], engine: Dict[str, Any],
                  seed: int) -> Dict[str, Any]:
    return dict(
        config=build_config(m), lora_rank=engine["lora_rank"],
        max_batch_size=engine["max_batch_size"],
        allowed_batch_sizes=tuple(engine["allowed_batch_sizes"]),
        max_new_tokens=engine["max_new_tokens"],
        seq_bucket=engine["seq_bucket"], seed=seed % (2 ** 31))


class Served(LlamaGenerator):
    """The program's class, with each step under a span that names its
    padded length: the work a step needs differs by bucket, and the
    roofline reader counts the traced steps' own (``host_spans``)."""

    def _step(self, model_id, states):
        import jax

        with jax.profiler.TraceAnnotation(
                f"bench:len_{self._padded_len(states)}"):
            return super()._step(model_id, states)

    def last_position_logits(self, prompt: List[int]):
        # the program's name for it (`tests/benchmark/test_benchmark.py`
        # pins `last_position_logits` to the dense family's stand-in)
        return self.logits_after_prompt(prompt)


# ---------------------------------------------------------------- counts
def layer_params(m: Dict[str, Any], *, active: bool = False) -> int:
    """One block: attention, the two norms over its projections, the
    router, the experts (``active``: the ones a position meets), the
    block's two norms."""
    h, w = m["hidden_size"], m["intermediate_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    experts = m["num_experts_per_tok"] if active else m["num_experts"]
    return (h * (q + 2 * kv) + q * h + q + kv + h * m["num_experts"]
            + experts * 3 * h * w + 2 * h)


def num_params(m: Dict[str, Any]) -> int:
    return (m["num_hidden_layers"] * layer_params(m)
            + 2 * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward and backward matmuls a token meets (its 8 experts, the
    router, attention's projections, the head) and causal attention; no
    lookup, norms, softmax, sort or gather."""
    h = m["hidden_size"]
    norms = (2 * h + (m["num_attention_heads"] + m["num_key_value_heads"])
             * m["head_dim"])
    per_layer = layer_params(m, active=True) - norms
    matmul = m["num_hidden_layers"] * per_layer + m["vocab_size"] * h
    return 6.0 * matmul + flops.attention_flops_per_token(
        m, seq, backward=True)


attention_kernel_flops = flops.flash_train_flops
attention_kernel_bytes = flops.flash_train_bytes


def expert_ffn_flops(m: Dict[str, Any], positions: int) -> float:
    """What the three grouped matmuls of every layer need for ``positions``
    positions of one forward pass: each position meets
    ``num_experts_per_tok`` experts, each three matmuls of hidden x width,
    2 FLOP a multiply-add. The router, the sort, the gathers and the
    weighted sum are not counted."""
    return (m["num_hidden_layers"] * positions * m["num_experts_per_tok"]
            * 3 * 2.0 * m["hidden_size"] * m["intermediate_size"])


def expert_ffn_bytes(m: Dict[str, Any]) -> float:
    """Least HBM traffic of those matmuls in one forward pass: every
    expert's three matrices read once, in the parameters' type. The rows
    in and out are left out (a lower bound stays a lower bound)."""
    size = BYTES[m.get("program", {}).get("param_dtype", "float32")]
    return (m["num_hidden_layers"] * m["num_experts"] * 3.0
            * m["hidden_size"] * m["intermediate_size"] * size)
