"""The Mellum family (``model_type: mellum``, Mellum2-12B-A2.5B):
``models/llama.py``'s one block with grouped-query attention as its
operator in both layer types, ``sliding_attention`` layers whose query sees
its last ``sliding_window`` keys under plain rope (the equal-width flash
forward told the window) and ``full_attention`` layers over every causal
key under YaRN, an RMSNorm over each head of the queries and keys, then
``models/moe.py``'s routed experts under a softmax router whose chosen
weights are renormalised, no shared expert; at a configuration file's
sizes, served by ``serve/llm.py::LlamaGenerator``, checked against
``reference/mellum.py``.

It gives what ``families/dense_decoder.py``'s docstring lists, and beside
it what its readers ask for: ``expert_ffn_flops`` and ``expert_ffn_bytes``
for the expert feed-forward's share of its roofline, and for each of its
two attention kernels the FLOPs and the least bytes of a traced step from
the step's record (``harness/steprecord.py``): ``window_flash_*`` over the
(query, key) pairs INSIDE the window, ``full_flash_*`` (and
``flash_fwd_pair_flops`` / ``flash_fwd_row_bytes``, which
``readers/flash_fwd_roofline_pct_serve.py`` asks for by those names) over
the causal pairs of the live rows. Importing this module imports no jax:
the harness process and the readers load it too.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.families.dots3_note import kept_pairs
from benchmark.families.lfm2_moe import _config_fields
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "mellum"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "intermediate_size": "dense_mlp_hidden",  # a dense layer's: none here
    "moe_intermediate_size": "mlp_hidden",    # ONE expert's width
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "rms_eps", "sliding_window": "sliding_window",
    "num_experts": "num_experts", "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob",
    "tie_word_embeddings": "tie_embeddings",
}
# keys the family reads itself: the two patterns and the two ropes
OWN_KEYS = ("layer_types", "mlp_layer_types", "rope_parameters")
# what `build_config` sets beside the mapped keys: from OWN_KEYS, and what
# is modeling code and no key (the file states it under `assumed`)
BUILT = ("layer_types", "num_dense_layers", "rope_theta", "rope_scaling")
MODELING = {"qk_head_norm": True}
# published keys held to the one value that the program computes
HELD = {"attention_bias": False, "hidden_act": "silu",
        "use_sliding_window": True, "max_window_layers": 0}
LAYER_TYPES = ("sliding_attention", "full_attention")
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key. A
    checkout whose program has no sliding operator fails here, at once."""
    name = m.get("name")
    known = (set(MODEL_KEYS) | set(OWN_KEYS) | set(HELD)
             | set(BOOKKEEPING_KEYS))
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"configuration {name!r}: the mellum family does "
                         f"not understand {unknown}")
    missing = sorted(known - set(BOOKKEEPING_KEYS) - set(m))
    if missing:
        raise ValueError(f"configuration {name!r} lacks {missing}")
    lacking = sorted((set(MODEL_KEYS.values()) | set(BUILT) | set(MODELING))
                     - _config_fields())
    if lacking:
        raise ValueError(f"this checkout's LlamaConfig has no {lacking}: "
                         "models/llama.py cannot build this family")
    if "window_keys_seen" not in getattr(LlamaGenerator, "STEP_COUNTERS",
                                         ()):
        raise ValueError("this checkout's serve/llm.py counts no causal "
                         "keys beside a window's (window_keys_seen): its "
                         "program has no sliding_attention operator and "
                         "cannot serve this family")
    for key, only in HELD.items():
        if m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: the program computes "
                             f"{only!r} only")
    types, ffns = m["layer_types"], m["mlp_layer_types"]
    if not len(types) == len(ffns) == m["num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(types)} layers and mlp_layer_types "
            f"{len(ffns)}, num_hidden_layers is {m['num_hidden_layers']}")
    strange = sorted(set(types) - set(LAYER_TYPES))
    if strange:
        raise ValueError(f"layer_types {strange}: expected some of "
                         f"{list(LAYER_TYPES)}")
    dense = ffns.count("dense")
    if ffns != ["dense"] * dense + ["sparse"] * (len(ffns) - dense):
        raise ValueError(f"mlp_layer_types {ffns}: the program's dense "
                         "layers are the leading ones, the rest sparse")
    if m["sliding_window"] < 1:
        raise ValueError("sliding_window counts keys: at least 1")
    if not 0 < m["num_experts_per_tok"] <= m["num_experts"]:
        raise ValueError("num_experts_per_tok must lie in 1..num_experts")
    if m["num_attention_heads"] % m["num_key_value_heads"]:
        raise ValueError("the query heads share the key/value heads in "
                         "whole groups")
    ropes = m["rope_parameters"]
    if not isinstance(ropes, dict) or set(ropes) != set(LAYER_TYPES):
        raise ValueError(f"rope_parameters: expected one entry each of "
                         f"{list(LAYER_TYPES)}")
    full, sliding = ropes["full_attention"], ropes["sliding_attention"]
    if sliding != {"rope_type": "default",
                   "rope_theta": full.get("rope_theta")}:
        raise ValueError(
            f"rope_parameters.sliding_attention {sliding!r}: the program "
            "turns a sliding layer under plain rope at the full layers' "
            "rope_theta")
    yarn_keys = {"rope_type", "rope_theta", "factor", "beta_fast",
                 "beta_slow", "original_max_position_embeddings",
                 "attention_factor"}
    if full.get("rope_type") != "yarn" or set(full) != yarn_keys:
        raise ValueError(f"rope_parameters.full_attention {full!r}: "
                         f"expected rope_type yarn with {sorted(yarn_keys)}")
    if not math.isclose(full["attention_factor"],
                        0.1 * math.log(full["factor"]) + 1.0,
                        rel_tol=1e-12):
        raise ValueError(
            f"attention_factor {full['attention_factor']!r}: the program "
            "multiplies cos and sin by 0.1 ln(factor) + 1 only")


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, RopeScaling

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    full = m["rope_parameters"]["full_attention"]
    kwargs.update(
        layer_types=tuple(m["layer_types"]),
        num_dense_layers=m["mlp_layer_types"].count("dense"),
        rope_theta=float(full["rope_theta"]),
        # mscale 1 over mscale_all_dim 0: cos and sin times 0.1 ln(factor)
        # + 1, which `check` holds attention_factor to, and the softmax
        # scale head_dim ** -0.5 untouched
        rope_scaling=RopeScaling(
            factor=float(full["factor"]),
            original_max_position_embeddings=full[
                "original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            mscale=1.0, mscale_all_dim=0.0))
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
    # the norms over each head of the queries and keys are modeling code,
    # not a key of config.json: assumed (the file's `assumed` says why)
    return LlamaConfig(**MODELING, **kwargs)


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
    pass


# ---------------------------------------------------------------- counts
def layer_counts(m: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each half: ``sliding`` or ``full``, and
    ``dense`` or ``routed``."""
    types, ffns = m["layer_types"], m["mlp_layer_types"]
    return {"sliding": types.count("sliding_attention"),
            "full": types.count("full_attention"),
            "dense": ffns.count("dense"), "routed": ffns.count("sparse")}


def part_params(m: Dict[str, Any], *, active: bool = False
                ) -> Dict[str, int]:
    """Parameters of one layer's parts: attention (its four projections and
    the two norms over a head, the same under a window or none), a routed
    feed-forward (the router and the experts; ``active``: the ones a
    position meets), a dense one, the block's two norms."""
    h = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    experts = m["num_experts_per_tok"] if active else m["num_experts"]
    return {"attention": h * (q + 2 * kv) + q * h + 2 * m["head_dim"],
            "routed": (h * m["num_experts"]
                       + experts * 3 * h * m["moe_intermediate_size"]),
            "dense": 3 * h * m["intermediate_size"], "norms": 2 * h}


def num_params(m: Dict[str, Any], *, active: bool = False) -> int:
    parts, counts = part_params(m, active=active), layer_counts(m)
    tied = 1 if m["tie_word_embeddings"] else 2
    return (m["num_hidden_layers"] * (parts["attention"] + parts["norms"])
            + counts["routed"] * parts["routed"]
            + counts["dense"] * parts["dense"]
            + tied * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def expert_ffn_flops(m: Dict[str, Any], positions: int) -> float:
    """What the three grouped matmuls of every routed layer need for
    ``positions`` positions of one forward pass: each position meets
    ``num_experts_per_tok`` experts, each three matmuls of hidden x
    ``moe_intermediate_size``, 2 FLOP a multiply-add. The router, the
    sort, the gathers and the weighted sum are not counted."""
    return (layer_counts(m)["routed"] * positions * m["num_experts_per_tok"]
            * 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"])


def expert_ffn_bytes(m: Dict[str, Any], met: float = None) -> float:
    """Least HBM traffic of those matmuls in one forward pass: the three
    matrices of each of the ``met`` experts that a position met (summed
    over the routed layers; every expert of every such layer where the
    program does not say) read once, in the parameters' type. The rows in
    and out are left out (a lower bound stays a lower bound)."""
    size = BYTES[m.get("program", {}).get("param_dtype", "float32")]
    if met is None:
        met = layer_counts(m)["routed"] * m["num_experts"]
    return (met * 3.0 * m["hidden_size"] * m["moe_intermediate_size"]
            * size)


# --------------------------------- the two attention kernels' need, a step
def _pair_flops(m: Dict[str, Any], layers: int, pairs: float) -> float:
    """A score and a weighted value over ``head_dim`` a query head and
    pair, 2 FLOP a multiply-add."""
    return (layers * pairs * 2 * 2.0 * m["num_attention_heads"]
            * m["head_dim"])


def _row_bytes(m: Dict[str, Any], layers: int, queries: float,
               keys: float) -> float:
    """bf16: q and o once a query position at the query heads, k and v
    once a key position at the key/value heads (a group's eight query
    heads read one key head; read once is the least)."""
    elems = m["head_dim"] * (2 * m["num_attention_heads"] * queries
                             + 2 * m["num_key_value_heads"] * keys)
    return layers * 2.0 * elems


def window_flash_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The sliding layers' flash forward over the pairs inside the window
    (``families/dots3_note.py::kept_pairs`` at ``sliding_window``: exact
    for a step whose every row is at least a window long and re-runs its
    whole prefix, and a count that errs low for any other)."""
    return _pair_flops(m, layer_counts(m)["sliding"],
                       kept_pairs(step, m["sliding_window"]))


def window_flash_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    return _row_bytes(m, layer_counts(m)["sliding"], step["positions_live"],
                      step["attention_keys"])


def flash_fwd_pair_flops(m: Dict[str, Any], pairs: float) -> float:
    """What the flash forward of every FULL layer needs for ``pairs``
    causal (query, key) pairs."""
    return _pair_flops(m, layer_counts(m)["full"], pairs)


def flash_fwd_row_bytes(m: Dict[str, Any], queries: float,
                        keys: float) -> float:
    """Least HBM traffic of the full layers' forwards."""
    return _row_bytes(m, layer_counts(m)["full"], queries, keys)


def full_flash_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The full layers' flash forward over the causal pairs of a traced
    step's live rows (the record's ``attention_pairs``)."""
    return flash_fwd_pair_flops(m, step["attention_pairs"])


def full_flash_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    return flash_fwd_row_bytes(m, step["positions_live"],
                               step["attention_keys"])
