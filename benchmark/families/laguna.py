"""The Laguna family (``model_type: laguna``, Laguna-XS.2):
``models/llama.py``'s one block with grouped-query attention as its
operator in both layer types at a head count a type
(``num_attention_heads_per_layer``: 64 query heads on 8 key/value heads in
a ``sliding_attention`` layer, whose query sees its last ``sliding_window``
keys under plain rope at its own ``rope_theta`` over the whole head; 48 on
8 in a ``full_attention`` layer, over every causal key under YaRN over the
first ``partial_rotary_factor`` of the head), a sigmoid gate a head on
attention's output (``gating``), one leading dense layer, then
``models/moe.py``'s routed experts under a sigmoid router whose chosen
weights are renormalised and scaled beside one shared expert; at a
configuration file's sizes, served by ``serve/llm.py::LlamaGenerator``,
checked against ``reference/laguna.py``.

It gives what ``families/dense_decoder.py``'s docstring lists, and beside
it what its readers ask for: ``expert_ffn_flops`` and ``expert_ffn_bytes``
for the expert feed-forward's share of its roofline, and for each of its
two attention kernels the FLOPs and the least bytes of a traced step from
the step's record (``harness/steprecord.py``), each by its kind's own head
count: ``window_flash_*`` over the (query, key) pairs INSIDE the window,
``full_flash_*`` (and ``flash_fwd_pair_flops`` / ``flash_fwd_row_bytes``,
which ``readers/flash_fwd_roofline_pct_serve.py`` asks for by those names)
over the causal pairs of the live rows. Importing this module imports no
jax: the harness process and the readers load it too.
"""

from __future__ import annotations

import math
from typing import Any, Dict

from benchmark.families.dots3_note import kept_pairs
from benchmark.families.lfm2_moe import _config_fields
# the same keys count the same things in both families of window and full
# layers: layers by half, and the grouped matmuls' FLOPs (8 routed experts a
# position, three matmuls each; the shared expert is plain dots in the
# trace and not among them) and least bytes
from benchmark.families.mellum import (  # noqa: F401
    LAYER_TYPES, expert_ffn_bytes, expert_ffn_flops, layer_counts)
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "laguna"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "intermediate_size": "dense_mlp_hidden",  # the leading dense layer's
    "moe_intermediate_size": "mlp_hidden",    # ONE expert's width
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",       # a full layer's
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "rms_eps", "sliding_window": "sliding_window",
    "num_experts": "num_experts", "num_experts_per_tok": "experts_per_token",
    "moe_routed_scaling_factor": "routed_scaling_factor",
    "gating": "head_gate", "tie_word_embeddings": "tie_embeddings",
}
# keys the family reads itself: the three patterns, the two ropes and the
# shared expert's width
OWN_KEYS = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer",
            "rope_parameters", "partial_rotary_factor",
            "shared_expert_intermediate_size")
# what `build_config` sets beside the mapped keys: from OWN_KEYS, and what
# is modeling code and no key (the file states it under `assumed`)
BUILT = ("layer_types", "num_dense_layers", "swa_num_heads", "rope_theta",
         "swa_rope_theta", "partial_rotary_factor", "rope_scaling",
         "num_shared_experts")
MODELING = {"router_scores": "sigmoid", "norm_topk_prob": True}
# published keys held to the one value that the program computes
HELD = {"attention_bias": False, "moe_apply_router_weight_on_input": False}


def _heads(m: Dict[str, Any]) -> Dict[str, int]:
    """Each layer type's query heads, from the per-layer list."""
    return {t: n for t, n in zip(m["layer_types"],
                                 m["num_attention_heads_per_layer"])}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key. A
    checkout whose program has no head count a layer type fails here, at
    once."""
    name = m.get("name")
    known = (set(MODEL_KEYS) | set(OWN_KEYS) | set(HELD)
             | set(BOOKKEEPING_KEYS))
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"configuration {name!r}: the laguna family does "
                         f"not understand {unknown}")
    missing = sorted(known - set(BOOKKEEPING_KEYS) - set(m))
    if missing:
        raise ValueError(f"configuration {name!r} lacks {missing}")
    lacking = sorted((set(MODEL_KEYS.values()) | set(BUILT) | set(MODELING))
                     - _config_fields())
    if lacking:
        raise ValueError(f"this checkout's LlamaConfig has no {lacking}: "
                         "models/llama.py cannot build this family")
    for key, only in HELD.items():
        if m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: the program computes "
                             f"{only!r} only")
    if m["gating"] is not True:
        raise ValueError(f"gating {m['gating']!r}: the program has the "
                         "head-wise gate, which this family reads `true` as")
    types, ffns = m["layer_types"], m["mlp_layer_types"]
    per_layer = m["num_attention_heads_per_layer"]
    if not len(types) == len(ffns) == len(per_layer) == m[
            "num_hidden_layers"]:
        raise ValueError(
            f"layer_types names {len(types)} layers, mlp_layer_types "
            f"{len(ffns)} and num_attention_heads_per_layer "
            f"{len(per_layer)}; num_hidden_layers is "
            f"{m['num_hidden_layers']}")
    strange = sorted(set(types) - set(LAYER_TYPES))
    if strange:
        raise ValueError(f"layer_types {strange}: expected some of "
                         f"{list(LAYER_TYPES)}")
    heads = _heads(m)
    if per_layer != [heads[t] for t in types]:
        raise ValueError(
            f"num_attention_heads_per_layer {per_layer}: the program has "
            "one head count a layer type")
    if heads.get("full_attention", m["num_attention_heads"]) != m[
            "num_attention_heads"]:
        raise ValueError("num_attention_heads is the full layers' count")
    for n in heads.values():
        if n % m["num_key_value_heads"]:
            raise ValueError("the query heads share the key/value heads "
                             "in whole groups")
    dense = ffns.count("dense")
    if ffns != ["dense"] * dense + ["sparse"] * (len(ffns) - dense):
        raise ValueError(f"mlp_layer_types {ffns}: the program's dense "
                         "layers are the leading ones, the rest sparse")
    if m["sliding_window"] < 1:
        raise ValueError("sliding_window counts keys: at least 1")
    if not 0 < m["num_experts_per_tok"] <= m["num_experts"]:
        raise ValueError("num_experts_per_tok must lie in 1..num_experts")
    if m["shared_expert_intermediate_size"] % m["moe_intermediate_size"]:
        raise ValueError("the shared expert is a whole number of experts "
                         "wide")
    ropes = m["rope_parameters"]
    own = {"original_max_position_embeddings"} & set(ropes)
    if not isinstance(ropes, dict) or set(ropes) - own != set(LAYER_TYPES):
        raise ValueError(f"rope_parameters: expected one entry each of "
                         f"{list(LAYER_TYPES)}")
    full, sliding = ropes["full_attention"], ropes["sliding_attention"]
    if (set(sliding) != {"rope_type", "rope_theta", "partial_rotary_factor"}
            or sliding["rope_type"] != "default"
            or sliding["partial_rotary_factor"] != 1):
        raise ValueError(
            f"rope_parameters.sliding_attention {sliding!r}: the program "
            "turns a sliding layer's whole head under plain rope")
    yarn_keys = {"rope_type", "rope_theta", "factor", "beta_fast",
                 "beta_slow", "original_max_position_embeddings",
                 "attention_factor", "partial_rotary_factor"}
    if full.get("rope_type") != "yarn" or set(full) != yarn_keys:
        raise ValueError(f"rope_parameters.full_attention {full!r}: "
                         f"expected rope_type yarn with {sorted(yarn_keys)}")
    if full["partial_rotary_factor"] != m["partial_rotary_factor"]:
        raise ValueError("partial_rotary_factor is the full layers'")
    if ropes.get("original_max_position_embeddings",
                 full["original_max_position_embeddings"]) != full[
                     "original_max_position_embeddings"]:
        raise ValueError("original_max_position_embeddings is given twice "
                         "and differs")
    rotary = m["head_dim"] * full["partial_rotary_factor"]
    if rotary != int(rotary) or int(rotary) % 2:
        raise ValueError("partial_rotary_factor leaves no whole pairs")
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
    ropes = m["rope_parameters"]
    full, sliding = ropes["full_attention"], ropes["sliding_attention"]
    kwargs.update(
        layer_types=tuple(m["layer_types"]),
        num_dense_layers=m["mlp_layer_types"].count("dense"),
        swa_num_heads=_heads(m).get("sliding_attention", 0),
        rope_theta=float(full["rope_theta"]),
        swa_rope_theta=float(sliding["rope_theta"]),
        partial_rotary_factor=float(full["partial_rotary_factor"]),
        num_shared_experts=(m["shared_expert_intermediate_size"]
                            // m["moe_intermediate_size"]),
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
    # the router's sigmoid and its renormalisation are modeling code, not
    # keys of config.json: assumed (the file's `assumed` says why)
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
def kind_heads(m: Dict[str, Any]) -> Dict[str, int]:
    """``sliding`` and ``full``: each kind's query heads."""
    heads = _heads(m)
    return {"sliding": heads.get("sliding_attention", 0),
            "full": heads.get("full_attention", 0)}


def part_params(m: Dict[str, Any], *, active: bool = False
                ) -> Dict[str, int]:
    """Parameters of one layer's parts: attention by kind (its four
    projections and the gate's ``hidden x heads``), a routed feed-forward
    (the router, the experts and the shared one; ``active``: the ones a
    position meets), a dense one, the block's two norms."""
    h, d = m["hidden_size"], m["head_dim"]
    kv = m["num_key_value_heads"] * d
    experts = m["num_experts_per_tok"] if active else m["num_experts"]
    attention = {kind: h * (n * d + 2 * kv) + n * d * h + h * n
                 for kind, n in kind_heads(m).items()}
    return {**attention,
            "routed": (h * m["num_experts"]
                       + experts * 3 * h * m["moe_intermediate_size"]
                       + 3 * h * m["shared_expert_intermediate_size"]),
            "dense": 3 * h * m["intermediate_size"], "norms": 2 * h}


def num_params(m: Dict[str, Any], *, active: bool = False) -> int:
    parts, counts = part_params(m, active=active), layer_counts(m)
    tied = 1 if m["tie_word_embeddings"] else 2
    return (sum(counts[part] * parts[part]
                for part in ("sliding", "full", "routed", "dense"))
            + m["num_hidden_layers"] * parts["norms"]
            + tied * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


# --------------------------------- the two attention kernels' need, a step
def _pair_flops(m: Dict[str, Any], kind: str, pairs: float) -> float:
    """A score and a weighted value over ``head_dim`` a query head of the
    kind and pair, 2 FLOP a multiply-add, over the kind's layers."""
    return (layer_counts(m)[kind] * pairs * 2 * 2.0 * kind_heads(m)[kind]
            * m["head_dim"])


def _row_bytes(m: Dict[str, Any], kind: str, queries: float,
               keys: float) -> float:
    """bf16: q and o once a query position at the kind's query heads, k
    and v once a key position at the key/value heads (a group's query
    heads read one key head; read once is the least)."""
    elems = m["head_dim"] * (2 * kind_heads(m)[kind] * queries
                             + 2 * m["num_key_value_heads"] * keys)
    return layer_counts(m)[kind] * 2.0 * elems


def window_flash_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The sliding layers' flash forward over the pairs inside the window
    (``families/dots3_note.py::kept_pairs`` at ``sliding_window``: exact
    for a step whose every row is at least a window long and re-runs its
    whole prefix; of a shorter row, which the step's record does not tell
    from a longer one, it counts fewer than the row keeps, so the share
    errs low and never over)."""
    return _pair_flops(m, "sliding", kept_pairs(step, m["sliding_window"]))


def window_flash_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    return _row_bytes(m, "sliding", step["positions_live"],
                      step["attention_keys"])


def flash_fwd_pair_flops(m: Dict[str, Any], pairs: float) -> float:
    """What the flash forward of every FULL layer needs for ``pairs``
    causal (query, key) pairs."""
    return _pair_flops(m, "full", pairs)


def flash_fwd_row_bytes(m: Dict[str, Any], queries: float,
                        keys: float) -> float:
    """Least HBM traffic of the full layers' forwards."""
    return _row_bytes(m, "full", queries, keys)


def full_flash_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The full layers' flash forward over the causal pairs of a traced
    step's live rows (the record's ``attention_pairs``)."""
    return flash_fwd_pair_flops(m, step["attention_pairs"])


def full_flash_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    return flash_fwd_row_bytes(m, step["positions_live"],
                               step["attention_keys"])
