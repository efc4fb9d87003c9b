"""The Keye family (``model_type: KeyeVL2``, Keye-VL-2.0-30B-A3B's language
model): ``models/llama.py``'s one block with ``indexed_attention`` as its
operator in every layer (grouped-query attention, ``num_attention_heads``
query heads on ``num_key_value_heads`` key/value heads, an RMSNorm a head
over q and k, multi-axis rope by ``rope_scaling.mrope_section``, and a
query attending the ``sa_config.topk`` keys that a learned indexer of
``indexer_num_heads`` heads of ``indexer_head_dim`` against ONE key a
position chooses), then ``models/moe.py``'s routed experts under a softmax
router whose chosen weights are renormalised, no shared expert; at a
configuration file's sizes, served by ``serve/llm.py::LlamaGenerator``,
checked against ``reference/keye.py``. The vision tower is not in the
catalog's ``config`` and is not here.

It gives what ``families/dense_decoder.py``'s docstring lists but
``training`` (no cell trains it, and the flash forward under a choice has
no backward: a training cell brings that with it), and beside it what its
readers ask for: ``expert_ffn_flops`` and ``expert_ffn_bytes``
for the expert feed-forward's share of its roofline, and for its two
attention kernels the FLOPs and the least bytes of a traced step from the
step's record (``harness/steprecord.py``): ``index_scores_*`` over the
causal pairs, all of which an indexer has to score, and ``sparse_flash_*``
over the (query, key) pairs the choice KEEPS. Importing this module imports
no jax: the harness process and the readers load it too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families.dots3_note import kept_pairs
from benchmark.families.lfm2_moe import _config_fields
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "keye"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "moe_intermediate_size": "mlp_hidden",    # ONE expert's width
    "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "rms_eps", "rope_theta": "rope_theta",
    "num_experts": "num_experts", "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob",
    "tie_word_embeddings": "tie_embeddings",
}
# keys the family reads itself: the indexer's and the rope's groups
OWN_KEYS = ("sa_config", "rope_scaling")
# what `build_config` sets beside the mapped keys: from OWN_KEYS, and what
# is modeling code and no key (the file states it under `assumed`)
BUILT = ("layer_types", "index_heads", "index_head_dim", "index_topk",
         "mrope_section")
MODELING = {"qk_head_norm": True, "router_scores": "softmax"}
# published keys held to the one value that the program computes; the last
# four are inert under the first two (no dense layer, no window) and carried
HELD = {"attention_bias": False, "hidden_act": "silu",
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "use_sliding_window": False, "sliding_window": None}
# carried and read by nobody: the width of a dense layer the model does not
# have, the repeat of num_experts, the window's first layer
INERT = ("intermediate_size", "num_local_experts", "max_window_layers")
SA_KEYS = {"indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads",
           "kv_chunk_size", "q_chunk_size", "topk"}
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key. A
    checkout whose program has no indexer on grouped-query attention or no
    multi-axis rope fails here, at once."""
    name = m.get("name")
    known = (set(MODEL_KEYS) | set(OWN_KEYS) | set(HELD) | set(INERT)
             | set(BOOKKEEPING_KEYS))
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"configuration {name!r}: the keye family does "
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
    if m["num_local_experts"] != m["num_experts"]:
        raise ValueError("num_local_experts repeats num_experts")
    if m["num_attention_heads"] % m["num_key_value_heads"]:
        raise ValueError("the query heads share the key/value heads in "
                         "whole groups")
    if not 0 < m["num_experts_per_tok"] <= m["num_experts"]:
        raise ValueError("num_experts_per_tok must lie in 1..num_experts")
    sa = m["sa_config"]
    if not isinstance(sa, dict) or set(sa) != SA_KEYS:
        raise ValueError(f"sa_config {sa!r}: expected {sorted(SA_KEYS)}")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("indexer_num_kv_heads: the program's indexer has "
                         "ONE key a position")
    if sa["topk"] < 1 or sa["indexer_head_dim"] % 2:
        raise ValueError("sa_config: topk counts keys (at least 1) and the "
                         "indexer's head turns in whole pairs")
    rope = m["rope_scaling"]
    if (not isinstance(rope, dict)
            or set(rope) != {"mrope_section", "rope_type", "type"}
            or rope["rope_type"] != "default" or rope["type"] != "default"):
        raise ValueError(f"rope_scaling {rope!r}: expected mrope_section "
                         "under rope_type and type `default`")
    sections = rope["mrope_section"]
    if len(sections) != 3 or sum(sections) != m["head_dim"] // 2:
        raise ValueError(f"mrope_section {sections!r}: three counts that "
                         f"sum to head_dim / 2 = {m['head_dim'] // 2}")


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    sa = m["sa_config"]
    kwargs.update(
        rope_theta=float(m["rope_theta"]),
        layer_types=("indexed_attention",) * m["num_hidden_layers"],
        index_heads=sa["indexer_num_heads"],
        index_head_dim=sa["indexer_head_dim"], index_topk=sa["topk"],
        mrope_section=tuple(m["rope_scaling"]["mrope_section"]))
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
    # the RMSNorm a head over q and k and the softmax before the top-k are
    # modeling code, not keys of config.json: assumed (the file's `assumed`
    # says why)
    return LlamaConfig(**MODELING, **kwargs)


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
def part_params(m: Dict[str, Any], *, active: bool = False
                ) -> Dict[str, int]:
    """Parameters of one layer's parts: attention (its four projections
    and the two head norms), the indexer (its queries, its one key with a
    LayerNorm's weight and bias, its heads' weights), a routed feed-forward
    (the router and the experts; ``active``: the ones a position meets),
    the block's two norms."""
    h, d = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    sa = m["sa_config"]
    ih, ihd = sa["indexer_num_heads"], sa["indexer_head_dim"]
    experts = m["num_experts_per_tok"] if active else m["num_experts"]
    return {"attention": h * (q + 2 * kv) + q * h + 2 * d,
            "indexer": h * ih * ihd + h * ihd + 2 * ihd + h * ih,
            "routed": (h * m["num_experts"]
                       + experts * 3 * h * m["moe_intermediate_size"]),
            "norms": 2 * h}


def num_params(m: Dict[str, Any], *, active: bool = False) -> int:
    tied = 1 if m["tie_word_embeddings"] else 2
    return (m["num_hidden_layers"] * sum(part_params(m, active=active
                                                     ).values())
            + tied * m["vocab_size"] * m["hidden_size"] + m["hidden_size"])


def expert_ffn_flops(m: Dict[str, Any], positions: int) -> float:
    """What the three grouped matmuls of every layer need for ``positions``
    positions of one forward pass: each position meets
    ``num_experts_per_tok`` experts, each three matmuls of hidden x
    ``moe_intermediate_size``, 2 FLOP a multiply-add. The router, the sort,
    the gathers and the weighted sum are not counted."""
    return (m["num_hidden_layers"] * positions * m["num_experts_per_tok"]
            * 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"])


def expert_ffn_bytes(m: Dict[str, Any], met: float = None) -> float:
    """Least HBM traffic of those matmuls in one forward pass: the three
    matrices of each of the ``met`` experts that a position met (summed
    over the layers; every expert of every layer where the program does
    not say) read once, in the parameters' type. The rows in and out are
    left out (a lower bound stays a lower bound)."""
    size = BYTES[m.get("program", {}).get("param_dtype", "float32")]
    if met is None:
        met = m["num_hidden_layers"] * m["num_experts"]
    return (met * 3.0 * m["hidden_size"] * m["moe_intermediate_size"]
            * size)


# ------------------------------------------ the two kernels' need, a step
def index_scores_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """Every layer's index scores: every causal pair of the live queries
    (the record's ``attention_pairs``) has to be scored before a choice can
    be made, ``indexer_num_heads`` products over ``indexer_head_dim`` a
    pair, 2 FLOP a multiply-add; the ReLU and the weighted sum are not
    counted."""
    sa = m["sa_config"]
    return (m["num_hidden_layers"] * step["attention_pairs"] * 2.0
            * sa["indexer_num_heads"] * sa["indexer_head_dim"])


def index_scores_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """Least HBM traffic of those scores: a live query's index queries
    (bf16) and heads' weights (float32), a key position's ONE index key
    (bf16), and the score of every causal pair written once in float32,
    which is what the choice reads."""
    sa = m["sa_config"]
    ih, ihd = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return m["num_hidden_layers"] * (
        step["positions_live"] * (2.0 * ih * ihd + 4.0 * ih)
        + step["attention_keys"] * 2.0 * ihd
        + step["attention_pairs"] * 4.0)


def sparse_flash_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """Every layer's flash forward over the pairs the indexer's choice
    KEEPS (``families/dots3_note.py::kept_pairs`` at ``sa_config.topk``:
    exact for a step whose every row is at least ``topk`` long and re-runs
    its whole prefix, a count that errs low for any other): a score and a
    weighted value over ``head_dim`` a query head and pair, 2 FLOP a
    multiply-add."""
    return (m["num_hidden_layers"] * kept_pairs(step, m["sa_config"]["topk"])
            * 2 * 2.0 * m["num_attention_heads"] * m["head_dim"])


def sparse_flash_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """bf16: q and o once a live query at the query heads, k and v once a
    key position at the key/value heads (a group's eight query heads read
    one key head; read once is the least). What tells the kernel which
    keys are kept is left out (a lower bound stays a lower bound)."""
    elems = m["head_dim"] * (
        2 * m["num_attention_heads"] * step["positions_live"]
        + 2 * m["num_key_value_heads"] * step["attention_keys"])
    return m["num_hidden_layers"] * 2.0 * elems
