"""The dots3-note family (``model_type: dots3_note``, dots3-note-prev's
language model): ``models/llama.py``'s one block with latent attention at
two geometries as its operators, ``full_attention`` layers whose query
attends the ``index_topk`` keys a learned indexer chooses
(``indexed_latent_attention``) and ``sliding_attention`` layers that see a
window of ``sliding_window_size`` keys (``window_latent_attention``), a
head-wise sigmoid gate on both, a leading dense layer, then
``models/moe.py``'s routed experts under a sigmoid router with a
bias-corrected choice beside one shared expert, of which this chip holds a
share; at a configuration file's sizes, served by
``serve/llm.py::LlamaGenerator``, checked against
``reference/dots3_note.py``.

The share is ``families/deepseek_v2.py``'s: ``n_routed_experts`` is how
many experts of each routed layer are held here (listed in the file's
``reduced``), ``expert_share`` gives ``of``, the published count and the
router's width, and ``first``, the first held expert.

It gives what ``families/dense_decoder.py``'s docstring lists, and beside
it what its readers ask for: ``expert_ffn_flops`` and ``expert_ffn_bytes``
over the routed layers' held experts, and for each of its three kernels
the FLOPs and the least bytes of a traced step from the step's record
(``harness/steprecord.py``): ``sparse_flash_*`` and ``window_flash_*``
over the (query, key) pairs the operator KEEPS, ``index_scores_*`` over
the causal pairs, all of which an indexer has to score. Importing this
module imports no jax: the harness process and the readers load it too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families.lfm2_moe import _config_fields
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "dots3_note"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "intermediate_size": "dense_mlp_hidden",  # the leading dense layer's
    "moe_intermediate_size": "mlp_hidden",    # ONE expert's width
    "num_hidden_layers": "num_layers",
    "first_k_dense_replace": "num_dense_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "rms_eps", "rope_theta": "rope_theta",
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "sliding_window_size": "sliding_window",
    "swa_num_attention_heads": "swa_num_heads",
    "swa_q_lora_rank": "swa_q_lora_rank",
    "swa_kv_lora_rank": "swa_kv_lora_rank",
    "swa_qk_nope_head_dim": "swa_qk_nope_head_dim",
    "swa_qk_rope_head_dim": "swa_qk_rope_head_dim",
    "swa_v_head_dim": "swa_v_head_dim", "swa_rope_theta": "swa_rope_theta",
    "index_n_heads": "index_heads", "index_head_dim": "index_head_dim",
    "index_topk": "index_topk",
    "apply_mla_qkv_lora_rescale": "latent_rescale",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "tie_word_embeddings": "tie_embeddings",
}
# keys the family reads itself: the share, the pattern, the drawn bias
OWN_KEYS = ("n_routed_experts", "expert_share", "layer_types",
            "expert_bias_init_std")
# what `build_config` sets beside the mapped keys: from OWN_KEYS, and what
# is modeling code and no key (the file states each under `assumed`)
BUILT = ("num_experts", "experts_held", "layer_types")
MODELING = {"router_scores": "sigmoid", "router_bias": True,
            "router_norm_eps": 1e-20, "head_gate": True}
# the program's operator for each published one
LAYER_TYPES = {"full_attention": "indexed_latent_attention",
               "sliding_attention": "window_latent_attention"}
# published keys held to the one value that the program computes
HELD = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "attention_gate_type": "headwise",
        "swa_attention_gate_type": "headwise", "rope_scaling": None}
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key. A
    checkout whose ``LlamaConfig`` lacks the fields fails here, at once."""
    name = m.get("name")
    known = (set(MODEL_KEYS) | set(OWN_KEYS) | set(HELD)
             | {"swa_num_key_value_heads"} | set(BOOKKEEPING_KEYS))
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"configuration {name!r}: the dots3_note family "
                         f"does not understand {unknown}")
    missing = sorted(known - set(BOOKKEEPING_KEYS) - set(m))
    if missing:
        raise ValueError(f"configuration {name!r} lacks {missing}")
    lacking = sorted((set(MODEL_KEYS.values()) | set(BUILT) | set(MODELING))
                     - _config_fields())
    if lacking:
        raise ValueError(f"this checkout's LlamaConfig has no {lacking}: "
                         "models/llama.py cannot build this family")
    if "index_keys_kept" not in getattr(LlamaGenerator, "STEP_COUNTERS", ()):
        raise ValueError("this checkout's serve/llm.py counts no keys that "
                         "an indexer kept: it cannot serve this family")
    for key, only in HELD.items():
        if m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: the program computes "
                             f"{only!r} only")
    for pre in ("", "swa_"):
        if m[pre + "num_key_value_heads"] != m[pre + "num_attention_heads"]:
            raise ValueError(pre + "num_key_value_heads: latent attention "
                             "gives every query head its own key and value")
        if not m[pre + "q_lora_rank"]:
            raise ValueError(f"{pre}q_lora_rank {m[pre + 'q_lora_rank']!r}: "
                             "the program's latent attention has low-rank "
                             "queries only")
    types = m["layer_types"]
    if len(types) != m["num_hidden_layers"]:
        raise ValueError(f"layer_types names {len(types)} layers, "
                         f"num_hidden_layers is {m['num_hidden_layers']}")
    strange = sorted(set(types) - set(LAYER_TYPES))
    if strange:
        raise ValueError(f"layer_types {strange}: expected some of "
                         f"{list(LAYER_TYPES)}")
    if not 0 <= m["first_k_dense_replace"] <= m["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace must lie in "
                         "0..num_hidden_layers")
    if m["sliding_window_size"] < 1 or m["index_topk"] < 1:
        raise ValueError("sliding_window_size and index_topk count keys: "
                         "at least 1 each")
    if not 0 < m["qk_rope_head_dim"] <= m["index_head_dim"]:
        raise ValueError("the indexer rotates its first qk_rope_head_dim "
                         "dims: index_head_dim cannot be narrower")
    share = m["expert_share"]
    if not isinstance(share, dict) or set(share) != {"first", "of"}:
        raise ValueError(f"expert_share {share!r}: expected first and of")
    of, first, held = share["of"], share["first"], m["n_routed_experts"]
    if not (0 <= first and 0 < held <= of - first) or of % held \
            or first % held:
        raise ValueError(f"expert_share: {held} experts from {first} of "
                         f"{of} is no whole share of them")
    if not 0 < m["num_experts_per_tok"] <= of:
        raise ValueError("num_experts_per_tok must lie in 1..the router's "
                         "width")


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    for theta in ("rope_theta", "swa_rope_theta"):
        kwargs[theta] = float(kwargs[theta])
    share, held = m["expert_share"], m["n_routed_experts"]
    kwargs["num_experts"] = share["of"]
    if held < share["of"]:
        kwargs["experts_held"] = (share["first"], held)
    kwargs["layer_types"] = tuple(LAYER_TYPES[t] for t in m["layer_types"])
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
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
        seq_bucket=engine["seq_bucket"], seed=seed % (2 ** 31),
        expert_bias_std=m["expert_bias_init_std"],
        # how many chips share a layer's experts: a whole number by `check`
        expert_shares=m["expert_share"]["of"] // m["n_routed_experts"])


def with_expert_bias(params, std: float, seed: int, shares: int):
    """``params`` with every routed layer's expert bias drawn from
    ``seed`` at deviation ``std``, in the leaf's type, as
    ``families/lfm2_moe.py::with_expert_bias`` draws it (the program starts
    it at zeros and training moves it; each layer gets the same ``E``
    values, the evenly spaced quantiles of a normal, in an order of its
    own), but dealt so that the ``shares`` chips which share a layer hold
    alike: the sorted values are cut into runs of ``shares`` neighbours,
    each run is dealt one value a share in an order drawn from the seed,
    and a share's values lie in an order of their own. Dealt without that,
    the 32 values this chip's experts get are a sample of the 256 whose
    mean wanders by a sixth of ``std`` from seed to seed, the share of the
    routers' pairs that land here with it (19 times a bias in relative
    terms, at the tail where 8 of 256 are chosen) and the longest step's
    time by 1.3 % (six runs, PERF.md section 6, PR 43), which is the
    cell's ``serve_gap_p95_ms``; the seeds are there to vary the weights
    and the prompts, not this chip's share of the load, and balancing
    across the chips is what training's rule is for."""
    import zlib

    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(seed), 24)

    def draw(path, leaf):
        if path[-1].key != "router_bias":
            return leaf
        layers, experts = leaf.shape
        values = std * jax.scipy.special.ndtri(
            (jnp.arange(experts, dtype=jnp.float32) + 0.5) / experts)
        runs = values.reshape(experts // shares, shares)

        def one_layer(k):
            deal, order = jax.random.split(k)
            # a run's values to the shares, then a share's values in order
            dealt = jax.vmap(jax.random.permutation)(
                jax.random.split(deal, runs.shape[0]), runs)
            mine = jax.vmap(jax.random.permutation)(
                jax.random.split(order, shares), dealt.T)
            return mine.reshape(experts)

        kind = jax.random.fold_in(key, zlib.crc32(str(path).encode()))
        return jax.vmap(one_layer)(jax.random.split(kind, layers)).astype(
            leaf.dtype)
    return jax.tree_util.tree_map_with_path(draw, params)


class Served(LlamaGenerator):
    """The program's class, with the routers' bias drawn from the seed
    (``with_expert_bias``: the program starts it at zeros, which would
    leave the bias-corrected choice unexercised)."""

    def __init__(self, *, expert_bias_std: float = 0.0,
                 expert_shares: int = 1, **kwargs):
        super().__init__(**kwargs)
        self._params = with_expert_bias(self._params, expert_bias_std,
                                        kwargs["seed"], expert_shares)


# ---------------------------------------------------------------- counts
def layer_counts(m: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each half: ``indexed`` or ``window``, and
    ``dense`` or ``routed``."""
    types, dense = m["layer_types"], m["first_k_dense_replace"]
    return {"indexed": types.count("full_attention"),
            "window": types.count("sliding_attention"),
            "dense": dense, "routed": len(types) - dense}


def _latent(m: Dict[str, Any], pre: str) -> int:
    """Latent attention at one geometry (``pre`` is ``""`` or ``"swa_"``):
    its five projections, its two inner norms and its head-wise gate."""
    h, heads = m["hidden_size"], m[pre + "num_attention_heads"]
    qr, kvr = m[pre + "q_lora_rank"], m[pre + "kv_lora_rank"]
    nope, rope, vd = (m[pre + "qk_nope_head_dim"],
                      m[pre + "qk_rope_head_dim"], m[pre + "v_head_dim"])
    return (h * qr + qr + qr * heads * (nope + rope) + h * (kvr + rope) + kvr
            + kvr * heads * (nope + vd) + heads * vd * h + h * heads)


def part_params(m: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's parts, by the names of ``layer_counts``:
    an indexed operator is latent attention at the full geometry with the
    indexer (its queries, its one key and that key's LayerNorm, its heads'
    weights), a routed feed-forward is counted as it is held here (the
    held experts, the shared ones, the router over all and its bias)."""
    h = m["hidden_size"]
    ih, ihd = m["index_n_heads"], m["index_head_dim"]
    expert = 3 * h * m["moe_intermediate_size"]
    of = m["expert_share"]["of"]
    return {
        "indexed": (_latent(m, "") + m["q_lora_rank"] * ih * ihd + h * ihd
                    + 2 * ihd + h * ih),
        "window": _latent(m, "swa_"),
        "dense": 3 * h * m["intermediate_size"],
        "routed": ((m["n_routed_experts"] + m["n_shared_experts"]) * expert
                   + h * of + of),
    }


def num_params(m: Dict[str, Any]) -> int:
    """Parameters resident here: a share's experts count as the share."""
    h, parts, counts = m["hidden_size"], part_params(m), layer_counts(m)
    tied = 1 if m["tie_word_embeddings"] else 2
    return (sum(n * parts[part] for part, n in counts.items())
            + m["num_hidden_layers"] * 2 * h
            + tied * m["vocab_size"] * h + h)


def held_share(m: Dict[str, Any]) -> float:
    """The share of a symmetric router's pairs that land on held experts."""
    return m["n_routed_experts"] / m["expert_share"]["of"]


def expert_ffn_flops(m: Dict[str, Any], positions: int) -> float:
    """What the three grouped matmuls of every routed layer need HERE for
    ``positions`` positions of one forward pass, as
    ``families/deepseek_v2.py::expert_ffn_flops`` counts it: each position
    makes ``num_experts_per_tok`` pairs over all the experts, of which a
    symmetric router sends ``held_share`` to the held ones, each pair
    three matmuls of hidden x ``moe_intermediate_size``."""
    return (layer_counts(m)["routed"] * positions * m["num_experts_per_tok"]
            * held_share(m) * 3 * 2.0 * m["hidden_size"]
            * m["moe_intermediate_size"])


def expert_ffn_bytes(m: Dict[str, Any], met: float = None) -> float:
    """Least HBM traffic of those matmuls in one forward pass: the three
    matrices of each of the ``met`` held experts that a position met
    (every held expert of every routed layer where the program does not
    say) read once, in the parameters' type."""
    size = BYTES[m.get("program", {}).get("param_dtype", "float32")]
    if met is None:
        met = layer_counts(m)["routed"] * m["n_routed_experts"]
    return met * 3.0 * m["hidden_size"] * m["moe_intermediate_size"] * size


# ------------------------------------- the three kernels' need, a step
def kept_pairs(step: Dict[str, Any], most: int) -> float:
    """The (query, key) pairs a step's live queries keep when a query
    keeps at most ``most`` of its causal keys (a window's width, an
    indexer's ``index_topk``): a row of ``n`` positions keeps ``sum_t
    min(t, most) = n most - most (most - 1) / 2`` once ``n >= most``, so a
    step whose every row is that long and re-runs its whole prefix keeps
    ``positions_live x most - rows x most (most - 1) / 2``, from the
    step's record alone. Of a shorter row the formula counts fewer than it
    keeps (by ``(most - n)(most - n - 1) / 2``), and of a step that
    computes fewer positions than its rows hold fewer still: a share of a
    roofline reckoned from it errs low and never over."""
    return max(0.0, step["positions_live"] * most
               - step["rows"] * most * (most - 1) / 2.0)


def _flash_flops(m: Dict[str, Any], pre: str, layers: int,
                 pairs: float) -> float:
    """A score over the query/key width and a weighted value over the
    value width a head and pair, 2 FLOP a multiply-add."""
    width = (m[pre + "qk_nope_head_dim"] + m[pre + "qk_rope_head_dim"]
             + m[pre + "v_head_dim"])
    return layers * m[pre + "num_attention_heads"] * 2.0 * width * pairs


def _flash_bytes(m: Dict[str, Any], pre: str, layers: int,
                 step: Dict[str, Any]) -> float:
    """bf16: a live query's q at the whole query width and its o at the
    value width a head; a key position's ``k_nope`` and ``v`` a head and
    its rotary key ONCE, not a head. What tells the kernel which keys are
    kept is left out (a lower bound stays a lower bound)."""
    nope, rope, vd = (m[pre + "qk_nope_head_dim"],
                      m[pre + "qk_rope_head_dim"], m[pre + "v_head_dim"])
    heads = m[pre + "num_attention_heads"]
    elems = (step["positions_live"] * heads * ((nope + rope) + vd)
             + step["attention_keys"] * (heads * (nope + vd) + rope))
    return layers * 2.0 * elems


def sparse_flash_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The indexed layers' flash forward over the pairs the indexer's
    choice keeps (``kept_pairs`` at ``index_topk``)."""
    return _flash_flops(m, "", layer_counts(m)["indexed"],
                        kept_pairs(step, m["index_topk"]))


def sparse_flash_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    return _flash_bytes(m, "", layer_counts(m)["indexed"], step)


def window_flash_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The window layers' flash forward over the pairs inside the window
    (``kept_pairs`` at ``sliding_window_size``)."""
    return _flash_flops(m, "swa_", layer_counts(m)["window"],
                        kept_pairs(step, m["sliding_window_size"]))


def window_flash_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    return _flash_bytes(m, "swa_", layer_counts(m)["window"], step)


def index_scores_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The indexed layers' index scores: every causal pair of the live
    queries (the record's ``attention_pairs``) has to be scored before a
    choice can be made, ``index_n_heads`` products over ``index_head_dim``
    a pair, 2 FLOP a multiply-add; the ReLU and the weighted sum are not
    counted."""
    return (layer_counts(m)["indexed"] * step["attention_pairs"] * 2.0
            * m["index_n_heads"] * m["index_head_dim"])


def index_scores_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """Least HBM traffic of those scores: a live query's index queries
    (bf16) and heads' weights (float32), a key position's ONE index key
    (bf16), and the score of every causal pair written once in float32,
    which is what the choice reads."""
    ih, ihd = m["index_n_heads"], m["index_head_dim"]
    return layer_counts(m)["indexed"] * (
        step["positions_live"] * (2.0 * ih * ihd + 4.0 * ih)
        + step["attention_keys"] * 2.0 * ihd
        + step["attention_pairs"] * 4.0)
