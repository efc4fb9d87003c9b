"""The Ling-3.0 family (``model_type: bailing_hybrid``, Ling-3.0-flash):
``models/llama.py``'s one block under a layer pattern of Kimi delta
attention (``"kda"``: one in-projection, depthwise causal taps and a SiLU
over the queries, keys and values, L2 norms, a bounded decay a channel, the
delta rule of ``ops/kda.py`` in chunks of ``kda_chunk_size``, an RMSNorm a
head under a sigmoid gate, out-projection) in five layers of every
``layer_group_size`` and latent attention with FULL-RANK queries
(``q_lora_rank`` null) and a head-wise gate in the sixth; leading dense
layers, then ``models/moe.py``'s routed experts beside one shared expert
under DeepSeek-V3's ``noaux_tc`` router (sigmoid scores, a bias on the
choice, groups scored by the sum of their two best), of which this chip
holds a share; at a configuration file's sizes, served by
``serve/llm.py::LlamaGenerator``, checked against
``reference/bailing_hybrid.py``.

The share is ``families/deepseek_v2.py``'s: ``num_experts`` is how many
experts of each routed layer are held here (listed in the file's
``reduced``), ``expert_share`` gives ``of``, the published count and the
router's width, and ``first``, the first held expert.

It gives the serving side of what ``families/dense_decoder.py``'s docstring
lists (``check``, ``Served``, ``served_kwargs``, ``REFERENCE``,
``num_params``; no ``training``: the delta rule's kernel has no backward
and no cell trains this model), and beside it what its readers ask for:
the delta rule's FLOPs and least bytes of a traced step from the step's
record (``kda_chunk_flops``, ``kda_chunk_bytes``: over the step's LIVE
positions), the flash forward's over the latent layers alone
(``flash_fwd_pair_flops``, ``flash_fwd_row_bytes``) and
``expert_ffn_flops`` and ``expert_ffn_bytes`` over the routed layers' held
experts. Importing this module imports no jax: the harness process and the
readers load it too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families.dots3_note import with_expert_bias
from benchmark.families.lfm2_moe import _config_fields
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "bailing_hybrid"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "intermediate_size": "dense_mlp_hidden",  # the leading dense layers'
    "moe_intermediate_size": "mlp_hidden",    # ONE expert's width
    "num_hidden_layers": "num_layers",
    "first_k_dense_replace": "num_dense_layers",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "max_position_embeddings": "max_seq_len",
    "rms_norm_eps": "rms_eps", "rope_theta": "rope_theta",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "short_conv_kernel_size": "kda_conv_kernel",
    "kda_lower_bound": "kda_lower_bound",
    "num_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "experts_per_token",
    "n_group": "router_groups", "topk_group": "router_topk_groups",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "tie_word_embeddings": "tie_embeddings",
}
# keys the family reads itself: the share, the pattern's period, the drawn
# bias, the kernel's chunk (the last three are this benchmark's keys)
OWN_KEYS = ("num_experts", "expert_share", "layer_group_size",
            "expert_bias_init_std", "kda_chunk_size")
# what `build_config` sets beside the mapped keys: from OWN_KEYS, and what
# is modeling code and no key (the file states each under `assumed`)
BUILT = ("num_experts", "experts_held", "layer_types", "kda_heads",
         "kda_head_dim", "kda_chunk", "q_lora_rank")
MODELING = {"router_scores": "sigmoid", "router_bias": True,
            "router_norm_eps": 1e-20, "router_group_score": "top2",
            "head_gate": True}
# published keys held to the one value that the program computes
HELD = {"gated_attention_proj_granularity_type": "head_wise",
        "group_norm_size": 1, "hidden_act": "silu", "kda_safe_gate": True,
        "linear_silu": True, "moe_router_enable_expert_bias": True,
        "no_kda_lora": True, "use_kda_lora": False,
        "num_kv_heads_for_linear_attn": 0, "partial_rotary_factor": 0.5,
        "q_lora_rank": None, "rope_interleave": True, "rope_scaling": None,
        "scale_router_input": False, "score_function": "sigmoid",
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "up_proj_norm": False, "use_bias": False, "use_mla_nope": False,
        "use_nGPT": False, "use_qk_norm": True, "use_qkv_bias": False,
        "value_norm": False}
# published and sizing nothing here: the multi-token-prediction module and
# the auxiliary loss are left out, `max_window_layers` is read by nothing,
# the three widths below are checked against the keys that size, and the
# two lists of SwiGLU clamps are held to 0 as deep as the cut goes
OTHER = ("max_window_layers", "mtp_loss_scaling_factor", "mtp_use_kda",
         "num_nextn_predict_layers", "seq_aux", "qk_head_dim", "rotary_dim",
         "moe_shared_expert_intermediate_size", "expert_swiglu_limit_list",
         "share_expert_swiglu_limit_list")
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key. A
    checkout whose ``LlamaConfig`` lacks the fields fails here, at once."""
    name = m.get("name")
    known = (set(MODEL_KEYS) | set(OWN_KEYS) | set(HELD) | set(OTHER)
             | set(BOOKKEEPING_KEYS))
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"configuration {name!r}: the bailing_hybrid "
                         f"family does not understand {unknown}")
    missing = sorted(known - set(BOOKKEEPING_KEYS) - set(m))
    if missing:
        raise ValueError(f"configuration {name!r} lacks {missing}")
    lacking = sorted((set(MODEL_KEYS.values()) | set(BUILT) | set(MODELING))
                     - _config_fields())
    if lacking:
        raise ValueError(f"this checkout's LlamaConfig has no {lacking}: "
                         "models/llama.py cannot build this family")
    if "kda_chunks_run" not in getattr(LlamaGenerator, "STEP_COUNTERS", ()):
        raise ValueError("this checkout's serve/llm.py counts no chunks of "
                         "a delta rule: it cannot serve this family")
    for key, only in HELD.items():
        if m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: the program computes "
                             f"{only!r} only")
    layers = m["num_hidden_layers"]
    if m["num_key_value_heads"] != m["num_attention_heads"]:
        raise ValueError("num_key_value_heads: latent attention and the "
                         "delta rule give every query head its own key and "
                         "value")
    if m["qk_head_dim"] != m["qk_nope_head_dim"] + m["qk_rope_head_dim"] \
            or m["rotary_dim"] != m["qk_rope_head_dim"]:
        raise ValueError("qk_head_dim is qk_nope_head_dim + "
                         "qk_rope_head_dim, and rotary_dim the latter")
    if m["moe_shared_expert_intermediate_size"] \
            != m["num_shared_experts"] * m["moe_intermediate_size"]:
        raise ValueError("moe_shared_expert_intermediate_size: the program's "
                         "shared experts are num_shared_experts times one "
                         "expert's width")
    if m["head_dim"] % 128 or m["kda_chunk_size"] % 16 \
            or m["short_conv_kernel_size"] < 2:
        raise ValueError("the delta rule's kernel takes heads of whole lane "
                         "tiles (head_dim a multiple of 128) and chunks of "
                         "whole blocks (kda_chunk_size a multiple of 16); "
                         "short_conv_kernel_size counts taps (at least 2)")
    if not -5.0 <= m["kda_lower_bound"] < 0:
        raise ValueError(f"kda_lower_bound {m['kda_lower_bound']!r}: the "
                         "kernel's decays are safe down to -5 a position")
    if not 0 < m["layer_group_size"] or not \
            0 <= m["first_k_dense_replace"] <= layers:
        raise ValueError("layer_group_size counts layers and "
                         "first_k_dense_replace lies in 0..num_hidden_layers")
    for clamps in ("expert_swiglu_limit_list",
                   "share_expert_swiglu_limit_list"):
        if len(m[clamps]) < layers or any(m[clamps][:layers]):
            raise ValueError(
                f"{clamps}: a layer within the first {layers} clamps its "
                "SwiGLU, and the clamp's form is not in the published "
                "config: this family computes the unclamped layers only")
    share = m["expert_share"]
    if not isinstance(share, dict) or set(share) != {"first", "of"}:
        raise ValueError(f"expert_share {share!r}: expected first and of")
    of, first, held = share["of"], share["first"], m["num_experts"]
    if not (0 <= first and 0 < held <= of - first) or of % held \
            or first % held:
        raise ValueError(f"expert_share: {held} experts from {first} of "
                         f"{of} is no whole share of them")
    if of % m["n_group"] or not 0 < m["topk_group"] <= m["n_group"]:
        raise ValueError(f"{of} experts in {m['n_group']} groups, "
                         f"{m['topk_group']} kept")
    if not 0 < m["num_experts_per_tok"] <= of:
        raise ValueError("num_experts_per_tok must lie in 1..the router's "
                         "width")


def layer_types(m: Dict[str, Any]):
    """The program's operator of each layer: latent attention where ``(l +
    1) % layer_group_size == 0``, else Kimi delta attention."""
    return tuple("latent_attention" if (l + 1) % m["layer_group_size"] == 0
                 else "kda" for l in range(m["num_hidden_layers"]))


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    for scalar in ("rope_theta", "kda_lower_bound", "routed_scaling_factor"):
        kwargs[scalar] = float(kwargs[scalar])
    share, held = m["expert_share"], m["num_experts"]
    kwargs["num_experts"] = share["of"]
    if held < share["of"]:
        kwargs["experts_held"] = (share["first"], held)
    kwargs["layer_types"] = layer_types(m)
    kwargs.update(kda_heads=m["num_attention_heads"],
                  kda_head_dim=m["head_dim"], kda_chunk=m["kda_chunk_size"],
                  q_lora_rank=0)
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
    return LlamaConfig(**MODELING, **kwargs)


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
        expert_shares=m["expert_share"]["of"] // m["num_experts"])


class Served(LlamaGenerator):
    """The program's class, with the routers' bias drawn from the seed and
    dealt alike to the chips that share a layer
    (``families/dots3_note.py::with_expert_bias``: the program starts it at
    zeros, which would leave the bias-corrected choice unexercised). The
    decay gate's leaves are the program's initialiser's, which draws them
    from the seed for the memory they give (``models/llama.py::init_llama``;
    the configuration's ``assumed`` says how)."""

    def __init__(self, *, expert_bias_std: float = 0.0,
                 expert_shares: int = 1, **kwargs):
        super().__init__(**kwargs)
        self._params = with_expert_bias(self._params, expert_bias_std,
                                        kwargs["seed"], expert_shares)


# ---------------------------------------------------------------- counts
def layer_counts(m: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each half: ``kda`` or ``latent``, and ``dense``
    or ``routed``."""
    types, dense = layer_types(m), m["first_k_dense_replace"]
    return {"kda": types.count("kda"),
            "latent": types.count("latent_attention"),
            "dense": dense, "routed": len(types) - dense}


def part_params(m: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's parts, by the names of ``layer_counts``. A
    delta-rule operator: the in-projection to ``[q | k | v | a | z]`` (five
    full-rank matrices: ``no_kda_lora``), ``W_beta``, the taps over ``[q |
    k | v]``, ``A_log`` a head, ``dt_bias`` a channel, the head norm's one
    weight a channel of a head, the out-projection. Latent attention: ONE
    query matrix (no rank, no norm), the compression with its norm, the
    decompression, the out-projection, the head-wise gate. A routed
    feed-forward is counted as it is held here (the held experts, the
    shared ones, the router over all and its bias)."""
    h, heads, hd = m["hidden_size"], m["num_attention_heads"], m["head_dim"]
    inner = heads * hd
    kvr, nope, rope, vd = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                           m["qk_rope_head_dim"], m["v_head_dim"])
    expert = 3 * h * m["moe_intermediate_size"]
    of = m["expert_share"]["of"]
    return {
        "kda": (5 * h * inner + h * heads
                + 3 * inner * m["short_conv_kernel_size"] + heads + inner
                + hd + inner * h),
        "latent": (h * heads * (nope + rope) + h * (kvr + rope) + kvr
                   + kvr * heads * (nope + vd) + heads * vd * h + h * heads),
        "dense": 3 * h * m["intermediate_size"],
        "routed": ((m["num_experts"] + m["num_shared_experts"]) * expert
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
    return m["num_experts"] / m["expert_share"]["of"]


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
        met = layer_counts(m)["routed"] * m["num_experts"]
    return met * 3.0 * m["hidden_size"] * m["moe_intermediate_size"] * size


# ---------------------------------- the delta rule's kernel, a traced step
def kda_flops_a_position(m: Dict[str, Any]) -> float:
    """What ONE operator's delta rule needs for a position in the chunked
    form, 2 FLOP a multiply-add, a head: its rows of the two ``[C, C]``
    matrices (``k k^T`` and ``q k^T`` under the decays: the chunked form
    computes the squares and the count keeps to what the form needs), its
    rows of ``q S_0`` and ``k S_0`` and of the state's update, each
    ``head_dim x head_dim``, and its row of ``A_qk v'``. The decays'
    exponentials and the triangular solve are the vector unit's and are
    NOT counted."""
    heads, d, c = m["num_attention_heads"], m["head_dim"], m["kda_chunk_size"]
    return 2.0 * heads * (3 * c * d + 3 * d * d)


def kda_chunk_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The delta-rule layers' kernels over a traced step's LIVE positions
    (its record's ``positions_live``): a kernel that runs a row's padding
    too reads low by the padding's share, which is the truth."""
    return (layer_counts(m)["kda"] * step["positions_live"]
            * kda_flops_a_position(m))


def kda_chunk_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """Least HBM traffic of those kernels: a live position's ``q``, ``k``
    and ``v`` in and ``o`` out at the inner width, in the activations'
    type, its running log-decay a channel, float32, and its ``beta``,
    float32 a head (49 280 bytes a position a layer at the published widths
    in bf16); the state never leaves the chip between chunks."""
    size = BYTES[m.get("program", {}).get("dtype", "bfloat16")]
    inner = m["num_attention_heads"] * m["head_dim"]
    a_position = (4 * size + 4) * inner + 4 * m["num_attention_heads"]
    return layer_counts(m)["kda"] * step["positions_live"] * a_position


def flash_fwd_pair_flops(m: Dict[str, Any], pairs: float) -> float:
    """What the latent flash forward of every latent layer needs for
    ``pairs`` (query, key) pairs: a score over the query/key width (192)
    and a weighted value over the value width (128) a head, 2 FLOP a
    multiply-add."""
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return (layer_counts(m)["latent"] * m["num_attention_heads"] * 2.0
            * width * pairs)


def flash_fwd_row_bytes(m: Dict[str, Any], queries: float,
                        keys: float) -> float:
    """Least HBM traffic of those forwards, bf16: a query position's q at
    the whole query width and its o at the value width, a key position's
    ``k_nope`` and ``v`` a head and its rotary key ONCE, not a head."""
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    heads = m["num_attention_heads"]
    elems = (queries * heads * ((nope + rope) + vd)
             + keys * (heads * (nope + vd) + rope))
    return layer_counts(m)["latent"] * 2.0 * elems
