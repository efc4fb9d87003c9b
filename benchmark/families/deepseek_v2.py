"""The DeepSeek-V2 family (``model_type: deepseek_v2``): ``models/llama.py``'s
one block with latent attention (MLA: low-rank queries, one normed latent
row a position decompressed to 128 heads, a rotary key every head shares,
heads of 192 against values of 128, YaRN) as its operator, a leading dense
layer, then ``models/moe.py``'s routed experts under a group-limited choice
beside shared experts, of which this chip holds one group's share; at a
configuration file's sizes, served by ``serve/llm.py::LlamaGenerator``,
checked against ``reference/deepseek_v2.py``.

The share. ``n_routed_experts`` is how many experts of each routed layer
are held here (listed in the file's ``reduced``); ``expert_share`` gives
``of``, the published count and the router's width, and ``first``, the
first held expert. The held experts are whole routing groups, as the
source spreads them: a group a device.

It gives what ``families/dense_decoder.py``'s docstring lists, and beside
it what its readers ask for, each with the share: ``expert_ffn_flops`` and
``expert_ffn_bytes`` over the routed layers' held experts, and the latent
flash forward's FLOPs and bytes (``flash_fwd_flops``, ``flash_fwd_bytes``).
Importing this module imports no jax: the harness process and the readers
load it too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families import lfm2_moe
from benchmark.families.lfm2_moe import _config_fields
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "deepseek_v2"

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
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "n_group": "router_groups", "topk_group": "router_topk_groups",
    "tie_word_embeddings": "tie_embeddings",
}
# keys the family reads itself: the share and the rotary scaling
OWN_KEYS = ("n_routed_experts", "expert_share", "rope_scaling")
# what `build_config` sets beside the mapped keys, from OWN_KEYS
BUILT = ("num_experts", "experts_held", "rope_scaling", "layer_types")
# published keys held to the one value that the program computes
HELD = {"attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
        "scoring_func": "softmax", "topk_method": "group_limited_greedy"}
# a published key of the training loss alone (the sequence-wise balancing
# term), which nothing here computes: kept as the source has it, not read
NOT_READ = ("seq_aux",)
ROPE_SCALING_KEYS = ("beta_fast", "beta_slow", "factor", "mscale",
                     "mscale_all_dim", "original_max_position_embeddings",
                     "type")
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key. A
    checkout whose ``LlamaConfig`` lacks the fields fails here, at once."""
    name = m.get("name")
    known = (set(MODEL_KEYS) | set(OWN_KEYS) | set(HELD) | set(NOT_READ)
             | set(BOOKKEEPING_KEYS))
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"configuration {name!r}: the deepseek_v2 family "
                         f"does not understand {unknown}")
    missing = sorted((set(MODEL_KEYS) | set(OWN_KEYS) | set(HELD)) - set(m))
    if missing:
        raise ValueError(f"configuration {name!r} lacks {missing}")
    lacking = sorted((set(MODEL_KEYS.values()) | set(BUILT))
                     - _config_fields())
    if lacking:
        raise ValueError(f"this checkout's LlamaConfig has no {lacking}: "
                         "models/llama.py cannot build this family")
    if "expert_pairs_all" not in getattr(LlamaGenerator, "STEP_COUNTERS",
                                         ()):
        raise ValueError("this checkout's serve/llm.py keeps no books of "
                         "a share of the experts: it cannot serve "
                         "expert_share")
    for key, only in HELD.items():
        if m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: the program computes "
                             f"{only!r} only")
    rope = m["rope_scaling"]
    if not isinstance(rope, dict) or rope.get("type") != "yarn" \
            or set(rope) != set(ROPE_SCALING_KEYS):
        raise ValueError(f"rope_scaling {rope!r}: models/llama.py computes "
                         f"type 'yarn' from {list(ROPE_SCALING_KEYS)} only")
    if m["num_key_value_heads"] != m["num_attention_heads"]:
        raise ValueError("num_key_value_heads: latent attention gives every "
                         "query head its own key and value")
    if not m["q_lora_rank"]:
        raise ValueError(f"q_lora_rank {m['q_lora_rank']!r}: the program's "
                         "latent attention has low-rank queries only")
    if not 0 <= m["first_k_dense_replace"] <= m["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace must lie in "
                         "0..num_hidden_layers")
    share = m["expert_share"]
    if not isinstance(share, dict) or set(share) != {"first", "of"}:
        raise ValueError(f"expert_share {share!r}: expected first and of")
    of, first, held = share["of"], share["first"], m["n_routed_experts"]
    groups = m["n_group"]
    if of % groups or not 0 < m["topk_group"] <= groups:
        raise ValueError(f"{of} experts in n_group {groups}, topk_group "
                         f"{m['topk_group']}")
    group = of // groups
    if held % group or first % group or not 0 < held <= of - first:
        raise ValueError(
            f"expert_share: {held} experts from {first} of {of} is no whole "
            f"number of the {groups} groups of {group}")
    if not 0 < m["num_experts_per_tok"] <= m["topk_group"] * group:
        raise ValueError("num_experts_per_tok must lie in 1..the kept "
                         "groups' experts")


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, RopeScaling

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    kwargs["rope_theta"] = float(kwargs["rope_theta"])
    share, held = m["expert_share"], m["n_routed_experts"]
    kwargs["num_experts"] = share["of"]
    if held < share["of"]:
        kwargs["experts_held"] = (share["first"], held)
    kwargs["rope_scaling"] = RopeScaling(**{
        k: v for k, v in m["rope_scaling"].items() if k != "type"})
    kwargs["layer_types"] = ("latent_attention",) * m["num_hidden_layers"]
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
    return LlamaConfig(**kwargs)


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
    padded length, as the other expert families' have: the work a step
    needs differs by bucket, and the roofline readers count the traced
    steps' own (``host_spans``). A second span names the step's live
    positions, the rows' own tokens: the held experts are spared the
    padding, so their need is over those
    (``readers/share_expert_ffn_roofline_pct_serve.py``)."""

    def _step(self, model_id, states):
        import jax

        pad = self._padded_len(states)
        live = sum(min(len(s["tokens"]), pad) for s in states
                   if s is not None)
        with jax.profiler.TraceAnnotation(f"bench:len_{pad}"), \
                jax.profiler.TraceAnnotation(f"bench:live_{live}"):
            return super()._step(model_id, states)

    # What ``drivers/serve.py``'s check asks of the served class: for this
    # family, as for LFM2's and by its code, the MEAN over the prompt's
    # positions of the logits (``reference/deepseek_v2.py::last_logits``
    # gives the same of the reference). The mean is linear, so the driver's
    # difference is the mean of the positions' differences. Why not the
    # last position alone: bf16 moves a router's score across the boundary
    # of the 6th expert, or of the 3rd group, where two nearly tie, a chosen
    # expert's weight is 16 times its score, and at ONE position such a flip
    # moves the logits as far as a fault does (one seed in twelve read 0.23
    # there, where the mean reads 0.007 to 0.014: the cell's ``check.why``).
    last_position_logits = lfm2_moe.Served.last_position_logits


# ---------------------------------------------------------------- counts
def layer_counts(m: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have a dense feed-forward and how many a routed
    one; every layer's operator is latent attention."""
    dense = m["first_k_dense_replace"]
    return {"dense": dense, "routed": m["num_hidden_layers"] - dense}


def part_params(m: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's parts: latent attention with its two
    inner norms, a dense SwiGLU, and a routed feed-forward as it is held
    here (the held experts, the shared ones, the router over all)."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    expert = 3 * h * m["moe_intermediate_size"]
    return {
        "latent": (h * qr + qr + qr * heads * (nope + rope)
                   + h * (kvr + rope) + kvr + kvr * heads * (nope + vd)
                   + heads * vd * h),
        "dense": 3 * h * m["intermediate_size"],
        "routed": ((m["n_routed_experts"] + m["n_shared_experts"]) * expert
                   + h * m["expert_share"]["of"]),
    }


def num_params(m: Dict[str, Any]) -> int:
    """Parameters resident here: a share's experts count as the share."""
    h, parts, counts = m["hidden_size"], part_params(m), layer_counts(m)
    layers = m["num_hidden_layers"]
    tied = 1 if m["tie_word_embeddings"] else 2
    return (layers * (parts["latent"] + 2 * h)
            + counts["dense"] * parts["dense"]
            + counts["routed"] * parts["routed"]
            + tied * m["vocab_size"] * h + h)


def held_share(m: Dict[str, Any]) -> float:
    """The share of a symmetric router's pairs that land on held experts."""
    return m["n_routed_experts"] / m["expert_share"]["of"]


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward and backward matmuls a token meets here (latent attention's
    projections, the shared experts, its share of the routed experts and
    the router, the dense layers, the head) and causal attention at the
    two widths; no lookup, norms, softmax, sort or gather."""
    h, parts, counts = m["hidden_size"], part_params(m), layer_counts(m)
    expert = 3 * h * m["moe_intermediate_size"]
    latent = parts["latent"] - m["q_lora_rank"] - m["kv_lora_rank"]
    routed = ((m["num_experts_per_tok"] * held_share(m)
               + m["n_shared_experts"]) * expert
              + h * m["expert_share"]["of"])
    matmul = (m["num_hidden_layers"] * latent
              + counts["dense"] * parts["dense"] + counts["routed"] * routed
              + m["vocab_size"] * h)
    return 6.0 * matmul + 3.5 * flash_fwd_flops(m, 1, seq) / seq


def attention_kernel_flops(m: Dict[str, Any], batch: int, seq: int) -> float:
    """Required FLOPs of a forward and a backward at the two widths: two
    matmuls forward and five backward, the causal half. The program has
    the forward alone (``ops/pallas/flash_attention.py``)."""
    return 3.5 * flash_fwd_flops(m, batch, seq)


def attention_kernel_bytes(m: Dict[str, Any], batch: int, seq: int) -> float:
    return 2.0 * flash_fwd_bytes(m, batch, seq)


def flash_fwd_flops(m: Dict[str, Any], batch: int, seq: int) -> float:
    """What the latent flash forward of every layer needs for ``batch``
    rows of ``seq`` positions: scores over the query/key width (192) and
    weighted values over the value width (128), 2 FLOP a multiply-add,
    only the causal half."""
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return (m["num_hidden_layers"] * batch * m["num_attention_heads"]
            * 2.0 * width * seq * seq / 2.0)


def flash_fwd_bytes(m: Dict[str, Any], batch: int, seq: int) -> float:
    """Least HBM traffic of those forwards, bf16: q at the whole query
    width, ``k_nope`` and ``v`` a head, o at the value width, and the
    rotary key ONCE a position, not a head."""
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    elems = batch * seq * (m["num_attention_heads"]
                           * ((nope + rope) + nope + vd + vd) + rope)
    return m["num_hidden_layers"] * 2.0 * elems


def expert_ffn_flops(m: Dict[str, Any], positions: int) -> float:
    """What the three grouped matmuls of every routed layer need HERE for
    ``positions`` positions of one forward pass: each position makes
    ``num_experts_per_tok`` pairs over all the experts, of which a
    symmetric router sends ``held_share`` to the held ones (the traced
    steps' own share is ``dsv2_routed_pairs_here_pct.serve``), each pair
    three matmuls of hidden x ``moe_intermediate_size``, 2 FLOP a
    multiply-add. The router, the sort, the gathers, the weighted sum, the
    shared experts and the dense layer are not counted."""
    return (layer_counts(m)["routed"] * positions * m["num_experts_per_tok"]
            * held_share(m) * 3 * 2.0 * m["hidden_size"]
            * m["moe_intermediate_size"])


def expert_ffn_bytes(m: Dict[str, Any]) -> float:
    """Least HBM traffic of those matmuls in one forward pass: every held
    expert's three matrices of every routed layer read once, in the
    parameters' type."""
    size = BYTES[m.get("program", {}).get("param_dtype", "float32")]
    return (layer_counts(m)["routed"] * m["n_routed_experts"] * 3.0
            * m["hidden_size"] * m["moe_intermediate_size"] * size)
