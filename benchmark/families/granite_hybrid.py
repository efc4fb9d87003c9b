"""The Granite 4.0-H family (``model_type: granitemoehybrid`` with no
routed part, granite-4.0-h-micro): ``models/llama.py``'s one block under a
layer pattern of Mamba-2 state-space mixers (``"mamba"``: in-projection,
depthwise causal taps with a bias, the scan of ``ops/ssm.py`` in chunks of
``mamba_chunk_size``, a gated RMSNorm, out-projection) beside grouped-query
attention WITHOUT rope (``position_embedding_type: "nope"``), a dense
SwiGLU of ``shared_intermediate_size`` in every layer, a head tied to the
embedding, and the family's four scalars (``embedding_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``attention_multiplier``); at a
configuration file's sizes, served by ``serve/llm.py::LlamaGenerator``,
checked against ``reference/granite_hybrid.py``.

It gives the serving side of what ``families/dense_decoder.py``'s docstring
lists (``check``, ``Served``, ``served_kwargs``, ``REFERENCE``,
``num_params``; no ``training`` and no training counts: the scan's kernel
has no backward and no cell trains this model), and beside it what its
readers ask for: the scan's FLOPs and least bytes of a traced step from the
step's record (``ssd_scan_flops``, ``ssd_scan_bytes``: over the step's LIVE
positions) and the flash forward's over the attention layers alone
(``flash_fwd_pair_flops``, ``flash_fwd_row_bytes``). Importing this module
imports no jax: the harness process and the readers load it too.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.families.lfm2_moe import (
    _config_fields, with_final_norm_signs)
from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "granite_hybrid"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "shared_intermediate_size": "mlp_hidden",  # the SwiGLU of every layer
    "num_hidden_layers": "num_layers", "layer_types": "layer_types",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "max_position_embeddings": "max_seq_len", "rms_norm_eps": "rms_eps",
    "rope_theta": "rope_theta",  # published, and read by nothing: no rope
    "mamba_n_heads": "mamba_heads", "mamba_d_head": "mamba_head_dim",
    "mamba_d_state": "mamba_state", "mamba_d_conv": "mamba_conv_kernel",
    "mamba_chunk_size": "mamba_chunk",
    "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "logits_scaling": "logits_scaling",
    "attention_multiplier": "attention_multiplier",
    "tie_word_embeddings": "tie_embeddings",
}
# what `build_config` sets beside the mapped keys
BUILT = ("head_dim", "use_rope")
# what `Served` draws from --seed, stated under `assumed` (beside the
# embedding's scale, `with_unit_input`, which no key switches)
DRAWN = ("final_norm_signs",)
# the program's operator for each published one
LAYER_TYPES = {"mamba": "mamba", "attention": "full_attention"}
# published keys held to the one value that the program computes: no
# bias but the taps', SiLU, RMSNorm, no rope, ONE group of B and C, no
# routed experts (`num_local_experts` 0: `intermediate_size`, one expert's
# width, then sizes nothing and is held to the published number)
HELD = {"attention_bias": False, "hidden_act": "silu",
        "normalization_function": "rmsnorm",
        "position_embedding_type": "nope", "rope_scaling": None,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "mamba_n_groups": 1, "num_local_experts": 0,
        "num_experts_per_tok": 0}
# published and sizing nothing here: `mamba_expand` is checked against the
# heads, `intermediate_size` is the absent experts' width
OTHER = ("mamba_expand", "intermediate_size")
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key. A
    checkout whose ``LlamaConfig`` lacks the fields fails here, at once."""
    name = m.get("name")
    known = (set(MODEL_KEYS) | set(HELD) | set(OTHER) | set(DRAWN)
             | set(BOOKKEEPING_KEYS))
    unknown = sorted(set(m) - known)
    if unknown:
        raise ValueError(f"configuration {name!r}: the granite_hybrid "
                         f"family does not understand {unknown}")
    missing = sorted(known - set(BOOKKEEPING_KEYS) - set(m))
    if missing:
        raise ValueError(f"configuration {name!r} lacks {missing}")
    lacking = sorted((set(MODEL_KEYS.values()) | set(BUILT))
                     - _config_fields())
    if lacking:
        raise ValueError(f"this checkout's LlamaConfig has no {lacking}: "
                         "models/llama.py cannot build this family")
    if "ssm_chunks_run" not in getattr(LlamaGenerator, "STEP_COUNTERS", ()):
        raise ValueError("this checkout's serve/llm.py counts no chunks of "
                         "a state-space scan: it cannot serve this family")
    for key, only in HELD.items():
        if m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: the program computes "
                             f"{only!r} only")
    types = m["layer_types"]
    if len(types) != m["num_hidden_layers"]:
        raise ValueError(f"layer_types names {len(types)} layers, "
                         f"num_hidden_layers is {m['num_hidden_layers']}")
    strange = sorted(set(types) - set(LAYER_TYPES))
    if strange:
        raise ValueError(f"layer_types {strange}: expected some of "
                         f"{list(LAYER_TYPES)}")
    if m["hidden_size"] % m["num_attention_heads"] \
            or m["num_attention_heads"] % m["num_key_value_heads"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads in "
                         "this family, and the query heads share the "
                         "key/value heads evenly")
    if m["mamba_n_heads"] * m["mamba_d_head"] \
            != m["mamba_expand"] * m["hidden_size"]:
        raise ValueError("mamba_n_heads x mamba_d_head must be mamba_expand "
                         "x hidden_size, the mixer's inner width")
    if m["mamba_d_conv"] < 2 or m["mamba_chunk_size"] % 128:
        raise ValueError("mamba_d_conv counts taps (at least 2) and the "
                         "scan's kernel takes chunks of whole lane tiles "
                         "(mamba_chunk_size a multiple of 128)")
    if not m["logits_scaling"] or not m["attention_multiplier"]:
        raise ValueError("logits_scaling divides and attention_multiplier "
                         "is the softmax scale: neither may be 0")


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    kwargs["layer_types"] = tuple(LAYER_TYPES[t] for t in m["layer_types"])
    kwargs["head_dim"] = m["hidden_size"] // m["num_attention_heads"]
    for scalar in ("rope_theta", "embedding_multiplier",
                   "residual_multiplier", "logits_scaling",
                   "attention_multiplier"):
        kwargs[scalar] = float(kwargs[scalar])
    program = m.get("program", {})
    kwargs.update({k: program[k] for k in PROGRAM_KEYS if k in program})
    kwargs["dtype"] = jnp.dtype(program.get("dtype", "bfloat16")).type
    kwargs["param_dtype"] = jnp.dtype(
        program.get("param_dtype", "float32")).type
    return LlamaConfig(use_rope=False, **kwargs)


def served_kwargs(m: Dict[str, Any], engine: Dict[str, Any],
                  seed: int) -> Dict[str, Any]:
    return dict(
        config=build_config(m), lora_rank=engine["lora_rank"],
        max_batch_size=engine["max_batch_size"],
        allowed_batch_sizes=tuple(engine["allowed_batch_sizes"]),
        max_new_tokens=engine["max_new_tokens"],
        seq_bucket=engine["seq_bucket"], seed=seed % (2 ** 31),
        final_norm_signs=m["final_norm_signs"])


def with_unit_input(params, multiplier: float):
    """``params`` with the embedding over ``embedding_multiplier``, so that
    ``multiplier * embed(ids)``, what the first layer is handed, has the
    unit scale that every other configuration's first layer is handed. The
    program's initialiser draws every embedding at unit scale; times 12 the
    residual would be 12 a channel beside 80 sub-layers that each add 0.22
    of order 1, a served token would be decided by the token fed and
    hardly by the layers, and `correct`, which reads served tokens, would
    see little of them (PERF.md section 6, PR 46: the first chip run read
    a gap_mean of 0.00004 so). This benchmark's, as the signs below are:
    stated under the configuration's `assumed`. The division is one
    fused program over the embedding's own buffer, which is donated
    (``params`` is spent): 205M numbers leave no float32 copy behind to
    stand as the run's `memory_peak_bytes`."""
    import jax
    import jax.numpy as jnp

    scaled = jax.jit(
        lambda e: (e.astype(jnp.float32) / multiplier).astype(e.dtype),
        donate_argnums=0)(params["embed"])
    return {**params, "embed": scaled}


class Served(LlamaGenerator):
    """The program's class with the embedding brought to the scale its
    multiplier assumes (``with_unit_input``) and, where the configuration
    says so, the last norm's gain given signs from the seed
    (``families/lfm2_moe.py::with_final_norm_signs``: the head is tied)."""

    def __init__(self, *, final_norm_signs: bool = False, **kwargs):
        super().__init__(**kwargs)
        self._params = with_unit_input(self._params,
                                       self._cfg.embedding_multiplier)
        if final_norm_signs:
            self._params = with_final_norm_signs(self._params,
                                                 kwargs["seed"])


# ---------------------------------------------------------------- counts
def mamba_widths(m: Dict[str, Any]) -> Dict[str, int]:
    """The mixer's widths: ``inner`` (the heads' channels), ``conv`` (what
    the taps run over: ``[x | B | C]``) and ``proj`` (what the
    in-projection makes: ``[z | x B C | dt]``)."""
    inner = m["mamba_n_heads"] * m["mamba_d_head"]
    conv = inner + 2 * m["mamba_n_groups"] * m["mamba_d_state"]
    return {"inner": inner, "conv": conv,
            "proj": inner + conv + m["mamba_n_heads"]}


def layer_counts(m: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each part: ``mamba`` or ``attention``, and the
    ``dense`` SwiGLU, which every layer has."""
    types = m["layer_types"]
    return {"mamba": types.count("mamba"),
            "attention": types.count("attention"), "dense": len(types)}


def part_params(m: Dict[str, Any]) -> Dict[str, int]:
    """Parameters of one layer's parts, by the names of ``layer_counts``.
    A mixer: the in-projection, the taps and their bias, ``dt_bias``,
    ``A_log`` and ``D`` a head, the gated norm's gain, the out-projection."""
    h = m["hidden_size"]
    hd = h // m["num_attention_heads"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    w = mamba_widths(m)
    return {
        "mamba": (h * w["proj"] + w["conv"] * (m["mamba_d_conv"] + 1)
                  + 3 * m["mamba_n_heads"] + w["inner"] + w["inner"] * h),
        "attention": h * (q + 2 * kv) + q * h,
        "dense": 3 * h * m["shared_intermediate_size"],
    }


def num_params(m: Dict[str, Any]) -> int:
    h, parts = m["hidden_size"], part_params(m)
    tied = 1 if m["tie_word_embeddings"] else 2
    return (sum(n * parts[part] for part, n in layer_counts(m).items())
            + m["num_hidden_layers"] * 2 * h
            + tied * m["vocab_size"] * h + h)


def scan_flops_a_position(m: Dict[str, Any]) -> float:
    """What ONE mixer's scan needs for a position, 2 FLOP a multiply-add:
    a head's ``(L o C B^T) X`` over its chunk's ``mamba_chunk_size`` keys
    (the causal half would be half of it; the chunked form computes the
    square and the count keeps to what the form needs), its row of ``C
    h_in`` and its row of the state's update, each ``mamba_d_head x
    mamba_d_state``; ``C B^T`` itself once a position for all heads. The
    decays' exponentials are the vector unit's and are NOT counted."""
    heads, hd, n = m["mamba_n_heads"], m["mamba_d_head"], m["mamba_d_state"]
    q = m["mamba_chunk_size"]
    return 2.0 * (heads * hd * (q + 2 * n) + n * q)


def ssd_scan_flops(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """The state-space layers' scans over a traced step's LIVE positions
    (its record's ``positions_live``): a scan that runs a row's padding
    too reads low by the padding's share, which is the truth."""
    return (layer_counts(m)["mamba"] * step["positions_live"]
            * scan_flops_a_position(m))


def ssd_scan_bytes(m: Dict[str, Any], step: Dict[str, Any]) -> float:
    """Least HBM traffic of those scans: a live position's ``x`` in and
    ``y`` out at the inner width and its ``B`` and ``C`` rows, in the
    activations' type, and its ``dt``, float32 a head (17 152 bytes a
    position a layer at the published widths in bf16); the state never
    leaves the chip between chunks."""
    size = BYTES[m.get("program", {}).get("dtype", "bfloat16")]
    w = mamba_widths(m)
    a_position = (size * (2 * w["inner"]
                          + 2 * m["mamba_n_groups"] * m["mamba_d_state"])
                  + 4 * m["mamba_n_heads"])
    return layer_counts(m)["mamba"] * step["positions_live"] * a_position


def flash_fwd_pair_flops(m: Dict[str, Any], pairs: float) -> float:
    """What the flash forward of every attention layer needs for ``pairs``
    (query, key) pairs: a score and a weighted value a head, 2 FLOP a
    multiply-add."""
    return (layer_counts(m)["attention"] * pairs * 2 * 2.0
            * m["hidden_size"])


def flash_fwd_row_bytes(m: Dict[str, Any], queries: float,
                        keys: float) -> float:
    """Least HBM traffic of those forwards, bf16: q and o once a query
    position at the query heads, k and v once a key position at the
    key/value heads."""
    hd = m["hidden_size"] // m["num_attention_heads"]
    elems = hd * (2 * m["num_attention_heads"] * queries
                  + 2 * m["num_key_value_heads"] * keys)
    return layer_counts(m)["attention"] * 2.0 * elems
