"""The LFM2 mixture-of-experts family (``model_type: lfm2_moe``):
``models/llama.py``'s one block under a layer pattern (gated short
convolutions beside grouped-query attention with heads of 64, leading dense
layers, then ``models/moe.py``'s routed experts under a sigmoid router with
a bias-corrected choice), at a configuration file's sizes, served by
``serve/llm.py::LlamaGenerator``, checked against ``reference/lfm2_moe.py``.

It gives what ``families/dense_decoder.py``'s docstring lists, and beside
it what its own readers ask for: ``expert_ffn_flops`` and
``expert_ffn_bytes`` over the routed layers alone, and the flash forward's
FLOPs and bytes over the attention layers alone (``flash_fwd_flops``,
``flash_fwd_bytes``). Importing this module imports no jax: the harness
process and the readers load it too.
"""

from __future__ import annotations

from typing import Any, Dict, List

from benchmark.harness.loader import BOOKKEEPING_KEYS
from benchmark.harness.modelcfg import PROGRAM_KEYS
from ray_tpu.serve.llm import LlamaGenerator

REFERENCE = "lfm2_moe"

# published key -> field of the program's configuration class
MODEL_KEYS = {
    "vocab_size": "vocab_size", "hidden_size": "hidden",
    "intermediate_size": "dense_mlp_hidden",  # the leading dense layers'
    "moe_intermediate_size": "mlp_hidden",    # ONE expert's width
    "num_hidden_layers": "num_layers", "num_dense_layers": "num_dense_layers",
    "layer_types": "layer_types", "conv_L_cache": "conv_kernel",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "max_position_embeddings": "max_seq_len",
    "norm_eps": "rms_eps",
    "num_experts": "num_experts", "num_experts_per_tok": "experts_per_token",
    "norm_topk_prob": "norm_topk_prob", "use_expert_bias": "router_bias",
    "routed_scaling_factor": "routed_scaling_factor",
    "tie_word_embeddings": "tie_embeddings",
    # not a key of config.json: the file states it under `assumed`
    "router_norm_eps": "router_norm_eps",
}
# what `Served` draws from --seed, stated under `assumed`: the program
# starts the expert bias at zeros, as HuggingFace does
DRAWN = ("expert_bias_init_std",)
# what `build_config` turns on beside the mapped keys: in modeling_lfm2_moe,
# not keys of config.json
MODELING = {"router_scores": "sigmoid", "qk_head_norm": True}
# published keys held to the one value that the program computes
HELD = {"conv_bias": False}
LAYER_TYPES = ("conv", "full_attention")
BYTES = {"bfloat16": 2, "float32": 4}


def check(m: Dict[str, Any]) -> None:
    """Every key of the file is one this family maps, holds to one value
    or keeps its books by; anything else raises and names the key."""
    name = m.get("name")
    unknown = sorted(set(m) - set(MODEL_KEYS) - set(HELD) - set(DRAWN)
                     - set(BOOKKEEPING_KEYS) - {"rope_parameters"})
    if unknown:
        raise ValueError(f"configuration {name!r}: the lfm2_moe family "
                         f"does not understand {unknown}")
    missing = sorted((set(MODEL_KEYS) | set(DRAWN) | {"rope_parameters"})
                     - set(m))
    if missing:
        raise ValueError(f"configuration {name!r} lacks {missing}")
    for key, only in HELD.items():
        if key in m and m[key] != only:
            raise ValueError(f"{key} {m[key]!r}: models/llama.py computes "
                             f"{only!r} only")
    rope = m["rope_parameters"]
    if set(rope) != {"rope_theta", "rope_type"} \
            or rope["rope_type"] != "default":
        raise ValueError(f"rope_parameters {rope!r}: models/llama.py "
                         "computes the default rotation from rope_theta only")
    types = m["layer_types"]
    if len(types) != m["num_hidden_layers"]:
        raise ValueError(f"layer_types names {len(types)} layers, "
                         f"num_hidden_layers is {m['num_hidden_layers']}")
    strange = sorted(set(types) - set(LAYER_TYPES))
    if strange:
        raise ValueError(f"layer_types {strange}: expected some of "
                         f"{list(LAYER_TYPES)}")
    if not 0 <= m["num_dense_layers"] <= m["num_hidden_layers"]:
        raise ValueError("num_dense_layers must lie in 0..num_hidden_layers")
    if not 0 < m["num_experts_per_tok"] <= m["num_experts"]:
        raise ValueError("num_experts_per_tok must lie in 1..num_experts")
    if m["head_dim"] * m["num_attention_heads"] != m["hidden_size"]:
        raise ValueError("head_dim is hidden_size / num_attention_heads in "
                         "this family")
    lacking = sorted((set(MODEL_KEYS.values()) | set(MODELING))
                     - _config_fields())
    if lacking:
        raise ValueError(f"this checkout's LlamaConfig has no {lacking}: "
                         "models/llama.py cannot build this family")


def _config_fields() -> set:
    """The fields of this checkout's ``LlamaConfig``, read from its source:
    importing ``models/llama.py`` imports jax, which the harness process
    must not, and a replica that fails to build its configuration is
    retried for minutes where this raises at once."""
    import ast
    import os

    import ray_tpu

    path = os.path.join(os.path.dirname(ray_tpu.__file__), "models",
                        "llama.py")
    with open(path) as f:
        classes = [n for n in ast.parse(f.read()).body
                   if isinstance(n, ast.ClassDef) and n.name == "LlamaConfig"]
    return {n.target.id for c in classes for n in c.body
            if isinstance(n, ast.AnnAssign)}


def build_config(m: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    check(m)
    kwargs = {ours: m[theirs] for theirs, ours in MODEL_KEYS.items()}
    kwargs["layer_types"] = tuple(kwargs["layer_types"])
    kwargs["rope_theta"] = float(m["rope_parameters"]["rope_theta"])
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
        expert_bias_std=m["expert_bias_init_std"])


def with_expert_bias(params, std: float, seed: int):
    """``params`` with every routed layer's expert bias drawn from
    ``seed`` at deviation ``std``, in the leaf's type: the program starts
    it at zeros and training moves it; a benchmark has no training, and a
    bias of zeros would leave the bias-corrected choice unexercised. Each
    layer gets the same ``E`` values, the evenly spaced quantiles of a
    normal, in an order of its own drawn from the seed: with 64
    independent normal draws the largest wanders between 2 and 3
    deviations from seed to seed, the fullest expert's load with it, and
    the longest step's time by 1 % (six runs, PERF.md section 6, PR 33),
    which is the cell's ``serve_gap_p95_ms``; the seeds are there to vary
    the weights and the prompts, not the skew of the load."""
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
        kind = jax.random.fold_in(key, zlib.crc32(str(path).encode()))
        return jax.vmap(lambda k: jax.random.permutation(k, values))(
            jax.random.split(kind, layers)).astype(leaf.dtype)
    return jax.tree_util.tree_map_with_path(draw, params)


class Served(LlamaGenerator):
    """The program's class, with the expert bias drawn
    (``with_expert_bias``) and each step under a span that names its
    padded length, as ``families/olmoe.py``'s has: the work a step needs
    differs by bucket, and the roofline readers count the traced steps'
    own (``host_spans``)."""

    def __init__(self, *, expert_bias_std: float = 0.0, **kwargs):
        super().__init__(**kwargs)
        self._params = with_expert_bias(self._params, expert_bias_std,
                                        kwargs["seed"])

    def _step(self, model_id, states):
        import jax

        with jax.profiler.TraceAnnotation(
                f"bench:len_{self._padded_len(states)}"):
            return super()._step(model_id, states)

    def last_position_logits(self, prompt: List[int]):
        """What ``drivers/serve.py``'s check asks of the served class, under
        the driver's name for it; for this family the mean over the
        prompt's positions of the logits, ``[vocab]`` float32
        (``reference/lfm2_moe.py::last_logits`` gives the same of the
        reference), through the step's program at the engine's batch and
        the program's head over the prompt's every position. The mean is
        linear, so the driver's difference is the mean of the positions'
        differences: bf16 moves a router's near-tie across the 4th place
        at about one (position, layer) in twenty, one expert of a
        renormalised 4 is a quarter of a feed-forward, and at one position
        that moves the logits as far as rounding every weight to 3
        mantissa bits does (PERF.md section 6, PR 33); over the prompt's
        384 positions it is one term of 384."""
        import numpy as np

        rows, n = self.engine.max_batch_size, len(prompt)
        tokens = np.zeros((rows, n), np.int32)
        tokens[0] = prompt
        mask = np.zeros((rows, n), bool)
        mask[0] = True
        _, hidden, _ = self._run_step(tokens, np.zeros(rows, np.int32), mask)
        logits = np.asarray(self._head_fn(self._params, hidden[0, :n]))
        return logits.mean(0, dtype=np.float64).astype(np.float32)


# ---------------------------------------------------------------- counts
def layer_kind(m: Dict[str, Any], l: int) -> str:
    """Layer ``l``'s kind under the program's names: its operator, then its
    feed-forward."""
    operator = "attention" if m["layer_types"][l] == "full_attention" \
        else "conv"
    return operator + ("_dense" if l < m["num_dense_layers"] else "_routed")


def half_counts(m: Dict[str, Any]) -> Dict[str, int]:
    """How many layers have each half: ``attention`` or ``conv``, and
    ``dense`` or ``routed``."""
    types, dense = m["layer_types"], m["num_dense_layers"]
    return {"attention": types.count("full_attention"),
            "conv": types.count("conv"),
            "dense": dense, "routed": len(types) - dense}


def half_params(m: Dict[str, Any], *, active: bool = False) -> Dict[str, int]:
    """Parameters of one layer's half, by the names of ``half_counts``
    (``active``: the experts a position meets and not all of them)."""
    h, hd = m["hidden_size"], m["head_dim"]
    q, kv = m["num_attention_heads"] * hd, m["num_key_value_heads"] * hd
    experts = m["num_experts_per_tok"] if active else m["num_experts"]
    return {
        "attention": h * (q + 2 * kv) + q * h + 2 * hd,
        "conv": 3 * h * h + h * m["conv_L_cache"] + h * h,
        "dense": 3 * h * m["intermediate_size"],
        "routed": (experts * 3 * h * m["moe_intermediate_size"]
                   + h * m["num_experts"]
                   + (m["num_experts"] if m["use_expert_bias"] else 0)),
    }


def num_params(m: Dict[str, Any]) -> int:
    h, halves = m["hidden_size"], half_params(m)
    layers = sum(n * halves[half] for half, n in half_counts(m).items())
    tied = 1 if m["tie_word_embeddings"] else 2
    return (layers + m["num_hidden_layers"] * 2 * h
            + tied * m["vocab_size"] * h + h)


def train_flops_per_token(m: Dict[str, Any], seq: int) -> float:
    """Forward and backward matmuls a token meets (the operators'
    projections, its experts and the router, the dense layers, the head)
    and causal attention in the attention layers; no lookup, norms, taps,
    softmax, sort or gather."""
    h, hd, counts = m["hidden_size"], m["head_dim"], half_counts(m)
    halves = half_params(m, active=True)
    not_matmul = {"attention": 2 * hd, "conv": h * m["conv_L_cache"],
                  "dense": 0,
                  "routed": m["num_experts"] if m["use_expert_bias"] else 0}
    matmul = sum(n * (halves[half] - not_matmul[half])
                 for half, n in counts.items()) + m["vocab_size"] * h
    attention = (counts["attention"] * 7 * 2.0 * m["num_attention_heads"]
                 * hd * seq / 2.0)
    return 6.0 * matmul + attention


def attention_kernel_flops(m: Dict[str, Any], batch: int, seq: int) -> float:
    """Required FLOPs of the three flash kernels over one training step,
    in the attention layers alone: two matmuls forward and five backward,
    the causal half."""
    return 3.5 * flash_fwd_flops(m, batch, seq)


def attention_kernel_bytes(m: Dict[str, Any], batch: int, seq: int) -> float:
    """Least HBM traffic of the three kernels: q, o, do, dq once each at
    the query heads, k, v, dk, dv at the key/value heads, bf16."""
    return 2.0 * flash_fwd_bytes(m, batch, seq)


def flash_fwd_flops(m: Dict[str, Any], batch: int, seq: int) -> float:
    """What the flash forward of every attention layer needs for ``batch``
    rows of ``seq`` positions: scores and weighted values, 2 FLOP a
    multiply-add, only the causal half."""
    return (half_counts(m)["attention"] * batch * seq * 2 * 2.0
            * m["num_attention_heads"] * m["head_dim"] * seq / 2.0)


def flash_fwd_bytes(m: Dict[str, Any], batch: int, seq: int) -> float:
    """Least HBM traffic of those forwards: q and o once each at the query
    heads, k and v at the key/value heads, bf16."""
    elems = batch * seq * m["head_dim"] * (
        2 * m["num_attention_heads"] + 2 * m["num_key_value_heads"])
    return half_counts(m)["attention"] * 2.0 * elems


def expert_ffn_flops(m: Dict[str, Any], positions: int) -> float:
    """What the three grouped matmuls of every routed layer need for
    ``positions`` positions of one forward pass: each position meets
    ``num_experts_per_tok`` experts, each three matmuls of hidden x
    ``moe_intermediate_size``, 2 FLOP a multiply-add. The router, the sort,
    the gathers, the weighted sum and the dense layers are not counted."""
    return (half_counts(m)["routed"] * positions * m["num_experts_per_tok"]
            * 3 * 2.0 * m["hidden_size"] * m["moe_intermediate_size"])


def expert_ffn_bytes(m: Dict[str, Any]) -> float:
    """Least HBM traffic of those matmuls in one forward pass: every
    expert's three matrices of every routed layer read once, in the
    parameters' type. The rows in and out are left out (a lower bound
    stays a lower bound)."""
    size = BYTES[m.get("program", {}).get("param_dtype", "float32")]
    return (half_counts(m)["routed"] * m["num_experts"] * 3.0
            * m["hidden_size"] * m["moe_intermediate_size"] * size)
