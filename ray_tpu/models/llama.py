"""Llama-2/3-family decoder-only transformer, TPU-first.

Design notes (why this is not a torch translation):
- Pure functional: params are a pytree of ``jnp.ndarray``; the forward pass is
  a jit-friendly function of (params, tokens). No module objects, no state.
- Every parameter carries *logical axis names* (see ``llama_logical_axes``) so
  the same model runs 1-chip or on any (data, fsdp, seq, tensor) mesh purely
  by changing the rule table — GSPMD inserts the collectives.
- Layers are stacked into single arrays (num_layers leading dim) and scanned
  with ``jax.lax.scan``: one compiled layer body regardless of depth, which
  keeps XLA compile time flat and enables per-layer remat.
- A layer pattern is data on ``LlamaConfig`` (LFM2-24B-A2B has all of it):
  ``layer_types`` names each layer's operator, causal attention or a gated
  short convolution, and the first ``num_dense_layers`` of a model with
  experts keep a dense SwiGLU. A layer's kind is its operator and its
  feed-forward (``LlamaConfig.layer_kinds``). A model of one kind (every
  dense decoder, OLMoE) keeps its layers as one stacked pytree under
  ``params["layers"]``; a model of several keeps one stacked pytree a kind,
  ``params["layers"][kind]``. There is one block (``_layer``, which reads a
  layer's kind off its leaves) and one forward, which scans each run of
  like layers in the configuration's order (``LlamaConfig.layer_runs``).
  A run that is a proper part of its kind's stack is scanned over its
  slice of every leaf, which XLA copies every step, except in the forward
  that nobody differentiates (``llama_next_token``, the serving step):
  that one scans the run's indices and reads each layer out of the whole
  stack in the body, and the loss keeps the slices because a
  differentiated scan over a closed-over stack carries a cotangent of the
  whole stack through every run.
- A third operator, ``"latent_attention"`` (DeepSeek-V2's MLA;
  ``_latent_attention``): queries through a low-rank pair with a norm
  between, keys and values decompressed from one normed latent row a
  position, a rotary part of the key that every head shares, heads wider
  in the query and key than in the value, YaRN's frequencies and softmax
  scale (``RopeScaling``). A forward pass decompresses and calls
  ``ops.attention`` with the two widths; ``llama_decode`` keeps the latent
  row and the rotated shared key alone and attends in the absorbed form.
- Two more operators are the same function told other widths
  (dots3-note-prev has both; ``LlamaConfig.latent_widths``):
  ``"window_latent_attention"``, latent attention at a second geometry
  (the ``swa_`` fields, its own ``rope_theta``) whose query ``t`` sees the
  ``sliding_window`` keys up to its own, and
  ``"indexed_latent_attention"``, whose query attends the ``index_topk``
  keys that a learned indexer scores highest (DeepSeek-V3.2's lightning
  indexer; ``_index_queries_and_key``, ``_chosen_keys``); both may gate each head's
  output by a sigmoid of the layer's input (``head_gate``). The window and
  the selection reach ``ops.attention`` as arguments; a decode keeps the
  last ``sliding_window`` latent rows of a window layer, and the latent
  rows and the index keys of an indexed one.
- A sixth operator, ``"mamba"`` (Mamba-2's mixer as granite-4.0-h's
  ``granitemoehybrid`` computes it; ``_mamba``): one in-projection to a
  gate, the convolved ``[x | B | C]`` and a step size a head, depthwise
  causal taps with a bias and a SiLU (``_short_conv``'s taps), the
  state-space scan (``ops.ssm.ssd_scan``: a chunked Pallas kernel, or the
  recurrence itself), a gated RMSNorm and an out-projection. Its state in
  ``llama_decode`` is no rows of a cache: the last ``mamba_conv_kernel -
  1`` rows of ``[x | B | C]`` and the scan's state ``[B, heads, head_dim,
  state]`` float32. With it come that family's scalars: no rope
  (``use_rope``), and ``embedding_multiplier``, ``residual_multiplier``,
  ``logits_scaling`` and ``attention_multiplier``.
- A seventh operator, ``"kda"`` (Kimi delta attention, Kimi Linear's gated
  delta rule with a decay a channel, as Ling-3.0-flash's ``bailing_hybrid``
  has it; ``_kda``): one in-projection to the queries, keys, values, the
  decay's gate and the output's gate, depthwise causal taps and a SiLU over
  ``[q | k | v]`` (``_causal_taps`` again), L2-normed queries and keys, a
  bounded log-decay a channel, the delta rule (``ops.kda.kda``: a chunked
  Pallas kernel, or the recurrence itself), an RMSNorm a head times a
  sigmoid gate and an out-projection. Its state in ``llama_decode`` is the
  last ``kda_conv_kernel - 1`` rows of ``[q | k | v]`` before their taps
  and the rule's state ``[B, heads, head_dim, head_dim]`` float32. With it
  come latent attention with full-rank queries (``q_lora_rank`` 0: one
  matrix ``wq``, no ``wq_a`` and no norm between) and a head-wise gate on
  the plain ``latent`` operator too.
- An eighth operator, ``"sliding_attention"`` (Mellum2-12B-A2.5B has it
  in three layers of four): grouped-query attention whose query ``t`` sees
  keys ``t - sliding_window + 1 .. t``. Its leaves are the ``attention``
  layers' own, so ``_layer`` is told which it is (``operator``); its
  queries and keys turn under plain rope while the ``attention`` layers
  beside it take ``rope_scaling`` (YaRN: ``_yarn_rope``); a forward pass
  hands ``ops.attention`` the window (the equal-width flash forward walks
  the window's key blocks alone), and ``llama_decode`` keeps a sliding
  layer's last ``sliding_window`` keys and values where an ``attention``
  layer keeps ``max_len``. The two kinds may differ by more than the window
  (Laguna-XS.2 has all of it): the sliding layers their own count of query
  heads on the same key/value heads (``swa_num_heads``: ``wq``, ``wo`` and
  the gate's leaf have one shape a kind, the stacks being a kind's own)
  and their own ``swa_rope_theta``, a full layer turning the first
  ``partial_rotary_factor`` of its head alone (``_yarn_rope``'s
  ``rotary_dim``), and both gating each head's output (``head_gate``) as
  the latent operators do.
- A ninth operator, ``"indexed_attention"`` (Keye-VL-2.0-30B-A3B's
  language model has it in every layer): grouped-query attention whose
  query attends the ``index_topk`` keys a learned indexer chooses, the
  indexed latent operator's indexer on the ``attention`` layers' leaves.
  There is no query latent, so the indexer's queries come from the block's
  normed input, and its whole head turns (``_index_queries_and_key`` is
  told both); the choice reaches ``ops.attention`` as ``keep`` and the
  equal-width flash forward masks by it; ``llama_decode`` keeps a layer's
  index keys beside its keys and values. With it comes multi-axis rope
  (``mrope_section``): a token has three positions and each of a head's
  frequency pairs turns by one of them (``_rope``).
- Attention dispatches to ``ray_tpu.ops`` (Pallas flash attention on TPU,
  reference einsum path elsewhere; ring attention when the seq axis > 1).
- bfloat16 activations / fp32 params+optimizer by default: MXU-native.

Reference capability being replaced: Train users bring HF torch models
(reference: python/ray/train/huggingface/, release/air_examples/gptj_deepspeed
_finetuning); here the model is framework-native.
"""

from __future__ import annotations

import dataclasses
import math
from functools import cache, partial
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import moe
from ray_tpu.ops.attention import attention
from ray_tpu.parallel.sharding import constrain


def _ring_seq_attention(q, k, v):
    """Sequence-parallel exact attention: shard_map over the ambient mesh's
    ``seq`` axis; kv chunks ride the ICI ring (ops.ring_attention)."""
    from ray_tpu.ops.ring_attention import ring_attention
    from ray_tpu.parallel.sharding import logical_to_spec

    qs = logical_to_spec(("batch", "seq", "heads", "head_dim"))
    fn = jax.shard_map(
        partial(ring_attention, axis_name="seq", causal=True),
        in_specs=(qs, qs, qs), out_specs=qs, check_vma=False)
    return fn(q, k, v)


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN, as DeepSeek-V2's ``rope_scaling`` gives it: each rotary
    frequency a blend of the extrapolated one (``theta``'s own) and the
    interpolated one (that over ``factor``) by a linear ramp between the
    two correction dims, the dims that turn ``beta_fast`` and ``beta_slow``
    times over ``original_max_position_embeddings`` positions; cos and sin
    times ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``, and
    the softmax scale times ``mscale(factor, mscale_all_dim) ** 2``."""
    factor: float = 1.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    def rotary_amplitude(self) -> float:
        """What cos and sin are multiplied by."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    def softmax_amplitude(self) -> float:
        """What the softmax scale is multiplied by: ``m ** 2``."""
        return self._mscale(self.factor, self.mscale_all_dim) ** 2

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """The ``dim / 2`` rotary frequencies, float32."""
        exponent = np.arange(0, dim, 2, dtype=np.float32) / dim
        extrapolated = 1.0 / theta ** exponent
        interpolated = 1.0 / (self.factor * theta ** exponent)

        def correction_dim(rotations: float) -> float:
            return (dim * math.log(self.original_max_position_embeddings
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), dim - 1)
        if low == high:
            high += 0.001  # the ramp's own guard against a zero span
        ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                       / (high - low), 0.0, 1.0)
        return (interpolated * ramp + extrapolated * (1.0 - ramp)
                ).astype(np.float32)


# the operators that `_latent_attention` computes
LATENT_OPERATORS = ("latent", "window", "indexed")
# the operators whose leaves are grouped-query attention's: every causal
# key, the last `sliding_window` of them, or the `index_topk` an indexer
# chooses (its leaves beside them)
ATTENTION_OPERATORS = ("attention", "sliding", "chosen")


class LatentWidths(NamedTuple):
    """One latent operator's geometry (``LlamaConfig.latent_widths``):
    heads, the two ranks, a head's own and rotary key dims and its value
    dims, the rotary base, and what limits a query's keys: ``window`` (0:
    none) and ``topk`` (0: no indexer)."""
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    theta: float
    window: int
    topk: int


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden: int = 4096
    mlp_hidden: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16      # activation dtype
    param_dtype: Any = jnp.float32
    remat: bool = True             # checkpoint each layer (HBM↔FLOPs trade)
    remat_policy: str = "dots"     # dots (save matmuls) | full (recompute all)
    attn_impl: str = "auto"        # auto | flash | reference | ring_seq
    loss_chunk: int = 0            # >0: lm-head CE in seq chunks of this size
    #   (peak float32 logits memory B*chunk*V instead of B*S*V; the backward
    #    recomputes each chunk's softmax under jax.checkpoint, and under
    #    remat_policy "full" the chunk's matmul too)
    # What the model is, beyond the dense decoder (OLMoE-1B-7B has all of
    # it): with num_experts > 0 the block's feed-forward is models/moe.py's
    # routed experts, each of width mlp_hidden, experts_per_token a
    # position; qk_norm puts an RMSNorm with a learned weight over the
    # whole query and the whole key projection, before the heads and rope.
    num_experts: int = 0
    experts_per_token: int = 0
    norm_topk_prob: bool = False   # renormalise the chosen experts' weights
    router_aux_loss_coef: float = 0.0  # load-balancing term in llama_loss
    qk_norm: bool = False
    # The router's other variants (models/moe.py's docstring; the defaults
    # are OLMoE's router)
    router_scores: str = "softmax"     # softmax | sigmoid
    router_bias: bool = False          # choose on scores + a buffer [E]
    router_norm_eps: float = 0.0       # beside the renormalising sum
    routed_scaling_factor: float = 1.0
    # The layer pattern (LFM2-24B-A2B has all of it). layer_types names
    # each layer's operator, "full_attention" or "conv" (empty: attention
    # everywhere); conv is the gated short convolution of conv_kernel taps.
    # The first num_dense_layers layers of a model with experts keep a
    # dense SwiGLU, of width dense_mlp_hidden. qk_head_norm puts an RMSNorm
    # with one learned weight [head_dim] over each head of the queries and
    # of the keys, before rope (qk_norm's is over the whole projection: two
    # norms, both kept). tie_embeddings: the head is the embedding's
    # transpose and params has no "lm_head".
    layer_types: Tuple[str, ...] = ()
    conv_kernel: int = 3
    num_dense_layers: int = 0
    dense_mlp_hidden: int = 0
    qk_head_norm: bool = False
    tie_embeddings: bool = False
    # Latent attention and what comes with it (DeepSeek-V2 has all of it).
    # layer_types' third operator, "latent_attention": the queries are
    # RMSNorm(x W_qa [q_lora_rank]) W_qb, num_heads heads of
    # qk_nope_head_dim + qk_rope_head_dim; x W_kva gives kv_lora_rank
    # latent dims, normed, and qk_rope_head_dim rotary dims that are every
    # head's rotary key; the latent row W_kvb gives each head its
    # qk_nope_head_dim of key and v_head_dim of value (head_dim and
    # num_kv_heads are not read). rope_scaling: YaRN over the rotary dims
    # (None: theta's own frequencies, scale width ** -0.5).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[RopeScaling] = None
    # The routed feed-forward's other parts (models/moe.py's docstring).
    # num_shared_experts: a dense SwiGLU of that many times mlp_hidden
    # beside the routed experts, every position's. router_groups > 0: the
    # group-limited choice, the experts in that many groups of which a
    # position's best router_topk_groups stay. experts_held (first,
    # count): the experts of each routed layer that live here, one chip's
    # share; the router stays num_experts wide (None: all of them).
    num_shared_experts: int = 0
    router_groups: int = 0
    router_topk_groups: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    # Latent attention's other operators (dots3-note-prev has all of it).
    # "window_latent_attention": the swa_ fields are its geometry, as the
    # seven above are the full layers', and query t sees keys t -
    # sliding_window + 1 .. t. "indexed_latent_attention": the full
    # geometry, and a query attends its index_topk keys of largest index
    # score I[t, s] = sum_j w[t, j] ReLU(q_i[t, j] . k_i[s]) over
    # index_heads heads of index_head_dim (every key while t < index_topk).
    # head_gate: each head's output times sigmoid(u W_g)[head] before W_o,
    # in both. latent_rescale: c_q times (hidden / q_rank) ** 0.5 and c_kv
    # times (hidden / kv_rank) ** 0.5 after their norms, in all three.
    # sliding_window is also the window of layer_types' "sliding_attention"
    # (Mellum2-12B-A2.5B): grouped-query attention at the attention layers'
    # own widths, under plain rope, while the "full_attention" layers
    # beside it take rope_scaling. Where the two kinds differ by more than
    # the window (Laguna-XS.2), the swa_ fields say the sliding layers'
    # side here too: swa_num_heads query heads (0: num_heads; the key/value
    # heads and head_dim are one for both) and swa_rope_theta (0:
    # rope_theta), over the whole head, while a full layer turns the first
    # partial_rotary_factor of its head, under rope_scaling reckoned over
    # those dims, and passes the rest through; head_gate is honoured by
    # both (leaf w_head_gate [hidden, the kind's heads]).
    sliding_window: int = 0
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    partial_rotary_factor: float = 1.0
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    head_gate: bool = False
    latent_rescale: bool = False
    # The indexer on grouped-query attention, and multi-axis rope
    # (Keye-VL-2.0-30B-A3B's language model has both). layer_types'
    # "indexed_attention": the attention layers' widths and leaves, and a
    # query attends its index_topk keys of largest index score as an
    # indexed latent operator's does; there is no query latent, so the
    # indexer's queries are u W_iq from the block's normed input, and its
    # whole index_head_dim turns under rope. mrope_section (empty: one
    # stream): a token has three positions (temporal, height, width) and
    # the sections say how many of a head's head_dim / 2 frequency pairs,
    # in order, turn by each; the grouped-query operators' queries and keys
    # turn so (no rope_scaling, no partial turn beside it), an indexer's by
    # the first stream; positions [B, S] are three equal streams.
    mrope_section: Tuple[int, ...] = ()
    # The state-space operator and the scalars of its family
    # (granite-4.0-h has all of it). layer_types' "mamba": Mamba-2's mixer,
    # mamba_heads heads of mamba_head_dim channels (together the inner
    # width, mamba_expand x hidden in the source's terms) over a state of
    # mamba_state dims, B and C one row a position for all heads (one
    # group), mamba_conv_kernel depthwise causal taps with a bias over [x |
    # B | C], scanned in chunks of mamba_chunk by the kernel. use_rope
    # False: attention's queries and keys are not rotated ("nope").
    # embedding_multiplier scales the embedded tokens, residual_multiplier
    # every sub-layer's output before it joins the residual, logits are
    # divided by logits_scaling, and attention_multiplier, where not 0, is
    # the softmax scale in place of head_dim ** -0.5.
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_state: int = 0
    mamba_conv_kernel: int = 4
    mamba_chunk: int = 256
    use_rope: bool = True
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attention_multiplier: float = 0.0
    # Kimi delta attention (Ling-3.0-flash has it). layer_types' "kda":
    # kda_heads heads of kda_head_dim key and value channels each, the
    # queries, keys and values through kda_conv_kernel depthwise causal
    # taps without a bias and a SiLU, queries and keys L2-normed a head,
    # the log-decay a channel kda_lower_bound * sigmoid(exp(A_log) * (a +
    # dt_bias)), in (kda_lower_bound, 0), the rule run in chunks of
    # kda_chunk by the kernel. router_group_score: what a group of the
    # group-limited choice is scored by, its best expert ("max") or the
    # sum of its two best ("top2", DeepSeek-V3's noaux_tc).
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_chunk: int = 64
    kda_lower_bound: float = -5.0
    router_group_score: str = "max"

    @staticmethod
    def llama2_7b_smoke() -> "LlamaConfig":
        """Llama-2-7B at full width (hidden 4096, MLP 11008, 32 heads x
        128, vocabulary 32000, sequence 2048), cut to 2 layers: 667M
        parameters, so fp32 params + AdamW moments + grads (16 B each) and
        a batch of 4 fit one 16 GB v5e chip (8.0 GB resident after a step,
        chip_smoke.py, PR 21). The flash kernel is named, not chosen; the
        chunked loss keeps the fp32 logits to B x 512 x V."""
        return LlamaConfig(num_layers=2, max_seq_len=2048, attn_impl="flash",
                           loss_chunk=512)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "LlamaConfig":
        return LlamaConfig(vocab_size=vocab_size, hidden=128, mlp_hidden=352,
                           num_layers=2, num_heads=4, num_kv_heads=2,
                           head_dim=32, max_seq_len=256, remat=False)

    @staticmethod
    def debug_1l() -> "LlamaConfig":
        return LlamaConfig(vocab_size=128, hidden=64, mlp_hidden=176,
                           num_layers=1, num_heads=2, num_kv_heads=1,
                           head_dim=32, max_seq_len=128, remat=False)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, ``<operator>_<feed-forward>``: ``attention``,
        ``sliding``, ``chosen``, ``conv``, ``latent``, ``window``,
        ``indexed``, ``mamba`` or ``kda``, then ``routed`` (experts) or
        ``dense``."""
        ops = tuple(self.layer_types) or ("full_attention",) * self.num_layers
        if len(ops) != self.num_layers:
            raise ValueError(f"layer_types names {len(ops)} layers, "
                             f"num_layers is {self.num_layers}")
        names = {"full_attention": "attention",
                 "sliding_attention": "sliding",
                 "indexed_attention": "chosen", "conv": "conv",
                 "latent_attention": "latent",
                 "window_latent_attention": "window",
                 "indexed_latent_attention": "indexed", "mamba": "mamba",
                 "kda": "kda"}
        unknown = sorted(set(ops) - set(names))
        if unknown:
            raise ValueError(f"layer_types {unknown}: expected "
                             + "|".join(repr(n) for n in names))
        return tuple(
            names[op] + ("_routed" if self.num_experts
                         and i >= self.num_dense_layers else "_dense")
            for i, op in enumerate(ops))

    def layer_places(self) -> Tuple[Tuple[str, int], ...]:
        """Where each layer's leaves lie: ``(kind, index among the layers
        of that kind)``, in the model's order."""
        seen: Dict[str, int] = {}
        places = []
        for kind in self.layer_kinds():
            places.append((kind, seen.get(kind, 0)))
            seen[kind] = places[-1][1] + 1
        return tuple(places)

    def layer_runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """The runs of like layers in the model's order: ``(kind, index of
        the run's first layer among the layers of its kind, layers)``."""
        runs = []
        for kind, j in self.layer_places():
            if runs and runs[-1][0] == kind:
                runs[-1][2] += 1
            else:
                runs.append([kind, j, 1])
        return tuple(tuple(r) for r in runs)

    def kind_counts(self) -> Dict[str, int]:
        """How many layers of each kind, in the order they first appear."""
        kinds = self.layer_kinds()
        return {k: kinds.count(k) for k in dict.fromkeys(kinds)}

    def latent_widths(self, operator: str = "latent") -> "LatentWidths":
        """What ``_latent_attention`` is told of a latent operator
        (``latent``, ``window`` or ``indexed``)."""
        if operator == "window":
            return LatentWidths(
                self.swa_num_heads, self.swa_q_lora_rank,
                self.swa_kv_lora_rank, self.swa_qk_nope_head_dim,
                self.swa_qk_rope_head_dim, self.swa_v_head_dim,
                self.swa_rope_theta or self.rope_theta, self.sliding_window,
                0)
        return LatentWidths(
            self.num_heads, self.q_lora_rank, self.kv_lora_rank,
            self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim,
            self.rope_theta, 0,
            self.index_topk if operator == "indexed" else 0)

    def attention_heads(self, operator: str = "attention") -> int:
        """The query heads of a grouped-query operator (``attention``,
        ``sliding`` or ``chosen``): the sliding layers' own count where one
        is given."""
        return (self.swa_num_heads or self.num_heads
                if operator == "sliding" else self.num_heads)

    def rotary_dim(self) -> int:
        """The leading dims of a head that a full layer's rope turns."""
        return int(self.head_dim * self.partial_rotary_factor)

    def mamba_widths(self) -> Tuple[int, int, int]:
        """The state-space operator's ``(inner, conv, proj)`` widths: its
        heads' channels, what the taps run over (``[x | B | C]``) and what
        the in-projection makes (``[z | x B C | dt]``)."""
        inner = self.mamba_heads * self.mamba_head_dim
        conv = inner + 2 * self.mamba_state
        return inner, conv, inner + conv + self.mamba_heads

    def kda_widths(self) -> Tuple[int, int]:
        """Kimi delta attention's ``(inner, proj)`` widths: its heads'
        channels, and what the in-projection makes (``[q | k | v | the
        decay's gate | the output's gate]``)."""
        inner = self.kda_heads * self.kda_head_dim
        return inner, 5 * inner

    def dense_width(self) -> int:
        """Width of a dense SwiGLU: the leading dense layers' own in a
        model with experts, whose ``mlp_hidden`` is one expert's."""
        return (self.dense_mlp_hidden if self.num_experts
                else self.mlp_hidden)

    def num_params(self) -> int:
        h, v, E = self.hidden, self.vocab_size, self.num_experts
        kv = self.num_kv_heads * self.head_dim
        held = self.experts_held[1] if self.experts_held else E

        def latent(operator):
            w = self.latent_widths(operator)
            gate = h * w.heads if self.head_gate else 0
            # the queries: a low-rank pair with a norm between, or one matrix
            queries = (h * w.q_rank + w.q_rank
                       + w.q_rank * w.heads * (w.nope + w.rope)
                       if w.q_rank else h * w.heads * (w.nope + w.rope))
            return (queries + h * (w.kv_rank + w.rope) + w.kv_rank
                    + w.kv_rank * w.heads * (w.nope + w.v)
                    + w.heads * w.v * h + gate)

        ih, ihd = self.index_heads, self.index_head_dim
        inner, conv, proj = self.mamba_widths()
        kda_inner, kda_proj = self.kda_widths()
        def grouped_query(operator):
            q = self.attention_heads(operator) * self.head_dim
            norms = ((q + kv) if self.qk_norm
                     else 2 * self.head_dim if self.qk_head_norm else 0)
            gate = h * self.attention_heads(operator) if self.head_gate else 0
            return h * (q + 2 * kv) + q * h + norms + gate

        # an indexer's own leaves beside its queries: its one key with a
        # LayerNorm's weight and bias, its heads' weights
        index_key_and_weights = h * ihd + 2 * ihd + h * ih
        half = {"attention": grouped_query("attention"),
                "sliding": grouped_query("sliding"),
                "chosen": (grouped_query("chosen") + h * ih * ihd
                           + index_key_and_weights),
                # in-projection, beta's, the taps, A_log a head, dt_bias a
                # channel, the norm's one weight a head's channel, out
                "kda": (h * kda_proj + h * self.kda_heads
                        + 3 * kda_inner * self.kda_conv_kernel
                        + self.kda_heads + kda_inner + self.kda_head_dim
                        + kda_inner * h),
                # in-projection, taps and their bias, dt_bias, A_log and D
                # a head, the gated norm, out-projection
                "mamba": (h * proj + conv * (self.mamba_conv_kernel + 1)
                          + 3 * self.mamba_heads + inner + inner * h),
                "conv": 4 * h * h + h * self.conv_kernel,
                "latent": latent("latent"), "window": latent("window"),
                "indexed": (latent("indexed") + self.q_lora_rank * ih * ihd
                            + index_key_and_weights),
                "routed": ((held + self.num_shared_experts) * 3 * h
                           * self.mlp_hidden + h * E
                           + (E if self.router_bias else 0)),
                "dense": 3 * h * self.dense_width()}
        layers = sum(
            n * (sum(half[part] for part in kind.split("_")) + 2 * h)
            for kind, n in self.kind_counts().items())
        return layers + (1 if self.tie_embeddings else 2) * v * h + h


@dataclasses.dataclass(frozen=True)
class LoraConfig:
    """Low-rank adaptation of the projection weights (frozen base).

    The reference fine-tunes LLMs by wrapping HF models with peft
    (reference: release/air_examples/gptj_deepspeed_finetuning,
    release/release_tests.yaml LLM fine-tune gates); here LoRA is native:
    adapters are a separate pytree, the base never enters the optimizer, and
    the deltas are applied activation-side (two thin matmuls per projection —
    never materializing the full-rank update, so remat recompute stays cheap).
    """
    rank: int = 16
    alpha: float = 32.0
    targets: Tuple[str, ...] = ("wq", "wk", "wv", "wo",
                                "w_gate", "w_up", "w_down")
    param_dtype: Any = jnp.float32

    @property
    def scale(self) -> float:
        return self.alpha / self.rank

    def num_params(self, cfg: LlamaConfig) -> int:
        h, m, r = cfg.hidden, cfg.dense_width(), self.rank
        nkv, hd = cfg.num_kv_heads, cfg.head_dim

        def per(kind):
            nh = cfg.attention_heads(kind.split("_")[0])
            return {"wq": h * r + r * nh * hd, "wk": h * r + r * nkv * hd,
                    "wv": h * r + r * nkv * hd, "wo": nh * hd * r + r * h,
                    "w_gate": h * r + r * m, "w_up": h * r + r * m,
                    "w_down": m * r + r * h}

        counts = cfg.kind_counts()
        return sum(counts[kind] * per(kind)[t]
                   for kind, ts in _lora_targets(cfg, self).items()
                   for t in ts)


# (in_axes of A, out_axes of B) per adaptable projection; the A/B shapes are
# in_axes+(rank,) and (rank,)+out_axes with a leading dim over the layers
# that have the projection.
_LORA_SHAPES = {
    "wq": (("embed",), ("heads", "head_dim")),
    "wk": (("embed",), ("kv_heads", "head_dim")),
    "wv": (("embed",), ("kv_heads", "head_dim")),
    "wo": (("heads", "head_dim"), ("embed",)),
    "w_gate": (("embed",), ("mlp",)),
    "w_up": (("embed",), ("mlp",)),
    "w_down": (("mlp",), ("embed",)),
}


def _lora_targets(cfg: LlamaConfig, lcfg: LoraConfig
                  ) -> Dict[str, Tuple[str, ...]]:
    """kind of layer -> the targets its layers have: attention's four
    projections in an attention layer, the SwiGLU's three in a layer with
    a dense feed-forward. A target that no layer of the model has raises
    by name."""
    half = {t: "dense" if t.startswith("w_") else "attention"
            for t in _LORA_SHAPES}
    unknown = sorted(set(lcfg.targets) - set(half))
    if unknown:
        raise ValueError(f"LoRA targets {unknown}: expected some of "
                         f"{sorted(half)}")
    def halves(kind):
        operator, ffn = kind.split("_")
        return ("attention" if operator in ATTENTION_OPERATORS else operator,
                ffn)

    out = {kind: tuple(t for t in lcfg.targets if half[t] in halves(kind))
           for kind in cfg.kind_counts()}
    absent = sorted(t for t in lcfg.targets
                    if not any(t in ts for ts in out.values()))
    if absent:
        raise ValueError(
            f"LoRA targets {absent}: no layer of this model has them (its "
            f"layers are {cfg.kind_counts()}; routed experts and the short "
            "convolution are not adapted)")
    return {kind: ts for kind, ts in out.items() if ts}


def _by_kind(layers: Dict, cfg: LlamaConfig) -> Dict[str, Dict]:
    """``params["layers"]`` (or an adapter tree's) as kind -> stacked
    pytree: a model of one kind keeps the stack itself there."""
    kinds = list(cfg.kind_counts())
    return {kinds[0]: layers} if len(kinds) == 1 else layers


def _as_layers(by_kind: Dict[str, Dict], cfg: LlamaConfig) -> Dict:
    """The inverse of ``_by_kind``."""
    kinds = list(cfg.kind_counts())
    return by_kind.get(kinds[0], {}) if len(kinds) == 1 else by_kind


def _lora_dims(cfg: LlamaConfig, kind: str):
    return {"embed": (cfg.hidden,), "mlp": (cfg.dense_width(),),
            "heads": (cfg.attention_heads(kind.split("_")[0]),),
            "kv_heads": (cfg.num_kv_heads,), "head_dim": (cfg.head_dim,)}


def init_lora(cfg: LlamaConfig, lcfg: LoraConfig, key: jax.Array) -> Dict:
    """A ~ truncated-normal fan-in, B = 0 (the adapted model starts exactly
    at the base), stacked over the layers of each kind that have the
    projection, for the scanned body; the tree has ``params["layers"]``'s
    shape (``_by_kind``)."""
    r, counts = lcfg.rank, cfg.kind_counts()
    keys = dict(zip(lcfg.targets, jax.random.split(key, len(lcfg.targets))))
    by_kind = {}
    for j, (kind, targets) in enumerate(_lora_targets(cfg, lcfg).items()):
        L, out, dims = counts[kind], {}, _lora_dims(cfg, kind)
        for name in targets:
            k = keys[name] if j == 0 else jax.random.fold_in(keys[name], j)
            in_ax, out_ax = _LORA_SHAPES[name]
            in_shape = sum((dims[a] for a in in_ax), ())
            out_shape = sum((dims[a] for a in out_ax), ())
            fan_in = 1
            for d in in_shape:
                fan_in *= d
            a = (jax.random.truncated_normal(
                k, -2, 2, (L,) + in_shape + (r,), jnp.float32)
                * fan_in ** -0.5).astype(lcfg.param_dtype)
            b = jnp.zeros((L, r) + out_shape, lcfg.param_dtype)
            out[name] = {"a": a, "b": b}
        by_kind[kind] = out
    return {"layers": _as_layers(by_kind, cfg)}


def lora_logical_axes(cfg: LlamaConfig, lcfg: LoraConfig) -> Dict:
    """Rank dim stays unsharded (it is tiny); in/out dims shard like the
    base weight they adapt so the activation-side matmuls need no extra
    resharding."""
    by_kind = {}
    for kind, targets in _lora_targets(cfg, lcfg).items():
        by_kind[kind] = {
            name: {"a": (None,) + _LORA_SHAPES[name][0] + (None,),
                   "b": (None, None) + _LORA_SHAPES[name][1]}
            for name in targets}
    return {"layers": _as_layers(by_kind, cfg)}


def merge_lora(params: Dict, lora: Dict, cfg: LlamaConfig,
               lcfg: LoraConfig) -> Dict:
    """Fold adapters into the base weights (for serving/export)."""
    by_kind = {kind: dict(layers)
               for kind, layers in _by_kind(params["layers"], cfg).items()}
    for kind, adapters in _by_kind(lora["layers"], cfg).items():
        layers = by_kind[kind]
        for name, ab in adapters.items():
            w = layers[name]
            a2 = ab["a"].reshape(w.shape[0], -1, lcfg.rank)
            b2 = ab["b"].reshape(w.shape[0], lcfg.rank, -1)
            delta = jnp.einsum("lir,lro->lio", a2.astype(jnp.float32),
                               b2.astype(jnp.float32)) * lcfg.scale
            layers[name] = (w.astype(jnp.float32)
                            + delta.reshape(w.shape)).astype(w.dtype)
    return dict(params, layers=_as_layers(by_kind, cfg))


# an indexer's leaves beside its queries', which come from the operator's
# own source
_INDEX_KEY_AXES = {"wi_k": ("embed", None), "wi_k_norm": ("norm",),
                   "wi_k_bias": ("norm",), "wi_w": ("embed", None)}


def _kind_logical_axes(cfg: LlamaConfig, kind: str) -> Dict[str, Any]:
    """One layer of a kind: leaf -> logical axes, without the leading dim
    over layers."""
    operator, ffn = kind.split("_")
    layer = {"attn_norm": ("norm",), "mlp_norm": ("norm",)}
    if operator in ATTENTION_OPERATORS:
        layer.update(wq=("embed", "heads", "head_dim"),
                     wk=("embed", "kv_heads", "head_dim"),
                     wv=("embed", "kv_heads", "head_dim"),
                     wo=("heads", "head_dim", "embed"))
        if cfg.qk_norm or cfg.qk_head_norm:
            layer.update(q_norm=("norm",), k_norm=("norm",))
        if cfg.head_gate:
            layer.update(w_head_gate=("embed", "heads"))
        if operator == "chosen":  # the indexer is whole on every device
            layer.update(wi_q=("embed", None, None), **_INDEX_KEY_AXES)
    elif operator in LATENT_OPERATORS:  # the ranks stay whole everywhere
        layer.update(wkv_a=("embed", None), kv_a_norm=("norm",),
                     wkv_b=(None, "heads", "head_dim"),
                     wo=("heads", "head_dim", "embed"))
        if cfg.latent_widths(operator).q_rank:
            layer.update(wq_a=("embed", None), q_a_norm=("norm",),
                         wq_b=(None, "heads", "head_dim"))
        else:  # full-rank queries: one matrix
            layer.update(wq=("embed", "heads", "head_dim"))
        if cfg.head_gate:
            layer.update(w_head_gate=("embed", "heads"))
        if operator == "indexed":  # the indexer is whole on every device
            layer.update(wi_q=(None, None, None), **_INDEX_KEY_AXES)
    elif operator == "mamba":  # its inner widths stay whole everywhere
        layer.update(mamba_in=("embed", None), mamba_conv_w=(None, None),
                     mamba_conv_b=(None,), mamba_dt_bias=(None,),
                     mamba_a_log=(None,), mamba_d=(None,),
                     mamba_norm=("norm",), mamba_out=(None, "embed"))
    elif operator == "kda":  # its inner widths stay whole everywhere
        layer.update(kda_in=("embed", None), kda_beta=("embed", None),
                     kda_conv_w=(None, None), kda_a_log=(None,),
                     kda_dt_bias=(None,), kda_norm=("norm",),
                     kda_out=(None, "embed"))
    else:  # the gated short convolution: in-projection, taps, out
        layer.update(conv_in=("embed", "mlp"), conv_w=("mlp", None),
                     conv_out=("mlp", "embed"))
    if ffn == "routed":
        layer.update(moe.EXPERT_LOGICAL_AXES)
        if cfg.router_bias:
            layer.update(router_bias=("expert",))
        if cfg.num_shared_experts:
            layer.update(moe.SHARED_LOGICAL_AXES)
    else:
        layer.update(w_gate=("embed", "mlp"), w_up=("embed", "mlp"),
                     w_down=("mlp", "embed"))
    return layer


def llama_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis tuples."""
    # scanned layers carry a leading 'layers' dim — replicated (None)
    by_kind = {kind: {k: (None,) + v
                      for k, v in _kind_logical_axes(cfg, kind).items()}
               for kind in cfg.kind_counts()}
    out = {
        "embed": ("vocab", "embed"),
        "layers": _as_layers(by_kind, cfg),
        "final_norm": ("norm",),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = ("embed", "vocab")
    return out


def init_llama(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Initialize params (truncated-normal fan-in scaling, fp32)."""
    h, nkv, hd = cfg.hidden, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 10)
    pd = cfg.param_dtype

    def norm_init(shape, k, fan_in):
        scale = fan_in ** -0.5
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * scale).astype(pd)

    def init_kind(kind, L, ks):
        """The L layers of a kind, stacked; ks: ten keys, of which the
        eighth and ninth are the embedding's and the head's."""
        operator, ffn = kind.split("_")

        def a_layer_at_a_time(shape, k, fan_in):
            # as moe.init_experts draws its stacks: a float32 draw of
            # all layers' wo on its way to bf16 is gigabytes
            return jax.lax.map(lambda lk: norm_init(shape, lk, fan_in),
                               jax.random.split(k, L))

        def indexer(source, kiq, kik, kiw):
            """An indexer's leaves, its queries made from ``source`` dims
            (the query latent's, or the block's input's)."""
            ih, ihd = cfg.index_heads, cfg.index_head_dim
            return dict(
                wi_q=a_layer_at_a_time((source, ih, ihd), kiq, source),
                wi_k=norm_init((L, h, ihd), kik, h),
                wi_k_norm=jnp.ones((L, ihd), pd),
                wi_k_bias=jnp.zeros((L, ihd), pd),
                wi_w=norm_init((L, h, ih), kiw, h))

        if operator in ATTENTION_OPERATORS:
            nh = cfg.attention_heads(operator)
            layers = {
                "wq": norm_init((L, h, nh, hd), ks[0], h),
                "wk": norm_init((L, h, nkv, hd), ks[1], h),
                "wv": norm_init((L, h, nkv, hd), ks[2], h),
                "wo": norm_init((L, nh, hd, h), ks[3], nh * hd),
            }
            if cfg.head_gate:
                layers["w_head_gate"] = norm_init(
                    (L, h, nh), jax.random.fold_in(ks[3], 1), h)
            if operator == "chosen":
                layers.update(indexer(h, *jax.random.split(
                    jax.random.fold_in(ks[3], 2), 3)))
        elif operator in LATENT_OPERATORS:
            nl, qr, kvr, nope, rope, vd = cfg.latent_widths(operator)[:6]
            kq, kk = jax.random.split(ks[1])

            queries = {
                "wq_a": a_layer_at_a_time((h, qr), ks[0], h),
                "q_a_norm": jnp.ones((L, qr), pd),
                "wq_b": a_layer_at_a_time((qr, nl, nope + rope), kq, qr),
            } if qr else {
                "wq": a_layer_at_a_time((h, nl, nope + rope), ks[0], h)}
            layers = {
                **queries,
                "wkv_a": a_layer_at_a_time((h, kvr + rope), kk, h),
                "kv_a_norm": jnp.ones((L, kvr), pd),
                "wkv_b": a_layer_at_a_time((kvr, nl, nope + vd), ks[2], kvr),
                "wo": a_layer_at_a_time((nl, vd, h), ks[3], nl * vd),
            }
            if cfg.head_gate or operator == "indexed":
                kg, kiq, kik, kiw = jax.random.split(
                    jax.random.fold_in(ks[3], 1), 4)
            if cfg.head_gate:
                layers["w_head_gate"] = norm_init((L, h, nl), kg, h)
            if operator == "indexed":
                layers.update(indexer(qr, kiq, kik, kiw))
        elif operator == "mamba":
            # the mixer's own leaves as mamba_ssm and transformers start
            # them, which is what sets how far a state remembers: A in 1 to
            # 16, the step size log-uniform in 0.001 to 0.1 (its bias the
            # inverse softplus), D ones, the taps and their bias uniform at
            # fan-in (torch's Conv1d)
            nm, taps = cfg.mamba_heads, cfg.mamba_conv_kernel
            inner, conv, proj = cfg.mamba_widths()
            ka, kdt, kw, kb = jax.random.split(ks[1], 4)
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                kdt, (L, nm), jnp.float32, math.log(0.001), math.log(0.1))),
                1e-4)
            layers = {
                "mamba_in": norm_init((L, h, proj), ks[0], h),
                "mamba_conv_w": jax.random.uniform(
                    kw, (L, conv, taps), jnp.float32, -taps ** -0.5,
                    taps ** -0.5).astype(pd),
                "mamba_conv_b": jax.random.uniform(
                    kb, (L, conv), jnp.float32, -taps ** -0.5,
                    taps ** -0.5).astype(pd),
                "mamba_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
                "mamba_a_log": jnp.log(jax.random.uniform(
                    ka, (L, nm), jnp.float32, 1.0, 16.0)).astype(pd),
                "mamba_d": jnp.ones((L, nm), pd),
                "mamba_norm": jnp.ones((L, inner), pd),
                "mamba_out": norm_init((L, inner, h), ks[3], inner),
            }
        elif operator == "kda":
            # the gate's leaves are drawn for the memory they give, not as
            # a training run would start them: with a fan-in scaled gate
            # and dt_bias at 0 the decay is exp(lower_bound / 2) a position
            # and a state forgets in two. A channel's memory m is drawn
            # log-uniform in 10 to 1000 positions and dt_bias set so that
            # at a = 0 the decay is exp(-1 / m): lower_bound * sigmoid(
            # exp(A_log) dt_bias) = -1 / m; exp(A_log) a head log-uniform
            # in 0.5 to 2; the gate's columns at a quarter of fan-in
            # scale, so that the data moves a channel's log-memory by some
            # tenths. The taps uniform at fan-in (torch's Conv1d).
            nk, hd_k, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
            inner, proj = cfg.kda_widths()
            ka, km, kw, kb = jax.random.split(ks[1], 4)
            a_log = jax.random.uniform(ka, (L, nk), jnp.float32,
                                       math.log(0.5), math.log(2.0))
            memory = jnp.exp(jax.random.uniform(
                km, (L, nk, hd_k), jnp.float32, math.log(10.0),
                math.log(1000.0)))
            at_rest = -jnp.log(-cfg.kda_lower_bound * memory - 1.0)
            in_scale = jnp.ones((proj,), jnp.float32).at[
                3 * inner:4 * inner].set(0.25)

            def in_projection(lk):
                return (jax.random.truncated_normal(
                    lk, -2, 2, (h, proj), jnp.float32)
                    * h ** -0.5 * in_scale).astype(pd)

            layers = {
                "kda_in": jax.lax.map(in_projection,
                                      jax.random.split(ks[0], L)),
                "kda_beta": norm_init((L, h, nk), kb, h),
                "kda_conv_w": jax.random.uniform(
                    kw, (L, 3 * inner, taps), jnp.float32, -taps ** -0.5,
                    taps ** -0.5).astype(pd),
                "kda_a_log": a_log.astype(pd),
                "kda_dt_bias": (at_rest / jnp.exp(a_log)[..., None]
                                ).reshape(L, inner).astype(pd),
                "kda_norm": jnp.ones((L, hd_k), pd),
                "kda_out": norm_init((L, inner, h), ks[3], inner),
            }
        else:
            layers = {
                "conv_in": norm_init((L, h, 3 * h), ks[0], h),
                "conv_w": norm_init((L, h, cfg.conv_kernel), ks[1],
                                    cfg.conv_kernel),
                "conv_out": norm_init((L, h, h), ks[3], h),
            }
        if ffn == "routed":
            layers.update(moe.init_experts(cfg, ks[9], L))
        else:
            m = cfg.dense_width()
            layers.update(w_gate=norm_init((L, h, m), ks[4], h),
                          w_up=norm_init((L, h, m), ks[5], h),
                          w_down=norm_init((L, m, h), ks[6], m))
        layers.update(attn_norm=jnp.ones((L, h), pd),
                      mlp_norm=jnp.ones((L, h), pd))
        if operator in ATTENTION_OPERATORS and (cfg.qk_norm
                                                or cfg.qk_head_norm):
            # over the whole projection, or one weight shared by the heads
            q, k = ((cfg.attention_heads(operator) * hd, nkv * hd)
                    if cfg.qk_norm else (hd, hd))
            layers.update(q_norm=jnp.ones((L, q), pd),
                          k_norm=jnp.ones((L, k), pd))
        return layers

    counts = cfg.kind_counts()
    # a model of one kind draws its layers from the ten keys themselves,
    # as it always has; each kind of a pattern from ten of its own
    by_kind = {
        kind: init_kind(kind, L, ks if len(counts) == 1 else
                        jax.random.split(jax.random.fold_in(key, j + 1), 10))
        for j, (kind, L) in enumerate(counts.items())}
    out = {
        "embed": norm_init((cfg.vocab_size, h), ks[7], 1.0),
        "layers": _as_layers(by_kind, cfg),
        "final_norm": jnp.ones((h,), pd),
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = norm_init((h, cfg.vocab_size), ks[8], h)
    return out


def _embed(params: Dict[str, Any], tokens: jax.Array,
           cfg: LlamaConfig) -> jax.Array:
    """The tokens' rows of the embedding in the activation type, times
    ``embedding_multiplier``."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)
    return x


def _scaled_logits(logits: jax.Array, scaling: float) -> jax.Array:
    """The head's products over ``LlamaConfig.logits_scaling``."""
    if scaling == 1.0:
        return logits
    return logits / jnp.asarray(scaling, logits.dtype)


def _lm_head(params: Dict[str, Any]) -> jax.Array:
    """The head's ``[hidden, vocab]``: its own leaf, or the embedding's
    transpose where the two are tied."""
    head = params.get("lm_head")
    return params["embed"].T if head is None else head


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def _rotate_pairs(x: jax.Array, cos: jax.Array, sin: jax.Array
                  ) -> jax.Array:
    """Rotate pairs (d, d + D/2) of the last dim — llama convention."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _rope(x: jax.Array, positions: jax.Array, theta: float,
          sections: Tuple[int, ...] = ()) -> jax.Array:
    """x: [B, S, H, D]; rotate pairs (d, d + D/2) — llama convention.
    Multi-axis rope (Qwen2-VL's ``apply_multimodal_rotary_pos_emb``):
    ``positions [3, B, S]``, a token's temporal, height and width
    positions, and ``sections``, three counts that sum to ``D / 2``: pair
    ``i`` turns by the stream whose section it falls in, the first
    ``sections[0]`` pairs by the first stream and so on, each at its own
    frequency ``theta ** (-2 i / D)``. ``positions [B, S]`` are three equal
    streams, and the sections then say nothing."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 3:
        if len(sections) != 3 or sum(sections) != half:
            raise ValueError(
                f"positions{positions.shape} are three streams: "
                f"mrope_section {tuple(sections)} must deal a head's "
                f"{half} frequency pairs to them")
        stream = np.repeat(np.arange(3), sections)            # [half]
        by_stream = positions[..., None].astype(jnp.float32) * freq
        angles = jnp.where(stream == 0, by_stream[0], jnp.where(
            stream == 1, by_stream[1], by_stream[2]))         # [B,S,half]
    else:
        angles = positions[..., None].astype(jnp.float32) * freq
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    return _rotate_pairs(x, cos, sin)


def _yarn_rope(x: jax.Array, positions: jax.Array, theta: float,
               scaling: Optional[RopeScaling],
               rotary_dim: Optional[int] = None) -> jax.Array:
    """``_rope`` at ``scaling``'s frequencies and amplitude, of ``x [B, S,
    H, D]`` or, one row a position, ``[B, S, D]``; None is theta's own.
    ``rotary_dim`` under ``D``: the first ``rotary_dim`` dims turn, pairs
    ``(d, d + rotary_dim / 2)`` at the frequencies (and YaRN's correction
    dims) of a head that wide, and the rest pass through as they are, the
    amplitude not on them (HuggingFace's ``apply_rotary_pos_emb`` under a
    ``partial_rotary_factor``)."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = _yarn_rope(x[..., :rotary_dim], positions, theta, scaling)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    heads = x if x.ndim == 4 else x[:, :, None, :]
    if scaling is None:
        return _rope(heads, positions, theta).reshape(x.shape)
    angles = (positions[..., None].astype(jnp.float32)
              * scaling.inv_freq(x.shape[-1], theta))       # [B,S,half]
    amplitude = scaling.rotary_amplitude()
    return _rotate_pairs(heads, (jnp.cos(angles) * amplitude)[:, :, None, :],
                         (jnp.sin(angles) * amplitude)[:, :, None, :]
                         ).reshape(x.shape)


def _pairs_apart(w: jax.Array) -> jax.Array:
    """A projection's rotary columns from the interleaved order the source
    stores them in, ``(x0, y0, x1, y1, ...)``, to ``(x0, x1, ..., y0, y1,
    ...)``: what HuggingFace's ``apply_rotary_pos_emb`` does to the
    activations before its rotate-half, done to the weight's columns, which
    is the same product in another order and costs a pass over a weight
    where that costs one over the activations."""
    pairs = w.reshape(w.shape[:-1] + (w.shape[-1] // 2, 2))
    return jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1)


def latent_softmax_scale(cfg: LlamaConfig,
                         widths: Optional[LatentWidths] = None) -> float:
    """``(qk_nope_head_dim + qk_rope_head_dim) ** -0.5`` of a latent
    operator's ``widths`` (the full geometry's by default), times YaRN's
    ``m ** 2`` under a ``rope_scaling``."""
    w = widths or cfg.latent_widths()
    scale = (w.nope + w.rope) ** -0.5
    if cfg.rope_scaling is not None:
        scale *= cfg.rope_scaling.softmax_amplitude()
    return scale


def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
                eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def _gate_heads(out: jax.Array, u: jax.Array, w: jax.Array) -> jax.Array:
    """``head_gate``: each head of ``out [B, S, heads, D]`` times the
    sigmoid (float32) of the block's normed input ``u`` through ``w
    [hidden, heads]``, in ``out``'s type."""
    gate = jax.nn.sigmoid(jnp.einsum(
        "bsh,hn->bsn", u, w.astype(out.dtype),
        preferred_element_type=jnp.float32))
    return out * gate[..., None].astype(out.dtype)


def _index_queries_and_key(cfg: LlamaConfig, u: jax.Array,
                           source: jax.Array, lp: Dict[str, jax.Array],
                           positions: jax.Array, rd: int, theta: float):
    """The indexer's side of a position (DeepSeek-V3.2's lightning
    indexer): its queries ``q_i [B, index_heads, S, index_head_dim] =
    source W_iq`` (``source``: the operator's query latent ``c_q``, or the
    block's normed input ``u`` where it has none), its ONE key ``k_i [B,
    S, index_head_dim] = LayerNorm(u W_ik)`` (weight and bias), both
    rotated over their first ``rd`` dims at ``theta`` (rotate-half over
    those dims as they lie; by the first of three position streams), and
    its heads' weights ``[B, S, index_heads] = u W_iw`` in float32."""
    dt = cfg.dtype
    if positions.ndim == 3:
        positions = positions[0]
    q_i = jnp.einsum("bsr,rjd->bsjd", source, lp["wi_q"].astype(dt))
    k_i = _layer_norm(jnp.einsum("bsh,hd->bsd", u, lp["wi_k"].astype(dt)),
                      lp["wi_k_norm"], lp["wi_k_bias"], cfg.rms_eps)
    q_i = jnp.concatenate(
        [_rope(q_i[..., :rd], positions, theta), q_i[..., rd:]], axis=-1)
    k_i = jnp.concatenate(
        [_rope(k_i[:, :, None, :rd], positions, theta)[:, :, 0],
         k_i[..., rd:]], axis=-1)
    weights = jnp.einsum("bsh,hj->bsj", u, lp["wi_w"].astype(dt),
                         preferred_element_type=jnp.float32)
    return jnp.swapaxes(q_i, 1, 2), k_i, weights


def _chosen_keys(scores: jax.Array, seen: jax.Array, k: int) -> jax.Array:
    """Which keys a query attends: of the keys it may see (``seen``, which
    broadcasts against ``scores [..., S, T]`` float32) the ``k`` of largest
    score, all of them where it sees no more than ``k``. Exact, and no
    sort: the ``k``-th largest score of each query is found bit by bit
    (float32's order is that of its bits seen as an unsigned number, the
    sign flipped and a negative's bits inverted; 32 counts of the scores at
    or above a candidate), and a key stays if its score is at or above it,
    so keys that tie with the ``k``-th all stay (``lax.top_k`` keeps the
    lowest indices among them)."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.uint32)
    ordered = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    ordered = jnp.where(seen, ordered, jnp.uint32(0))  # below every number

    def a_bit(i, kth):
        candidate = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(ordered >= candidate[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, candidate, kth)

    kth = jax.lax.fori_loop(0, 32, a_bit,
                            jnp.zeros(scores.shape[:-1], jnp.uint32))
    return seen & (ordered >= kth[..., None])


def _latent_attention(cfg: LlamaConfig, u: jax.Array,
                      lp: Dict[str, jax.Array], positions: jax.Array,
                      state: Optional[jax.Array] = None,
                      cache_index: Optional[jax.Array] = None, *,
                      operator: str = "latent",
                      live: Optional[jax.Array] = None,
                      counts: Optional[Dict[str, jax.Array]] = None,
                      lengths: Optional[jax.Array] = None):
    """Latent attention (DeepSeek-V2's MLA) on the normed input ``u [B, S,
    H]`` -> (its output ``[B, S, H]``, the state after it or None), at the
    widths of ``operator`` (``LlamaConfig.latent_widths``).
    ``c_q = RMSNorm(u W_qa)``; ``[q_nope | q_pe] = c_q W_qb`` a head (with
    a ``q_lora_rank`` of 0 ``[q_nope | q_pe] = u W_q``: one matrix, ``wq``,
    and no norm);
    ``[c_kv | k_pe] = u W_kva``, ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] =
    c_kv W_kvb`` a head; the rotary parts rotated (``k_pe`` is one row a
    position, every head's); softmax of ``(q_nope k_nope^T + q_pe k_pe^T)
    * latent_softmax_scale`` over the keys the operator allows; the heads'
    values through ``W_o``. Under ``latent_rescale`` ``c_q`` and ``c_kv``
    are multiplied by ``(hidden / rank) ** 0.5`` after their norms.

    The keys a query sees: the causal ones; of a ``window`` operator the
    last ``sliding_window`` of them, its own among them; of an ``indexed``
    one the ``index_topk`` that the indexer scores highest
    (``_index_queries_and_key``, ``ops.index_scores``, ``_chosen_keys``:
    scores and choice in float32). With ``w_head_gate`` among the leaves,
    head ``n``'s output is multiplied by ``sigmoid(u W_g)[n]`` before
    ``W_o``. ``counts``, where given, is left ``index_kept``: the (query,
    key) pairs an indexed operator's choice kept, over the queries that
    ``live [B, S]`` marks (all without it).

    Without a state the latent rows are decompressed to every head and
    ``ops.attention`` is handed the two widths, the window, the choice and
    ``lengths [B]`` int32 (a serving step's: how many positions of each
    right-padded row are its own), past which its kernel computes no block
    and writes zeros, which ``W_o`` leaves zeros.
    ``state`` is all a decode keeps of a position, the normed ``c_kv`` and
    the rotated ``k_pe`` (and an indexed operator's index key after them):
    ``[B, max_len, kv_lora_rank + qk_rope_head_dim (+ index_head_dim)]``,
    the new rows written at ``cache_index``; of a window operator ``[B,
    sliding_window, ...]``, the last rows before this call in order, the
    new ones pushed in at the end. Attention then runs in the absorbed
    form, ``q_nope W_kvb[k]^T`` against ``c_kv`` itself and the weighted
    ``c_kv`` through ``W_kvb[v]``, so no key or value of a head is ever
    made."""
    from ray_tpu.ops.attention import index_scores

    dt = cfg.dtype
    w = cfg.latent_widths(operator)
    nope, kvr = w.nope, w.kv_rank
    scale = latent_softmax_scale(cfg, w)
    S = u.shape[1]
    if w.topk and not w.q_rank:
        raise ValueError("an indexed operator's indexer reads the queries' "
                         "latent row: q_lora_rank cannot be 0")
    with jax.named_scope("latent_attention"):
        if w.q_rank:
            c_q = _rms_norm(
                jnp.einsum("bsh,hr->bsr", u, lp["wq_a"].astype(dt)),
                lp["q_a_norm"], cfg.rms_eps)
            wq_b, wkv_a = lp["wq_b"].astype(dt), lp["wkv_a"].astype(dt)
            wkv_b = lp["wkv_b"].astype(dt)
            if cfg.latent_rescale:
                c_q = c_q * (cfg.hidden / w.q_rank) ** 0.5
            q_nope = jnp.einsum("bsr,rnd->bsnd", c_q, wq_b[..., :nope])
            q_pe = _yarn_rope(
                jnp.einsum("bsr,rnd->bsnd", c_q,
                           _pairs_apart(wq_b[..., nope:])),
                positions, w.theta, cfg.rope_scaling)
        else:  # full-rank queries: one matrix, no norm
            wq, wkv_a = lp["wq"].astype(dt), lp["wkv_a"].astype(dt)
            wkv_b = lp["wkv_b"].astype(dt)
            q_nope = jnp.einsum("bsh,hnd->bsnd", u, wq[..., :nope])
            q_pe = _yarn_rope(
                jnp.einsum("bsh,hnd->bsnd", u, _pairs_apart(wq[..., nope:])),
                positions, w.theta, cfg.rope_scaling)
        c_kv = _rms_norm(jnp.einsum("bsh,hr->bsr", u, wkv_a[:, :kvr]),
                         lp["kv_a_norm"], cfg.rms_eps)
        if cfg.latent_rescale:
            c_kv = c_kv * (cfg.hidden / kvr) ** 0.5
        k_pe = _yarn_rope(
            jnp.einsum("bsh,hr->bsr", u, _pairs_apart(wkv_a[:, kvr:])),
            positions, w.theta, cfg.rope_scaling)
        q_nope = constrain(q_nope, ("batch", "seq", "heads", None))
        q_pe = constrain(q_pe, ("batch", "seq", "heads", None))
        new_rows = [c_kv, k_pe]
        if w.topk:
            with jax.named_scope("indexer"):
                q_i, k_i, head_weights = _index_queries_and_key(
                    cfg, u, c_q, lp, positions, w.rope, w.theta)
            new_rows.append(k_i)
        keep = None
        if state is None:
            if w.topk:
                with jax.named_scope("indexer"):
                    scores = index_scores(q_i, k_i, head_weights,
                                          impl=cfg.attn_impl)
                with jax.named_scope("index_choice"):
                    at = jnp.arange(S)
                    keep = _chosen_keys(scores, at[:, None] >= at[None, :],
                                        w.topk)
            k_nope = jnp.einsum("bsr,rnd->bsnd", c_kv, wkv_b[..., :nope])
            v = jnp.einsum("bsr,rnd->bsnd", c_kv, wkv_b[..., nope:])
            k_nope = constrain(k_nope, ("batch", "seq", "heads", None))
            out = attention(q_nope, k_nope, v, impl=cfg.attn_impl,
                            causal=True, q_rope=q_pe, k_rope=k_pe,
                            scale=scale, window=w.window or None, keep=keep,
                            lengths=lengths)
        else:
            rows = jnp.concatenate(new_rows, axis=-1).astype(state.dtype)
            q_pos = jnp.arange(S) + cache_index
            if w.window:  # the last rows before this call, then the new
                held = jnp.concatenate([state, rows], axis=1)
                state = held[:, S:]
                key_pos = q_pos[0] - w.window + jnp.arange(w.window + S)
            else:
                state = held = jax.lax.dynamic_update_slice_in_dim(
                    state, rows, cache_index, axis=1)
                key_pos = jnp.arange(state.shape[1])
            seen = (q_pos[:, None] >= key_pos[None, :]) & (key_pos >= 0)
            if w.window:
                seen &= q_pos[:, None] - key_pos[None, :] < w.window
            if w.topk:
                with jax.named_scope("indexer"):
                    scores = index_scores(q_i, held[..., -k_i.shape[-1]:],
                                          head_weights, impl="reference")
                with jax.named_scope("index_choice"):
                    keep = seen = _chosen_keys(scores, seen, w.topk)
            c_all = held[..., :kvr]
            pe_all = held[..., kvr:kvr + w.rope]
            q_abs = jnp.einsum("bsnd,rnd->bsnr", q_nope, wkv_b[..., :nope])
            scores = (jnp.einsum("bsnr,btr->bnst", q_abs, c_all
                                 ).astype(jnp.float32)
                      + jnp.einsum("bsnd,btd->bnst", q_pe, pe_all
                                   ).astype(jnp.float32)) * scale
            seen = seen[None, None] if seen.ndim == 2 else seen[:, None]
            probs = jax.nn.softmax(jnp.where(seen, scores, -1e30),
                                   axis=-1).astype(dt)
            weighted = jnp.einsum("bnst,btr->bsnr", probs, c_all)
            out = jnp.einsum("bsnr,rnd->bsnd", weighted, wkv_b[..., nope:])
        if keep is not None and counts is not None:
            mine = keep if live is None else keep & live[:, :, None]
            counts["index_kept"] = jnp.sum(mine, dtype=jnp.int32)
        if "w_head_gate" in lp:
            out = _gate_heads(out, u, lp["w_head_gate"])
        out = constrain(out, ("batch", "seq", "heads", None))
        y = jnp.einsum("bsnd,ndh->bsh", out, lp["wo"].astype(dt))
    return y, state


def _chosen_attention(cfg: LlamaConfig, u: jax.Array, q: jax.Array,
                      k: jax.Array, v: jax.Array, lp: Dict[str, jax.Array],
                      positions: jax.Array, state=None,
                      cache_index: Optional[jax.Array] = None, *,
                      live: Optional[jax.Array] = None,
                      counts: Optional[Dict[str, jax.Array]] = None,
                      lengths: Optional[jax.Array] = None):
    """Grouped-query attention over the keys an indexer chooses
    (``indexed_attention``): ``q [B, S, heads, D]``, ``k``, ``v`` ``[B, S,
    kv_heads, D]`` as ``_layer`` made them (normed, rotated) -> (the heads'
    outputs ``[B, S, heads, D]``, the state after it or None). The
    indexer reads the block's normed input ``u`` for its queries too and
    turns its whole head (``_index_queries_and_key``); query ``t`` attends
    the ``index_topk`` causal keys of largest index score, every one while
    it has no more (``ops.index_scores``, ``_chosen_keys``: float32), the
    same keys for all its heads. ``counts`` is left ``index_kept`` as
    ``_latent_attention`` leaves it. Without a state the choice goes to
    ``ops.attention`` as ``keep`` beside ``lengths``; ``state`` is a
    decode's (keys, values, index keys ``[B, max_len, index_head_dim]``),
    the new rows written at ``cache_index`` and the choice made over the
    held index keys."""
    from ray_tpu.ops.attention import index_scores

    if not cfg.index_topk:
        raise ValueError("an indexed_attention layer needs index_topk > 0")
    S = q.shape[1]
    with jax.named_scope("indexer"):
        # a choice is a step function of the scores: no gradient reaches
        # the indexer through it (its own loss is its trainer's to add),
        # and none is asked of the score kernel
        q_i, k_i, head_weights = jax.lax.stop_gradient(
            _index_queries_and_key(cfg, u, u, lp, positions,
                                   cfg.index_head_dim, cfg.rope_theta))
    cached = state is not None
    if cached:
        k, v, k_i = (jax.lax.dynamic_update_slice_in_dim(
            held, new.astype(held.dtype), cache_index, axis=1)
            for held, new in zip(state, (k, v, k_i)))
        state = (k, v, k_i)
    q_pos = jnp.arange(S) + (cache_index if cached else 0)
    with jax.named_scope("indexer"):
        scores = index_scores(q_i, k_i, head_weights,
                              impl="reference" if cached else cfg.attn_impl)
    with jax.named_scope("index_choice"):
        keep = _chosen_keys(
            scores, q_pos[:, None] >= jnp.arange(k.shape[1])[None, :],
            cfg.index_topk)
    if cached:
        out = attention(q, k, v, impl="reference", causal=True,
                        q_offset=cache_index, keep=keep)
    else:
        out = attention(q, k, v, impl=cfg.attn_impl, causal=True, keep=keep,
                        lengths=lengths)
    if counts is not None:
        mine = keep if live is None else keep & live[:, :, None]
        counts["index_kept"] = jnp.sum(mine, dtype=jnp.int32)
    return out, state


def _causal_taps(z: jax.Array, w: jax.Array,
                 state: Optional[jax.Array]) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal taps over ``z [B, S, C]``: ``c_t = sum_j w[:, j]
    z_{t-(K-1)+j}`` with ``w [C, K]``, summed in float32 -> (``c [B, S,
    C]`` float32, ``z`` with the ``K - 1`` rows before it in front, whose
    last ``K - 1`` rows are the state after this call). ``state [B, K-1,
    C]`` is those rows before this call; None stands for zeros, a
    sequence's start."""
    dt, taps = z.dtype, w.shape[1]
    if state is None:
        state = jnp.zeros((z.shape[0], taps - 1, z.shape[2]), dt)
    padded = jnp.concatenate([state.astype(dt), z], axis=1)
    S = z.shape[1]
    w = w.astype(jnp.float32)                              # [C, taps]
    c = sum(w[:, j] * padded[:, j:j + S].astype(jnp.float32)
            for j in range(taps))
    return c, padded


def _short_conv(cfg: LlamaConfig, u: jax.Array, lp: Dict[str, jax.Array],
                state: Optional[jax.Array] = None):
    """The gated short convolution (LFM2's ``Lfm2ShortConv``) on the
    normed input ``u [B, S, H]`` -> (its output ``[B, S, H]``, the state
    after it). ``[B_t, C_t, X_t] = W_in u_t``; ``z = B * X``; ``c_t`` is
    the causal depthwise convolution of ``z`` over ``conv_kernel`` taps,
    ``sum_j w[:, j] z_{t-(K-1)+j}``, summed in float32; the output is
    ``W_out (C * c)``. No bias. ``state [B, K-1, H]`` is the last ``K-1``
    rows of ``z`` before this call (zeros at a sequence's start, which is
    what ``None`` stands for): all an incremental decode keeps of a row."""
    dt = cfg.dtype
    with jax.named_scope("short_conv"):
        bcx = jnp.einsum("bsh,hc->bsc", u, lp["conv_in"].astype(dt))
        gate_b, gate_c, x = jnp.split(bcx, 3, axis=-1)
        z = gate_b * x
        c, padded = _causal_taps(z, lp["conv_w"], state)
        y = jnp.einsum("bsh,hd->bsd", gate_c * c.astype(dt),
                       lp["conv_out"].astype(dt))
    return y, padded[:, z.shape[1]:]


def _mamba(cfg: LlamaConfig, u: jax.Array, lp: Dict[str, jax.Array],
           state: Optional[Tuple[jax.Array, jax.Array]] = None,
           lengths: Optional[jax.Array] = None):
    """Mamba-2's mixer (granite-4.0-h's, as ``transformers``'
    ``granitemoehybrid`` computes it) on the normed input ``u [B, S, H]``
    -> (its output ``[B, S, H]``, the state after it). ``[z | xBC | dt] = u
    W_in``; ``xBC = silu(bias + taps(xBC))``, depthwise and causal
    (``_causal_taps``); ``[x | B | C] = xBC``, ``x`` as ``mamba_heads``
    heads of ``mamba_head_dim``, ``B`` and ``C`` one row of ``mamba_state``
    a position for every head; ``dt = softplus(dt + dt_bias)`` and ``A =
    -exp(A_log)`` a head, float32, no clamp; ``y`` the state-space scan's
    (``ops.ssm.ssd_scan``: ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``,
    ``y_t = h_t C_t + D x_t``); ``RMSNorm(y * silu(z)) * g`` over the whole
    inner width, the gate BEFORE the norm; ``W_out``. No bias but the
    taps'. ``state`` is all a decode keeps of a row: the last
    ``mamba_conv_kernel - 1`` rows of ``xBC`` before its taps and the
    scan's state ``[B, heads, head_dim, mamba_state]`` float32 (None:
    zeros, a sequence's start). A forward pass and a decode's pass over a
    prompt scan by ``attn_impl`` (the kernel on the chip), a single token
    by the recurrence itself. ``lengths [B]`` int32 (a serving step's: how
    many positions of each right-padded row are its own) lets the scan stop
    at a row's end (``ops.ssm``): the scan's state is the one after the
    row's last position, and the positions past the chunk that holds it get
    ``W_out`` of zeros, which is zeros. The taps' rows are the last of the
    padded length whatever the lengths."""
    from ray_tpu.ops.ssm import ssd_scan

    dt_, f32 = cfg.dtype, jnp.float32
    B, S = u.shape[:2]
    inner, conv, _ = cfg.mamba_widths()
    n = cfg.mamba_state
    rows, h0 = (None, None) if state is None else state
    with jax.named_scope("mamba_in_proj"):
        zxbcdt = jnp.einsum("bsh,hc->bsc", u, lp["mamba_in"].astype(dt_))
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + conv],
                  zxbcdt[..., inner + conv:])
    with jax.named_scope("mamba_conv"):
        c, padded = _causal_taps(xbc, lp["mamba_conv_w"], rows)
        xbc = jax.nn.silu(c + lp["mamba_conv_b"].astype(f32)).astype(dt_)
    x = xbc[..., :inner].reshape(B, S, cfg.mamba_heads, cfg.mamba_head_dim)
    dt = jax.nn.softplus(dt.astype(f32) + lp["mamba_dt_bias"].astype(f32))
    impl = cfg.attn_impl if cfg.attn_impl in ("flash", "auto") else "reference"
    y, h = ssd_scan(x, dt, -jnp.exp(lp["mamba_a_log"].astype(f32)),
                    xbc[..., inner:inner + n], xbc[..., inner + n:],
                    lp["mamba_d"].astype(f32), chunk=cfg.mamba_chunk, h0=h0,
                    impl="reference" if state is not None and S == 1
                    else impl, lengths=lengths)
    with jax.named_scope("mamba_gated_norm"):
        y = y.reshape(B, S, inner).astype(f32) * jax.nn.silu(z.astype(f32))
        y = _rms_norm(y, lp["mamba_norm"], cfg.rms_eps).astype(dt_)
    out = jnp.einsum("bsc,ch->bsh", y, lp["mamba_out"].astype(dt_))
    return out, (padded[:, S:], h)


def _kda(cfg: LlamaConfig, u: jax.Array, lp: Dict[str, jax.Array],
         state: Optional[Tuple[jax.Array, jax.Array]] = None,
         lengths: Optional[jax.Array] = None):
    """Kimi delta attention (Kimi Linear's KDA as Ling-3.0-flash's
    ``bailing_hybrid`` configures it) on the normed input ``u [B, S, H]``
    -> (its output ``[B, S, H]``, the state after it). ``[q | k | v | a |
    z] = u W_in``, each ``kda_heads`` heads of ``kda_head_dim``; ``[q | k |
    v] = silu(taps([q | k | v]))``, depthwise and causal, no bias
    (``_causal_taps``), rounded to the activations' type; ``q`` and ``k``
    L2-normed a head, ``q`` then times ``kda_head_dim ** -0.5`` (by the
    rule's ``l2_norm``, in float32); ``beta = sigmoid(u W_beta)`` a head; the
    log-decay a channel ``g = kda_lower_bound * sigmoid(exp(A_log) * (a +
    dt_bias))``, float32, in ``(kda_lower_bound, 0)``; ``o`` the delta
    rule's (``ops.kda.kda``: ``S' = Diag(exp(g_t)) S_{t-1}``, ``S_t = S' +
    beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T q_t``); ``RMSNorm(o)``
    over each head's channels with ONE weight ``[kda_head_dim]``, times
    ``sigmoid(z)``, the gate AFTER the norm; ``W_out``. No bias, no
    positions. ``state`` is all a decode keeps of a row: the last
    ``kda_conv_kernel - 1`` rows of ``[q | k | v]`` before their taps and
    the rule's state ``[B, heads, head_dim, head_dim]`` float32 (None:
    zeros, a sequence's start). A forward pass and a decode's pass over a
    prompt run the rule by ``attn_impl`` (the kernel on the chip), a single
    token by the recurrence itself. ``lengths [B]`` int32 (a serving
    step's: how many positions of each right-padded row are its own) lets
    the rule stop at a row's end (``ops.kda``): the positions past the
    chunk that holds it get ``W_out`` of zeros, which is zeros."""
    from ray_tpu.ops.kda import kda

    dt_, f32 = cfg.dtype, jnp.float32
    B, S = u.shape[:2]
    nk, hd = cfg.kda_heads, cfg.kda_head_dim
    inner, _ = cfg.kda_widths()
    rows, s0 = (None, None) if state is None else state
    with jax.named_scope("kda_in_proj"):
        proj = jnp.einsum("bsh,hc->bsc", u, lp["kda_in"].astype(dt_))
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsh,hn->bsn", u, lp["kda_beta"].astype(dt_),
            preferred_element_type=f32))
    qkv, a, z = (proj[..., :3 * inner], proj[..., 3 * inner:4 * inner],
                 proj[..., 4 * inner:])
    with jax.named_scope("kda_conv"):
        # one pass: the taps, the SiLU and the rounding (each head's
        # queries and keys are brought to unit length by the rule itself,
        # `l2_norm`, where a head's channels lie side by side anyway)
        c, padded = _causal_taps(qkv, lp["kda_conv_w"], rows)
        qkv = jax.nn.silu(c).astype(dt_)
        q, k, v = (qkv[..., i * inner:(i + 1) * inner].reshape(B, S, nk, hd)
                   for i in range(3))
    with jax.named_scope("kda_gate"):
        # over the inner width as it lies: a head's factor once a channel
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.repeat(jnp.exp(lp["kda_a_log"].astype(f32)), hd)
            * (a.astype(f32) + lp["kda_dt_bias"].astype(f32)))
    impl = cfg.attn_impl if cfg.attn_impl in ("flash", "auto") else "reference"
    o, s = kda(q, k, v, g.reshape(B, S, nk, hd), beta, s0, cfg.kda_chunk,
               impl="reference" if state is not None and S == 1 else impl,
               l2_norm=True, lengths=lengths)
    with jax.named_scope("kda_gated_norm"):
        o = (_rms_norm(o.astype(f32), lp["kda_norm"], cfg.rms_eps)
             * jax.nn.sigmoid(z.astype(f32).reshape(B, S, nk, hd)))
        o = o.reshape(B, S, inner).astype(dt_)
    out = jnp.einsum("bsc,ch->bsh", o, lp["kda_out"].astype(dt_))
    return out, (padded[:, S:], s)


def _layer(cfg: LlamaConfig, x: jax.Array, lp: Dict[str, jax.Array],
           positions: jax.Array, kv_cache=None,
           cache_index: Optional[jax.Array] = None,
           lora: Optional[Dict[str, Any]] = None, lora_scale: float = 0.0,
           operator: Optional[str] = None,
           live: Optional[jax.Array] = None,
           lengths: Optional[jax.Array] = None):
    """One block. x: [B, S, H_model] -> (x, the layer's updated state or
    None, the layer's books or None: the router's of ``moe.expert_ffn``
    under routed experts, and ``index_kept`` of an indexed operator,
    ``_latent_attention``'s count over the queries ``live`` marks). The
    layer's kind is read off its leaves: ``conv_in`` makes the operator the
    gated short convolution, ``mamba_in`` the state-space mixer,
    ``kda_in`` Kimi delta attention and ``wkv_a`` latent attention, and
    not attention; ``router`` makes the
    feed-forward the routed experts and
    not the dense SwiGLU. Which latent operator it is (``latent``,
    ``window``, ``indexed``) the leaves do not say, nor whether attention
    sees every causal key, the last ``sliding_window`` (``sliding``:
    plain rope there, ``rope_scaling``'s YaRN in the ``attention`` layers)
    or an indexer's choice of them (``chosen``: ``_chosen_attention``):
    ``operator`` does. ``positions`` are ``[B, S]``, or under
    ``mrope_section`` a token's three streams ``[3, B, S]`` (``_rope``).
    ``kv_cache`` is the layer's own state in an incremental decode: (keys,
    values) for attention (``[B, max_len, kv_heads, head_dim]`` each, the
    new rows written at ``cache_index``; a ``chosen`` layer's index keys
    after them; of a sliding layer ``[B,
    sliding_window, ...]``, the last rows before this call in order, the
    new ones pushed in at the end), the last rows of ``z`` for the short
    convolution (``_short_conv``), the latent rows for latent attention
    (``_latent_attention``), the taps' rows and the scan's state for the
    state-space mixer (``_mamba``), the taps' rows and the rule's state
    for Kimi delta attention (``_kda``). ``lengths [B]`` int32, where a
    serving step hands them, are the right-padded rows' own lengths, for an
    operator whose kernel can stop at a row's end: ``_kda``'s,
    ``_latent_attention``'s and attention's own flash forward do (``_mamba``
    is not handed them yet: ROADMAP S10 (2)). Every sub-layer's
    output joins the residual times ``residual_multiplier``."""
    dt = cfg.dtype
    counts: Dict[str, jax.Array] = {}

    def _res(y):
        """A sub-layer's output as it joins the residual."""
        if cfg.residual_multiplier == 1.0:
            return y
        return y * jnp.asarray(cfg.residual_multiplier, dt)

    def _ld(name, t_in, eq_a, eq_b):
        """Activation-side LoRA delta: (t_in @ A) @ B * scale, or 0."""
        if lora is None or name not in lora:
            return 0
        ab = lora[name]
        t = jnp.einsum(eq_a, t_in, ab["a"].astype(dt))
        return jnp.einsum(eq_b, t, ab["b"].astype(dt)) * lora_scale

    h = _rms_norm(x, lp["attn_norm"], cfg.rms_eps)
    if "conv_in" in lp:
        y, state = _short_conv(cfg, h, lp, kv_cache)
        new_cache = None if kv_cache is None else state
        x = x + _res(y)
    elif "mamba_in" in lp:
        y, state = _mamba(cfg, h, lp, kv_cache, lengths)
        new_cache = None if kv_cache is None else state
        x = x + _res(y)
    elif "kda_in" in lp:
        y, state = _kda(cfg, h, lp, kv_cache, lengths)
        new_cache = None if kv_cache is None else state
        x = x + _res(y)
    elif "wkv_a" in lp:
        y, new_cache = _latent_attention(
            cfg, h, lp, positions, kv_cache, cache_index,
            operator=operator or "latent", live=live, counts=counts,
            lengths=lengths)
        x = x + _res(y)
    else:
        # --- attention ---
        q = (jnp.einsum("bsh,hnd->bsnd", h, lp["wq"].astype(dt))
             + _ld("wq", h, "bsh,hr->bsr", "bsr,rnd->bsnd"))
        k = (jnp.einsum("bsh,hnd->bsnd", h, lp["wk"].astype(dt))
             + _ld("wk", h, "bsh,hr->bsr", "bsr,rnd->bsnd"))
        v = (jnp.einsum("bsh,hnd->bsnd", h, lp["wv"].astype(dt))
             + _ld("wv", h, "bsh,hr->bsr", "bsr,rnd->bsnd"))
        if cfg.qk_norm:  # over the whole projection, heads x head_dim
            q = _rms_norm(q.reshape(q.shape[:2] + (-1,)), lp["q_norm"],
                          cfg.rms_eps).reshape(q.shape)
            k = _rms_norm(k.reshape(k.shape[:2] + (-1,)), lp["k_norm"],
                          cfg.rms_eps).reshape(k.shape)
        elif cfg.qk_head_norm:  # over each head's head_dim, one weight
            q = _rms_norm(q, lp["q_norm"], cfg.rms_eps)
            k = _rms_norm(k, lp["k_norm"], cfg.rms_eps)
        window = cfg.sliding_window if operator == "sliding" else None
        if operator == "sliding" and not window:
            raise ValueError("a sliding_attention layer needs "
                             "sliding_window > 0")
        if cfg.use_rope:
            # YaRN and the partial turn are the full layers': a window's
            # keys lie near the query, and its theta may be its own
            scaling = None if window else cfg.rope_scaling
            if scaling is not None and scaling.softmax_amplitude() != 1.0:
                raise ValueError(
                    "attention scales its scores by head_dim ** -0.5: a "
                    "rope_scaling with mscale_all_dim is latent attention's")
            theta = (cfg.swa_rope_theta or cfg.rope_theta if window
                     else cfg.rope_theta)
            turned = None if window else cfg.rotary_dim()
            if cfg.mrope_section:
                if scaling is not None or turned not in (None, cfg.head_dim):
                    raise ValueError(
                        "mrope_section deals a whole head's pairs at "
                        "theta's own frequencies: no rope_scaling and no "
                        "partial_rotary_factor beside it")
                q = _rope(q, positions, theta, cfg.mrope_section)
                k = _rope(k, positions, theta, cfg.mrope_section)
            else:
                q = _yarn_rope(q, positions, theta, scaling, turned)
                k = _yarn_rope(k, positions, theta, scaling, turned)
        if cfg.attention_multiplier:
            # the softmax scale's ratio to the kernels' head_dim ** -0.5,
            # taken by the queries: where it is a power of two (granite's
            # 2 ** -3) the product is exact in any float type, the flash
            # forward, its tiles and the cached decode are untouched, and
            # no kernel grows a `scale` that all but one caller leaves alone
            q = q * jnp.asarray(cfg.attention_multiplier
                                * cfg.head_dim ** 0.5, dt)
        q = constrain(q, ("batch", "seq", "heads", None))
        k = constrain(k, ("batch", "seq", "kv_heads", None))
        new_cache = None
        if operator == "chosen":
            attn_out, new_cache = _chosen_attention(
                cfg, h, q, k, v, lp, positions, kv_cache, cache_index,
                live=live, counts=counts, lengths=lengths)
        elif kv_cache is not None and window:
            # the last `window` rows before this call, then the new ones:
            # slot j holds position cache_index - window + j, so a query's
            # place among the slots is its own plus `window`, and the slots
            # before the sequence's start (zeros) are no keys
            S = q.shape[1]
            k, v = (jnp.concatenate([held, new.astype(held.dtype)], axis=1)
                    for held, new in zip(kv_cache, (k, v)))
            new_cache = (k[:, S:], v[:, S:])
            started = cache_index - window + jnp.arange(window + S) >= 0
            attn_out = attention(q, k, v, impl="reference", causal=True,
                                 q_offset=window, window=window,
                                 keep=started[None, None, :])
        elif kv_cache is not None:
            ck, cv = kv_cache  # [B, max_S, nkv, d]
            ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_index,
                                                     axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_index,
                                                     axis=1)
            k, v = ck, cv
            new_cache = (ck, cv)
            attn_out = attention(q, k, v, impl="reference", causal=True,
                                 q_offset=cache_index)
        else:
            if cfg.attn_impl == "ring_seq":
                attn_out = _ring_seq_attention(q, k, v)
            else:
                attn_out = attention(q, k, v, impl=cfg.attn_impl,
                                     causal=True, window=window,
                                     lengths=lengths)
        if "w_head_gate" in lp:
            attn_out = _gate_heads(attn_out, h, lp["w_head_gate"])
        attn_out = constrain(attn_out, ("batch", "seq", "heads", None))
        x = (x + _res(jnp.einsum("bsnd,ndh->bsh", attn_out,
                                 lp["wo"].astype(dt)))
             + _res(_ld("wo", attn_out, "bsnd,ndr->bsr", "bsr,rh->bsh")))
    # --- feed-forward: routed experts, or the dense SwiGLU ---
    h = _rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if "router" in lp:
        y, books = moe.expert_ffn(cfg, h, lp)
        return (constrain(x + _res(y), ("batch", "seq", "embed")), new_cache,
                {**books, **counts})
    gate = (jnp.einsum("bsh,hm->bsm", h, lp["w_gate"].astype(dt))
            + _ld("w_gate", h, "bsh,hr->bsr", "bsr,rm->bsm"))
    up = (jnp.einsum("bsh,hm->bsm", h, lp["w_up"].astype(dt))
          + _ld("w_up", h, "bsh,hr->bsr", "bsr,rm->bsm"))
    act = constrain(jax.nn.silu(gate) * up, ("batch", "seq", "mlp"))
    x = (x + _res(jnp.einsum("bsm,mh->bsh", act, lp["w_down"].astype(dt)))
         + _res(_ld("w_down", act, "bsm,mr->bsr", "bsr,rh->bsh")))
    x = constrain(x, ("batch", "seq", "embed"))
    return x, new_cache, counts or None


def init_decode_state(cfg: LlamaConfig, batch: int, max_len: int) -> list:
    """What ``llama_decode`` carries from call to call, a layer's own
    state in the model's order: keys and values ``[batch, max_len,
    kv_heads, head_dim]`` for an attention layer (and an
    ``indexed_attention`` layer's index keys ``[batch, max_len,
    index_head_dim]`` after them), the last ``conv_kernel -
    1`` rows of ``z`` ``[batch, conv_kernel - 1, hidden]`` for a short
    convolution, the normed latent row and the rotated shared key
    ``[batch, max_len, kv_lora_rank + qk_rope_head_dim]`` for latent
    attention (an indexed operator's index key ``[index_head_dim]`` after
    them; a window operator keeps its last ``sliding_window`` rows and not
    ``max_len``, and so do a sliding layer's keys and values), and for a state-space mixer a pair that is no rows of a
    cache: the last ``mamba_conv_kernel - 1`` rows of ``[x | B | C]``
    ``[batch, mamba_conv_kernel - 1, inner + 2 mamba_state]`` and the
    scan's state ``[batch, mamba_heads, mamba_head_dim, mamba_state]``
    float32, and for Kimi delta attention such a pair too: the last
    ``kda_conv_kernel - 1`` rows of ``[q | k | v]`` ``[batch,
    kda_conv_kernel - 1, 3 inner]`` and the rule's state ``[batch,
    kda_heads, kda_head_dim, kda_head_dim]`` float32; all zeros."""
    def latent_rows(op):
        w = cfg.latent_widths(op)
        return (batch, w.window or max_len, w.kv_rank + w.rope
                + (cfg.index_head_dim if w.topk else 0))

    operators = [kind.split("_")[0] for kind in cfg.layer_kinds()]
    shapes = {
        "attention": (batch, max_len, cfg.num_kv_heads, cfg.head_dim),
        "chosen": (batch, max_len, cfg.num_kv_heads, cfg.head_dim),
        "sliding": (batch, cfg.sliding_window, cfg.num_kv_heads,
                    cfg.head_dim),
        "conv": (batch, cfg.conv_kernel - 1, cfg.hidden),
        **{op: latent_rows(op) for op in LATENT_OPERATORS}}
    zeros = {op: jnp.zeros(shapes[op], cfg.dtype)
             for op in set(operators) - {"mamba", "kda"}}
    if "mamba" in operators:
        zeros["mamba"] = (
            jnp.zeros((batch, cfg.mamba_conv_kernel - 1,
                       cfg.mamba_widths()[1]), cfg.dtype),
            jnp.zeros((batch, cfg.mamba_heads, cfg.mamba_head_dim,
                       cfg.mamba_state), jnp.float32))
    if "kda" in operators:
        zeros["kda"] = (
            jnp.zeros((batch, cfg.kda_conv_kernel - 1,
                       3 * cfg.kda_widths()[0]), cfg.dtype),
            jnp.zeros((batch, cfg.kda_heads, cfg.kda_head_dim,
                       cfg.kda_head_dim), jnp.float32))
    def state_of(op):
        if op == "chosen":  # the index keys beside the keys and values
            return (zeros[op], zeros[op], jnp.zeros(
                (batch, max_len, cfg.index_head_dim), cfg.dtype))
        return ((zeros[op], zeros[op]) if op in ATTENTION_OPERATORS
                else zeros[op])

    return [state_of(op) for op in operators]


def llama_decode(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    kv_caches,
    cache_index: jax.Array,
    *,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, list]:
    """Incremental decode: tokens [B, S] appended to the layers' states
    (``init_decode_state``) at ``cache_index`` → (logits [B, S, V] fp32,
    updated states). Python loop over layers so each layer's state updates
    functionally in place."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32) + cache_index, (B, S))
    x = _embed(params, tokens, cfg)
    stacks = _by_kind(params["layers"], cfg)
    new_caches = []
    for i, (kind, j) in enumerate(cfg.layer_places()):
        lp = jax.tree.map(lambda a: a[j], stacks[kind])
        if "router" in lp:
            lp = moe.in_stack(lp, stacks[kind], j)
        x, c, _ = _layer(cfg, x, lp, positions, kv_caches[i], cache_index,
                         operator=kind.split("_")[0])
        new_caches.append(c)
    x = _rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = jnp.einsum("bsh,hv->bsv", x, _lm_head(params).astype(cfg.dtype))
    return (_scaled_logits(logits.astype(jnp.float32), cfg.logits_scaling),
            new_caches)


def llama_hidden(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    lora: Optional[Dict[str, Any]] = None,
    lora_cfg: Optional[LoraConfig] = None,
) -> jax.Array:
    """tokens [B, S] int32 → final hidden states [B, S, H] (activation
    dtype, post final-norm). Layers run under ``lax.scan`` with optional
    per-layer remat; LoRA adapters (if given) scan alongside the base.
    ``positions [B, S]``, or a model with ``mrope_section``'s three streams
    ``[3, B, S]``; each row's own indices where not given."""
    return _hidden_and_books(params, tokens, cfg, positions=positions,
                             lora=lora, lora_cfg=lora_cfg)[0]


def _hidden_and_books(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    lora: Optional[Dict[str, Any]] = None,
    lora_cfg: Optional[LoraConfig] = None,
    router_mask: Optional[jax.Array] = None,
    in_place: bool = False,
    lengths: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """``llama_hidden``, and with it the layers' books (``_layer``), each
    key stacked over the layers that keep it, in the model's order: the
    routers' over the layers that have routed experts
    (``moe.expert_ffn``), ``index_kept`` over the indexed operators; None
    for a model that has neither. ``router_mask
    [B, S]`` marks the positions that are a row's own: the books count
    them alone and the routed experts multiply their pairs alone, so a
    masked-out position's hidden state lacks its routed part (rows are
    padded on the right and every operator is causal: no position that is
    marked reads one that is not). Without a mask every position is
    computed and counted. With ``in_place``, which is a forward pass's to
    ask for, a run that is a proper part of its stack reads each layer
    where it lies and no slice of a stack is made (differentiated, every
    such run's backward scan would carry a whole stack's cotangent).
    ``lengths [B]`` int32 are the right-padded rows' own lengths, for the
    operators that stop at a row's end (``_layer``): the hidden states past
    it are then not a forward pass's either."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = _embed(params, tokens, cfg)
    x = constrain(x, ("batch", "seq", "embed"))

    scale = lora_cfg.scale if lora_cfg is not None else 0.0
    stacks = _by_kind(params["layers"], cfg)
    lo_stacks = _by_kind(lora["layers"], cfg) if lora is not None else {}

    def scan_over(kind, start, n):
        """(scan body, xs) over layers ``start`` to ``start + n`` of a
        kind's stack. A run that is a proper part of its stack is handed
        its slice of every leaf, or with ``in_place`` its layers' indices
        alone, and the body reads each layer where it lies. With experts
        the body also sees the whole stack and its own index in it."""
        stack, lo_stack = stacks[kind], lo_stacks.get(kind) or {}
        routed = "router" in stack
        whole = jax.tree.leaves(stack)[0].shape[0] == n
        reads = in_place and not whole

        def part(tree):
            # xs is a pytree of arrays: no adapters are an empty dict, and
            # so is what the body reads for itself
            if whole:
                return tree
            if reads:
                return {}
            return jax.tree.map(lambda a: a[start:start + n], tree)

        index = jnp.arange(start, start + n) if routed or reads else None

        def scan_fn(carry, xs):
            lp, lo_i, i = xs
            if reads:
                # what `lax.scan` lowers `xs` to, `start` added to the
                # counter; a layer of the expert stacks is read by no one
                # (`moe.expert_ffn` reads `in_stack`'s) and compiles away
                lp, lo_i = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, i, keepdims=False), (stack, lo_stack))
            if routed:
                # the masked positions are all that is wanted of the
                # routed experts: the others' pairs are not multiplied
                lp = moe.in_stack(lp, stack, i, router_mask,
                                  skip_unmasked=True)
            y, _, books = _layer(cfg, carry, lp, positions, lora=lo_i,
                                 lora_scale=scale,
                                 operator=kind.split("_")[0],
                                 live=router_mask, lengths=lengths)
            return y, books

        return scan_fn, (part(stack), part(lo_stack), index)

    # Each run of like layers is one scan, in the model's order, under the
    # layer's remat policy. "dots": keep matmul outputs (_dots_policy: the
    # flash forward's two among them), recompute elementwise — near-zero
    # extra MXU work for most of full remat's memory win. "full":
    # recompute everything (longest-context fallback). "mixed:K": the
    # model's first K layers keep their matmul outputs, the rest recompute
    # — spends whatever HBM headroom full remat leaves on skipping
    # recompute FLOPs (each dots layer trades ~210 MB at 7B/B=1/S=2k, 50 of
    # them the flash forward's output and logsumexp, for one layer-forward
    # less recompute per step); a run that K falls inside is scanned in
    # two parts.
    dots = _dots_policy()
    full = jax.checkpoint_policies.nothing_saveable
    keep = cfg.num_layers if cfg.remat_policy == "dots" else 0
    if cfg.remat and cfg.remat_policy.startswith("mixed:"):
        keep = max(0, min(int(cfg.remat_policy.split(":", 1)[1]),
                          cfg.num_layers))
    elif cfg.remat and cfg.remat_policy not in ("dots", "full"):
        raise ValueError(
            f"remat_policy {cfg.remat_policy!r}: expected "
            "'dots'|'full'|'mixed:K'")
    books, first = [], 0  # first: the run's first layer in the model
    for kind, start, n in cfg.layer_runs():
        head = max(0, min(keep - first, n))
        for begin, count, policy in ((start, head, dots),
                                     (start + head, n - head, full)):
            if not count:
                continue
            scan_fn, xs = scan_over(kind, begin, count)
            if cfg.remat:
                scan_fn = jax.checkpoint(scan_fn, policy=policy)
            x, b = jax.lax.scan(scan_fn, x, xs)
            if b is not None:
                books.append(b)
        first += n
    # each key over the layers that keep it, in the model's order: the
    # routers' over the routed layers, `index_kept` over the indexed ones
    joined = {key: jnp.concatenate([b[key] for b in books if key in b])
              for key in dict.fromkeys(k for b in books for k in b)}
    return (_rms_norm(x, params["final_norm"], cfg.rms_eps), joined or None)


def llama_forward(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    *,
    positions: Optional[jax.Array] = None,
    lora: Optional[Dict[str, Any]] = None,
    lora_cfg: Optional[LoraConfig] = None,
) -> jax.Array:
    """tokens [B, S] int32 → logits [B, S, V] (fp32). For kv-cache decoding
    use ``llama_decode``."""
    x = llama_hidden(params, tokens, cfg, positions=positions,
                     lora=lora, lora_cfg=lora_cfg)
    logits = jnp.einsum("bsh,hv->bsv", x, _lm_head(params).astype(cfg.dtype))
    return _scaled_logits(logits.astype(jnp.float32), cfg.logits_scaling)


def llama_head(params: Dict[str, Any], x: jax.Array,
               cfg: LlamaConfig) -> jax.Array:
    """Final hidden states [..., H] → logits [..., V], accumulated and
    kept in fp32 (``llama_forward`` rounds the product to the activation
    dtype first; the operands are the same)."""
    return _scaled_logits(
        jnp.einsum("...h,hv->...v", x, _lm_head(params).astype(cfg.dtype),
                   preferred_element_type=jnp.float32), cfg.logits_scaling)


def llama_next_token(
    params: Dict[str, Any],
    tokens: jax.Array,
    last: jax.Array,
    cfg: LlamaConfig,
    *,
    lora: Optional[Dict[str, Any]] = None,
    lora_cfg: Optional[LoraConfig] = None,
    live: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Optional[Dict[str, jax.Array]]]:
    """Greedy next token of each row without the [B, S, V] logits: tokens
    [B, S] and the index ``last`` [B] int32 of each row's newest token →
    (next ids [B] int32, final hidden states [B, S, H], the routers'
    load). Only the B rows ``hidden[b, last[b]]`` meet the head; the argmax
    is over their fp32 logits and a tie goes to the lowest id, as
    ``np.argmax`` has it. The hidden states are returned so that a caller
    who wants every position's logits applies ``llama_head`` to them and
    runs the layers once. The load is None for a model without experts or
    an indexer, else ``moe.router_load`` over the positions ``live [B,
    S]`` marks (the rows' own tokens and not their padding), two float32 a
    routed layer, and ``index_kept``, an int32 an indexed operator: the
    (query, key) pairs its choice kept over those positions' queries.
    With ``live`` the routed experts compute the marked positions alone,
    and every model is told each row's length (the marks' row sums: a
    row's own tokens are its first) so that the delta rule's kernel, the
    state-space scan's and the flash forwards, at two widths and at equal
    ones, stop at its end; the
    hidden states of the others are not a forward pass's. Without
    ``live`` every position is computed: ``last`` is not taken for a
    length, because a caller who wants every position's hidden state hands
    zeros there (``serve/llm.py::_FullLogits``). ``positions`` as
    ``llama_hidden``'s: each row's own indices where not given."""
    lengths = None
    if live is not None:
        lengths = jnp.sum(live, axis=1, dtype=jnp.int32)
    x, books = _hidden_and_books(params, tokens, cfg, positions=positions,
                                 lora=lora, lora_cfg=lora_cfg,
                                 router_mask=live, in_place=True,
                                 lengths=lengths)
    rows = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    ids = jnp.argmax(llama_head(params, rows, cfg), axis=-1)
    load = None
    if books is not None:
        load = moe.router_load(books) if "pairs" in books else {}
        if "index_kept" in books:
            load["index_kept"] = books["index_kept"]
    return ids.astype(jnp.int32), x, load


@cache
def _dots_policy():
    """What ``remat_policy="dots"`` keeps for the backward: every matmul's
    output. ``dot_general``s by the policy of that name, and the flash
    forward's output and logsumexp, which come out of a ``pallas_call``,
    by the names ``_fa_fwd`` gives them: dropped, the backward runs that
    forward a second time. One object for the process: a ``checkpoint``
    equation holds its policy, and two traces of one program are then the
    same jaxpr."""
    from ray_tpu.ops.pallas.flash_attention import (
        LSE_RESIDUAL_NAME, OUT_RESIDUAL_NAME)

    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        policies.dots_with_no_batch_dims_saveable,
        policies.save_only_these_names(OUT_RESIDUAL_NAME, LSE_RESIDUAL_NAME))


def _nll_from_logits(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """-log p(target) without gather/scatter: the target logit comes from
    an iota-compare + masked reduce, so the backward is softmax - onehot
    (pure elementwise). ``take_along_axis`` over a 32k vocab axis lowers
    to a TPU gather whose BACKWARD is a serialized scatter — profiling
    the 7B step showed that formulation burning ~27% of the whole step
    inside the loss (xplane while-loop at ~5% MXU efficiency)."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    vocab_ids = jax.lax.broadcasted_iota(
        jnp.int32, logits.shape, logits.ndim - 1)
    target_logit = jnp.sum(
        jnp.where(vocab_ids == targets[..., None], logits, 0.0), axis=-1)
    return lse - target_logit


def _chunked_ce(x, lm_head, targets, mask, chunk, dtype, logits_scaling,
                remat_policy):
    """Cross-entropy over seq chunks: the float32 logits, their
    exponentials and the softmax's gradient exist for one chunk at a time
    (B*chunk*V instead of B*S*V — the difference between a 7B model
    fitting one 16-GiB chip or not), each chunk's recomputed in the
    backward (jax.checkpoint). What the backward keeps of a chunk is
    ``remat_policy``'s to say, as for the layers: under ``"full"`` nothing,
    and the head's matmul runs a second time; under any other the matmul's
    output in ``dtype`` (B*S*V*2 bytes in bf16 over the chunks), and the
    elementwise part alone is recomputed."""
    B, S, H = x.shape
    assert S % chunk == 0, f"seq {S} not divisible by loss_chunk {chunk}"
    n = S // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, H), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
    mc = (jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)
          if mask is not None else jnp.ones_like(tc, jnp.float32))

    @partial(
        jax.checkpoint,
        policy=(jax.checkpoint_policies.nothing_saveable
                if remat_policy == "full" else _dots_policy()))
    def body(carry, inp):
        xi, ti, mi = inp
        logits = jnp.einsum("bch,hv->bcv", xi, lm_head.astype(dtype))
        logits = _scaled_logits(logits, logits_scaling)
        nll = _nll_from_logits(logits, ti)
        tot, cnt = carry
        return (tot + jnp.sum(nll * mi), cnt + jnp.sum(mi)), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)


def llama_loss(params: Dict[str, Any], batch: Dict[str, jax.Array],
               cfg: LlamaConfig, *,
               lora: Optional[Dict[str, Any]] = None,
               lora_cfg: Optional[LoraConfig] = None) -> jax.Array:
    """Next-token cross-entropy; batch = {tokens [B,S]} or {inputs, targets}."""
    if "targets" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
        mask = batch.get("mask")
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
        mask = None
    x, books = _hidden_and_books(params, inputs, cfg, lora=lora,
                                 lora_cfg=lora_cfg)
    if cfg.loss_chunk:
        ce = _chunked_ce(x, _lm_head(params), targets, mask,
                         cfg.loss_chunk, cfg.dtype, cfg.logits_scaling,
                         cfg.remat_policy)
    else:
        logits = _scaled_logits(
            jnp.einsum("bsh,hv->bsv", x, _lm_head(params).astype(cfg.dtype)),
            cfg.logits_scaling)
        nll = _nll_from_logits(logits, targets)
        ce = (jnp.mean(nll) if mask is None else
              jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0))
    if books is not None and "pairs" in books:
        # the routers' load-balancing term rides on it
        ce = ce + cfg.router_aux_loss_coef * moe.load_balancing_loss(
            books, cfg)
    return ce


def llama_lora_loss(base_params: Dict[str, Any], lora: Dict[str, Any],
                    batch: Dict[str, jax.Array], cfg: LlamaConfig,
                    lcfg: LoraConfig) -> jax.Array:
    """Loss as a function of the ADAPTERS only — the signature
    ``make_train_step`` wants for frozen-base fine-tuning: grads flow
    through the frozen layers into A/B but no base dW is ever formed."""
    return llama_loss(base_params, batch, cfg, lora=lora, lora_cfg=lcfg)
