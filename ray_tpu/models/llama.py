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
- Attention dispatches to ``ray_tpu.ops`` (Pallas flash attention on TPU,
  reference einsum path elsewhere; ring attention when the seq axis > 1).
- bfloat16 activations / fp32 params+optimizer by default: MXU-native.

Reference capability being replaced: Train users bring HF torch models
(reference: python/ray/train/huggingface/, release/air_examples/gptj_deepspeed
_finetuning); here the model is framework-native.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

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
    #   (peak logits memory B*chunk*V instead of B*S*V; the backward
    #    recomputes each chunk's logits under jax.checkpoint)
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

    def num_params(self) -> int:
        h, m, v = self.hidden, self.mlp_hidden, self.vocab_size
        q, kv = self.num_heads * self.head_dim, self.num_kv_heads * self.head_dim
        attn = h * (q + 2 * kv) + q * h + ((q + kv) if self.qk_norm else 0)
        mlp = 3 * h * m
        if self.num_experts:
            mlp = self.num_experts * mlp + h * self.num_experts
        per_layer = attn + mlp + 2 * h
        return self.num_layers * per_layer + 2 * v * h + h


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
        h, m, r = cfg.hidden, cfg.mlp_hidden, self.rank
        nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        per = {"wq": h * r + r * nh * hd, "wk": h * r + r * nkv * hd,
               "wv": h * r + r * nkv * hd, "wo": nh * hd * r + r * h,
               "w_gate": h * r + r * m, "w_up": h * r + r * m,
               "w_down": m * r + r * h}
        return cfg.num_layers * sum(per[t] for t in self.targets)


# (in_axes of A, out_axes of B) per adaptable projection; the A/B shapes are
# in_axes+(rank,) and (rank,)+out_axes with a leading num_layers dim.
_LORA_SHAPES = {
    "wq": (("embed",), ("heads", "head_dim")),
    "wk": (("embed",), ("kv_heads", "head_dim")),
    "wv": (("embed",), ("kv_heads", "head_dim")),
    "wo": (("heads", "head_dim"), ("embed",)),
    "w_gate": (("embed",), ("mlp",)),
    "w_up": (("embed",), ("mlp",)),
    "w_down": (("mlp",), ("embed",)),
}


def _lora_dims(cfg: LlamaConfig):
    return {"embed": (cfg.hidden,), "mlp": (cfg.mlp_hidden,),
            "heads": (cfg.num_heads,), "kv_heads": (cfg.num_kv_heads,),
            "head_dim": (cfg.head_dim,)}


def init_lora(cfg: LlamaConfig, lcfg: LoraConfig, key: jax.Array) -> Dict:
    """A ~ truncated-normal fan-in, B = 0 (the adapted model starts exactly
    at the base), stacked over layers for the scanned body."""
    dims = _lora_dims(cfg)
    L, r = cfg.num_layers, lcfg.rank
    routed = sorted(set(lcfg.targets) & {"w_gate", "w_up", "w_down"})
    if cfg.num_experts and routed:
        raise ValueError(f"LoRA targets {routed}: a model with experts has "
                         "no dense feed-forward to adapt")
    out = {}
    keys = jax.random.split(key, len(lcfg.targets))
    for k, name in zip(keys, lcfg.targets):
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
    return {"layers": out}


def lora_logical_axes(cfg: LlamaConfig, lcfg: LoraConfig) -> Dict:
    """Rank dim stays unsharded (it is tiny); in/out dims shard like the
    base weight they adapt so the activation-side matmuls need no extra
    resharding."""
    out = {}
    for name in lcfg.targets:
        in_ax, out_ax = _LORA_SHAPES[name]
        out[name] = {"a": (None,) + in_ax + (None,),
                     "b": (None, None) + out_ax}
    return {"layers": out}


def merge_lora(params: Dict, lora: Dict, cfg: LlamaConfig,
               lcfg: LoraConfig) -> Dict:
    """Fold adapters into the base weights (for serving/export)."""
    merged = dict(params)
    layers = dict(params["layers"])
    for name, ab in lora["layers"].items():
        w = layers[name]
        a2 = ab["a"].reshape(cfg.num_layers, -1, lcfg.rank)
        b2 = ab["b"].reshape(cfg.num_layers, lcfg.rank, -1)
        delta = jnp.einsum("lir,lro->lio", a2.astype(jnp.float32),
                           b2.astype(jnp.float32)) * lcfg.scale
        layers[name] = (w.astype(jnp.float32)
                        + delta.reshape(w.shape)).astype(w.dtype)
    merged["layers"] = layers
    return merged


def llama_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis tuples."""
    layer = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
        "attn_norm": ("norm",),
        "mlp_norm": ("norm",),
    }
    if cfg.num_experts:
        layer.update(moe.EXPERT_LOGICAL_AXES)
    else:
        layer.update(w_gate=("embed", "mlp"), w_up=("embed", "mlp"),
                     w_down=("mlp", "embed"))
    if cfg.qk_norm:
        layer.update(q_norm=("norm",), k_norm=("norm",))
    # scanned layers carry a leading 'layers' dim — replicated (None)
    layers = {k: (None,) + v for k, v in layer.items()}
    return {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("norm",),
        "lm_head": ("embed", "vocab"),
    }


def init_llama(cfg: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Initialize params (truncated-normal fan-in scaling, fp32)."""
    h, m = cfg.hidden, cfg.mlp_hidden
    nh, nkv, hd, L = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.num_layers
    ks = jax.random.split(key, 10)
    pd = cfg.param_dtype

    def norm_init(shape, k, fan_in):
        scale = fan_in ** -0.5
        return (jax.random.truncated_normal(k, -2, 2, shape, jnp.float32)
                * scale).astype(pd)

    layers = {
        "wq": norm_init((L, h, nh, hd), ks[0], h),
        "wk": norm_init((L, h, nkv, hd), ks[1], h),
        "wv": norm_init((L, h, nkv, hd), ks[2], h),
        "wo": norm_init((L, nh, hd, h), ks[3], nh * hd),
    }
    if cfg.num_experts:
        layers.update(moe.init_experts(cfg, ks[9]))
    else:
        layers.update(w_gate=norm_init((L, h, m), ks[4], h),
                      w_up=norm_init((L, h, m), ks[5], h),
                      w_down=norm_init((L, m, h), ks[6], m))
    layers.update(attn_norm=jnp.ones((L, h), pd),
                  mlp_norm=jnp.ones((L, h), pd))
    if cfg.qk_norm:
        layers.update(q_norm=jnp.ones((L, nh * hd), pd),
                      k_norm=jnp.ones((L, nkv * hd), pd))
    return {
        "embed": norm_init((cfg.vocab_size, h), ks[7], 1.0),
        "layers": layers,
        "final_norm": jnp.ones((h,), pd),
        "lm_head": norm_init((h, cfg.vocab_size), ks[8], h),
    }


def _rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [B, S, H, D]; rotate pairs (d, d + D/2) — llama convention."""
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freq  # [B,S,half]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _layer(cfg: LlamaConfig, x: jax.Array, lp: Dict[str, jax.Array],
           positions: jax.Array, kv_cache=None,
           cache_index: Optional[jax.Array] = None,
           lora: Optional[Dict[str, Any]] = None, lora_scale: float = 0.0):
    """One transformer block. x: [B, S, H_model] -> (x, the updated
    key/value cache or None, the router's books of ``moe.expert_ffn`` or
    None for a dense feed-forward)."""
    dt = cfg.dtype

    def _ld(name, t_in, eq_a, eq_b):
        """Activation-side LoRA delta: (t_in @ A) @ B * scale, or 0."""
        if lora is None or name not in lora:
            return 0
        ab = lora[name]
        t = jnp.einsum(eq_a, t_in, ab["a"].astype(dt))
        return jnp.einsum(eq_b, t, ab["b"].astype(dt)) * lora_scale

    # --- attention ---
    h = _rms_norm(x, lp["attn_norm"], cfg.rms_eps)
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
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache  # [B, max_S, nkv, d]
        ck = jax.lax.dynamic_update_slice_in_dim(ck, k, cache_index, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cv, v, cache_index, axis=1)
        k, v = ck, cv
        new_cache = (ck, cv)
        attn_out = attention(q, k, v, impl="reference", causal=True,
                             q_offset=cache_index)
    else:
        if cfg.attn_impl == "ring_seq":
            attn_out = _ring_seq_attention(q, k, v)
        else:
            attn_out = attention(q, k, v, impl=cfg.attn_impl, causal=True)
    attn_out = constrain(attn_out, ("batch", "seq", "heads", None))
    x = (x + jnp.einsum("bsnd,ndh->bsh", attn_out, lp["wo"].astype(dt))
         + _ld("wo", attn_out, "bsnd,ndr->bsr", "bsr,rh->bsh"))
    # --- feed-forward: routed experts, or the dense SwiGLU ---
    h = _rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    if cfg.num_experts:
        y, books = moe.expert_ffn(cfg, h, lp)
        return constrain(x + y, ("batch", "seq", "embed")), new_cache, books
    gate = (jnp.einsum("bsh,hm->bsm", h, lp["w_gate"].astype(dt))
            + _ld("w_gate", h, "bsh,hr->bsr", "bsr,rm->bsm"))
    up = (jnp.einsum("bsh,hm->bsm", h, lp["w_up"].astype(dt))
          + _ld("w_up", h, "bsh,hr->bsr", "bsr,rm->bsm"))
    act = constrain(jax.nn.silu(gate) * up, ("batch", "seq", "mlp"))
    x = (x + jnp.einsum("bsm,mh->bsh", act, lp["w_down"].astype(dt))
         + _ld("w_down", act, "bsm,mr->bsr", "bsr,rh->bsh"))
    x = constrain(x, ("batch", "seq", "embed"))
    return x, new_cache, None


def llama_decode(
    params: Dict[str, Any],
    tokens: jax.Array,
    cfg: LlamaConfig,
    kv_caches,
    cache_index: jax.Array,
    *,
    positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, list]:
    """Incremental decode: tokens [B, S] appended to the kv caches at
    ``cache_index`` → (logits [B, S, V] fp32, updated caches). Python loop
    over layers so each layer's cache updates functionally in place."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32) + cache_index, (B, S))
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    new_caches = []
    for i in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        if cfg.num_experts:
            lp = moe.in_stack(lp, params["layers"], i)
        x, c, _ = _layer(cfg, x, lp, positions, kv_caches[i], cache_index)
        new_caches.append(c)
    x = _rms_norm(x, params["final_norm"], cfg.rms_eps)
    logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"].astype(cfg.dtype))
    return logits.astype(jnp.float32), new_caches


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
    per-layer remat; LoRA adapters (if given) scan alongside the base."""
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
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """``llama_hidden``, and with it the routers' books stacked over the
    layers (``moe.expert_ffn``; None for a model without experts)."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    x = constrain(x, ("batch", "seq", "embed"))

    scale = lora_cfg.scale if lora_cfg is not None else 0.0

    def scan_over(layers, lo):
        """(scan body, xs) over a stack of layers. With experts the body
        also sees the whole stack and its own index in it."""
        n = jax.tree.leaves(layers)[0].shape[0]
        index = jnp.arange(n) if cfg.num_experts else None

        def scan_fn(carry, xs):
            lp, lo_i, i = xs
            if cfg.num_experts:
                lp = moe.in_stack(lp, layers, i, router_mask)
            y, _, books = _layer(cfg, carry, lp, positions, lora=lo_i,
                                 lora_scale=scale)
            return y, books

        return scan_fn, (layers, lo, index)

    lo_layers = lora["layers"] if lora is not None else None
    if cfg.remat:
        # "dots": keep matmul outputs, recompute elementwise — near-zero
        # extra MXU work for most of full remat's memory win. "full":
        # recompute everything (longest-context fallback). "mixed:K":
        # first K layers keep their matmul outputs, the rest recompute —
        # spends whatever HBM headroom full remat leaves on skipping
        # recompute FLOPs (each dots layer trades ~160 MB at 7B/B=1/S=2k
        # for one layer-forward less recompute per step).
        if cfg.remat_policy.startswith("mixed:"):
            k = int(cfg.remat_policy.split(":", 1)[1])
            n = cfg.num_layers
            k = max(0, min(k, n))
            dots = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            full = jax.checkpoint_policies.nothing_saveable
            head = jax.tree.map(lambda a: a[:k], params["layers"])
            tail = jax.tree.map(lambda a: a[k:], params["layers"])
            lo_head = (jax.tree.map(lambda a: a[:k], lo_layers)
                       if lo_layers is not None else {})
            lo_tail = (jax.tree.map(lambda a: a[k:], lo_layers)
                       if lo_layers is not None else {})
            fn, xs = scan_over(head, lo_head)
            x, b_head = jax.lax.scan(jax.checkpoint(fn, policy=dots), x, xs)
            fn, xs = scan_over(tail, lo_tail)
            x, b_tail = jax.lax.scan(jax.checkpoint(fn, policy=full), x, xs)
            books = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                 b_head, b_tail)
            return _rms_norm(x, params["final_norm"], cfg.rms_eps), books
        if cfg.remat_policy not in ("dots", "full"):
            raise ValueError(
                f"remat_policy {cfg.remat_policy!r}: expected "
                "'dots'|'full'|'mixed:K'")
    # broadcast None through the scan when no adapters: xs must be a pytree
    # of arrays, so substitute an empty dict
    scan_fn, xs = scan_over(params["layers"], lo_layers or {})
    if cfg.remat:
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat_policy == "full"
                  else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        scan_fn = jax.checkpoint(scan_fn, policy=policy)
    x, books = jax.lax.scan(scan_fn, x, xs)
    return _rms_norm(x, params["final_norm"], cfg.rms_eps), books


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
    logits = jnp.einsum("bsh,hv->bsv", x, params["lm_head"].astype(cfg.dtype))
    return logits.astype(jnp.float32)


def llama_head(params: Dict[str, Any], x: jax.Array,
               cfg: LlamaConfig) -> jax.Array:
    """Final hidden states [..., H] → logits [..., V], accumulated and
    kept in fp32 (``llama_forward`` rounds the product to the activation
    dtype first; the operands are the same)."""
    return jnp.einsum("...h,hv->...v", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def llama_next_token(
    params: Dict[str, Any],
    tokens: jax.Array,
    last: jax.Array,
    cfg: LlamaConfig,
    *,
    lora: Optional[Dict[str, Any]] = None,
    lora_cfg: Optional[LoraConfig] = None,
    live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Optional[Dict[str, jax.Array]]]:
    """Greedy next token of each row without the [B, S, V] logits: tokens
    [B, S] and the index ``last`` [B] int32 of each row's newest token →
    (next ids [B] int32, final hidden states [B, S, H], the routers'
    load). Only the B rows ``hidden[b, last[b]]`` meet the head; the argmax
    is over their fp32 logits and a tie goes to the lowest id, as
    ``np.argmax`` has it. The hidden states are returned so that a caller
    who wants every position's logits applies ``llama_head`` to them and
    runs the layers once. The load is None for a model without experts,
    else ``moe.router_load`` over the positions ``live [B, S]`` marks (the
    rows' own tokens and not their padding): two float32 a layer."""
    x, books = _hidden_and_books(params, tokens, cfg, lora=lora,
                                 lora_cfg=lora_cfg, router_mask=live)
    rows = jnp.take_along_axis(x, last[:, None, None], axis=1)[:, 0]
    ids = jnp.argmax(llama_head(params, rows, cfg), axis=-1)
    load = moe.router_load(books) if cfg.num_experts else None
    return ids.astype(jnp.int32), x, load


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


def _chunked_ce(x, lm_head, targets, mask, chunk, dtype):
    """Cross-entropy over seq chunks: logits for one chunk at a time, each
    chunk's logits recomputed in the backward (jax.checkpoint) so peak
    memory is B*chunk*V instead of B*S*V — the difference between a 7B
    model fitting one 16-GiB chip or not."""
    B, S, H = x.shape
    assert S % chunk == 0, f"seq {S} not divisible by loss_chunk {chunk}"
    n = S // chunk
    xc = jnp.moveaxis(x.reshape(B, n, chunk, H), 1, 0)
    tc = jnp.moveaxis(targets.reshape(B, n, chunk), 1, 0)
    mc = (jnp.moveaxis(mask.reshape(B, n, chunk), 1, 0)
          if mask is not None else jnp.ones_like(tc, jnp.float32))

    @jax.checkpoint
    def body(carry, inp):
        xi, ti, mi = inp
        logits = jnp.einsum("bch,hv->bcv", xi, lm_head.astype(dtype))
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
        ce = _chunked_ce(x, params["lm_head"], targets, mask,
                         cfg.loss_chunk, cfg.dtype)
    else:
        logits = jnp.einsum("bsh,hv->bsv", x,
                            params["lm_head"].astype(cfg.dtype))
        nll = _nll_from_logits(logits, targets)
        ce = (jnp.mean(nll) if mask is None else
              jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0))
    if cfg.num_experts:  # the routers' load-balancing term rides on it
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
