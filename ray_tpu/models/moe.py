"""Sparse expert feed-forward: the routed half of ``models/llama.py``'s block.

A model with experts is a ``LlamaConfig`` whose ``num_experts`` is above 0
(OLMoE-1B-7B: 64 experts of width 1024, 8 a token, no shared expert;
LFM2-24B-A2B: 64 of width 1536, 4 a token, after its leading dense layers).
Its block is ``llama.py::_layer``: the operator half, the scan, remat, the
head and the loss are the dense model's. This module holds what only the
routed feed-forward needs: the router, the dispatch, the expert matmuls,
the load-balancing term, and the expert leaves' initialiser and logical
axes (the leading ``expert`` dim of the three stacks shards over the mesh's
``expert`` axis).

The router's variants are fields of the configuration, one function: the
scores are a softmax or a sigmoid of the router's logits
(``router_scores``); the ``K`` experts are chosen on the scores or, with
``router_bias``, on the scores plus a per-expert bias, a buffer no gradient
reaches (kept in the parameters' type like every leaf, added in float32),
while the weights stay the scores without it; the chosen
weights are renormalised or not (``norm_topk_prob``, over their sum plus
``router_norm_eps``) and scaled by ``routed_scaling_factor``. The defaults
are OLMoE's router, to the bit.

The dispatch drops nothing and has no capacity: the ``T x K`` (position,
expert) pairs are sorted by expert, the rows gathered in that order, and
the three matmuls run as grouped matmuls over the sorted rows, each
expert's weights read once. Every shape is static, the sort is over a
fixed ``T x K``, so a (batch, length) shape compiles once whatever the
routing.

The layers are scanned, and a custom call cannot read its operand through
the scan's slice as a dense matmul does: XLA copies the layer's three
``[E, hidden, width]`` slices out of the stacked ``[L, E, ...]`` arrays
first (0.8 GB a layer at OLMoE's widths, 39 ms a serving step on a v5e).
So the forward pass multiplies by the whole stack, seen as ``L x E``
groups, and nothing is copied (``_in_place``, ``_gated_in_place``). Which
kernel reads it follows from what the program can see when it is traced
(``_kernel_takes``), as ``attention(impl="auto")`` chooses: where both of
a weight's dims are on the lane width and no mesh of more than one device
is in scope, the repo's own (``ops/pallas/grouped_matmul.py``: the layer's
first group reaches its index maps, only the strips of a row tile that
hold an expert's rows are computed, and gate, up and SiLU are one pass over
the rows); everywhere
else ``jax.lax.ragged_dot``, XLA's kernel, for which only this layer's
``E`` of the ``L x E`` groups hold rows: it differentiates and partitions,
which a ``pallas_call`` does not. The backward pass is ``ragged_dot``'s on
the layer's slice either way, so that the weights' gradient is the slice's
and the scan stacks it as it stacks every other leaf's. Where the
parameters are kept in another type than the activations' the slice is
cast on its way in, which is that copy, and the matmuls take the slice.
The stack and the layer's index in it travel with the layer's leaves
(``in_stack``), so the block's signature is the dense model's.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import grouped_matmul

ROUTER_SCORES = ("softmax", "sigmoid")
EXPERT_LOGICAL_AXES = {
    "router": ("embed", "expert"),
    "we_gate": ("expert", "embed", "mlp"),
    "we_up": ("expert", "embed", "mlp"),
    "we_down": ("expert", "mlp", "embed"),
}
EXPERT_STACKS = ("we_gate", "we_up", "we_down")
# keys `in_stack` adds to a layer's leaves: not leaves themselves
_WHERE, _MASK = "_experts_in", "_router_mask"


def in_stack(lp: Dict[str, jax.Array], layers: Dict[str, jax.Array], layer,
             mask: Optional[jax.Array] = None) -> Dict[str, jax.Array]:
    """The layer's leaves ``lp`` (``layers[name][layer]``) with where its
    experts lie: the three ``EXPERT_STACKS`` over all layers and the
    layer's index in them, for ``expert_ffn`` to read them in place; and
    ``mask [B, S]``, the positions the router's books count."""
    return dict(lp, **{_WHERE: ({n: layers[n] for n in EXPERT_STACKS}, layer),
                       _MASK: mask})


def init_experts(cfg, key: jax.Array, num_layers: int
                 ) -> Dict[str, jax.Array]:
    """The router and the three expert stacks, stacked over ``num_layers``
    layers (truncated normal, fan-in scaled, as ``init_llama`` draws the
    dense leaves). One layer is drawn at a time: at OLMoE's widths a stack
    is ``[16, 64, 2048, 1024]``, 8.6 GB in float32 on its way to bf16, and
    a layer of it is 0.5 GB. With ``router_bias`` the per-expert bias rides
    along as zeros, as HuggingFace starts it: a buffer that training's
    balancing moves and no gradient reaches."""
    h, m, E, L = cfg.hidden, cfg.mlp_hidden, cfg.num_experts, num_layers
    pd = cfg.param_dtype

    def stack(k, shape, fan_in):
        def one(layer_key):
            return (jax.random.truncated_normal(
                layer_key, -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(pd)
        return jax.lax.map(one, jax.random.split(k, L))

    ks = jax.random.split(key, 4)
    out = {
        "router": stack(ks[0], (h, E), h),
        "we_gate": stack(ks[1], (E, h, m), h),
        "we_up": stack(ks[2], (E, h, m), h),
        "we_down": stack(ks[3], (E, m, h), m),
    }
    if cfg.router_bias:
        out["router_bias"] = jnp.zeros((L, E), pd)
    return out


def _kernel_takes(stack: jax.Array) -> bool:
    """Whether ``ops/pallas/grouped_matmul.py`` has a path for a stack, from
    what the program can see when it is traced: both of a weight's dims on
    the lane width, and no mesh of more than one device in scope (a
    ``pallas_call`` has no partitioning rule; ``ops/attention.py`` asks the
    same of the flash kernel)."""
    from ray_tpu.parallel.sharding import ambient_mesh

    mesh = ambient_mesh()
    return (grouped_matmul.takes(*stack.shape[2:])
            and (mesh is None or mesh.size == 1))


def _as_groups(stack: jax.Array) -> jax.Array:
    """``[L, E, K, N]`` seen as ``L x E`` groups: layer ``l``'s experts are
    groups ``l * E`` onwards."""
    return stack.reshape((-1,) + stack.shape[2:])


def _ragged_dot_in_stack(rows, sizes, stack, layer, out_dtype):
    """``ragged_dot(rows, stack[layer], sizes)`` by XLA's kernel, read from
    the stack where it lies: ``sizes`` sits at this layer's ``E`` of the
    ``L x E`` groups and the others are empty, which costs it nothing."""
    L, E = stack.shape[:2]
    flat = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E,), sizes.dtype), sizes, (layer * E,))
    return jax.lax.ragged_dot(rows, _as_groups(stack), flat,
                              preferred_element_type=out_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _in_place(rows, w, sizes, stack, layer, out_dtype):
    """``ragged_dot(rows, w, sizes)`` for ``w = stack[layer]``, read from
    the stack where it lies; the repo's kernel is handed the layer's first
    group."""
    if _kernel_takes(stack):
        return grouped_matmul.grouped_matmul(
            rows, _as_groups(stack), sizes, layer * stack.shape[1], out_dtype)
    return _ragged_dot_in_stack(rows, sizes, stack, layer, out_dtype)


def _in_place_fwd(rows, w, sizes, stack, layer, out_dtype):
    return _in_place(rows, w, sizes, stack, layer, out_dtype), (rows, w, sizes)


def _in_place_bwd(out_dtype, res, g):
    rows, w, sizes = res
    _, vjp = jax.vjp(lambda r, ww: jax.lax.ragged_dot(
        r, ww, sizes, preferred_element_type=out_dtype), rows, w)
    return vjp(g) + (None, None, None)


_in_place.defvjp(_in_place_fwd, _in_place_bwd)


@jax.custom_vjp
def _gated_in_place(rows, w_gate, w_up, sizes, gate_stack, up_stack, layer):
    """``silu(ragged_dot(rows, w_gate)) * ragged_dot(rows, w_up)`` in the
    rows' type, both read from their stacks where they lie. The kernel
    makes it in one pass over the rows and rounds once, from the float32
    products; XLA's two matmuls round each product first."""
    if _kernel_takes(gate_stack):
        return grouped_matmul.grouped_swiglu(
            rows, _as_groups(gate_stack), _as_groups(up_stack), sizes,
            layer * gate_stack.shape[1], rows.dtype)
    return (jax.nn.silu(_ragged_dot_in_stack(rows, sizes, gate_stack, layer,
                                             rows.dtype))
            * _ragged_dot_in_stack(rows, sizes, up_stack, layer, rows.dtype))


def _gated_in_place_fwd(rows, w_gate, w_up, sizes, gate_stack, up_stack,
                        layer):
    return (_gated_in_place(rows, w_gate, w_up, sizes, gate_stack, up_stack,
                            layer), (rows, w_gate, w_up, sizes))


def _gated_in_place_bwd(res, g):
    # through the layer's slices, the two products made again
    rows, w_gate, w_up, sizes = res

    def on_slices(r, wg, wu):
        return (jax.nn.silu(jax.lax.ragged_dot(
            r, wg, sizes, preferred_element_type=r.dtype))
            * jax.lax.ragged_dot(r, wu, sizes,
                                 preferred_element_type=r.dtype))
    _, vjp = jax.vjp(on_slices, rows, w_gate, w_up)
    return vjp(g) + (None, None, None, None)


_gated_in_place.defvjp(_gated_in_place_fwd, _gated_in_place_bwd)


def expert_ffn(cfg, h: jax.Array, lp: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``h [B, S, H]`` (after the block's second norm) -> the routed
    feed-forward's output ``[B, S, H]`` and the router's books of this
    layer: ``pairs [E]`` (how many (position, expert) pairs each expert
    took), ``prob [E]`` (the router's scores summed over positions)
    and ``positions`` (how many were counted), all float32 and all over the
    positions where ``in_stack``'s mask is true (every position without
    one). Padded positions are computed like any other; the mask only
    keeps them out of the books. ``lp`` is the layer's leaves, as they are
    or from ``in_stack``."""
    dt = cfg.dtype
    where, mask = lp.get(_WHERE), lp.get(_MASK)
    B, S, H = h.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    x = h.reshape(T, H)
    if cfg.router_scores not in ROUTER_SCORES:
        raise ValueError(f"router_scores {cfg.router_scores!r}: expected "
                         + "|".join(ROUTER_SCORES))
    with jax.named_scope("moe_router"):
        # logits, scores and the chosen weights in float32; the operands
        # are the activations and the router as every other matmul has them
        logits = jnp.einsum("th,he->te", x, lp["router"].astype(dt),
                            preferred_element_type=jnp.float32)
        probs = (jax.nn.sigmoid(logits) if cfg.router_scores == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        if "router_bias" in lp:
            # the bias moves the choice and not the weights
            _, chosen = jax.lax.top_k(
                probs + jax.lax.stop_gradient(
                    lp["router_bias"].astype(jnp.float32)), K)
            weights = jnp.take_along_axis(probs, chosen, axis=-1)
        else:
            weights, chosen = jax.lax.top_k(probs, K)        # [T, K]
        if cfg.norm_topk_prob:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            if cfg.router_norm_eps:
                total = total + cfg.router_norm_eps
            weights = weights / total
        if cfg.routed_scaling_factor != 1.0:
            weights = weights * cfg.routed_scaling_factor
    with jax.named_scope("moe_dispatch"):
        flat = chosen.reshape(T * K)
        order = jnp.argsort(flat)                 # stable: pairs by expert
        onehot = flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :]
        sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)     # [E]
        rows = jnp.take(x, order // K, axis=0)               # [T*K, H]
    with jax.named_scope("moe_experts"):
        if where is None or lp["we_gate"].dtype != dt:
            # the layer's slices are cast on their way in, which is the
            # copy: multiply by them (float32 master weights in training)
            def matmul(x, name, out_dtype):
                return jax.lax.ragged_dot(x, lp[name].astype(dt), sizes,
                                          preferred_element_type=out_dtype)

            hidden = (jax.nn.silu(matmul(rows, "we_gate", dt))
                      * matmul(rows, "we_up", dt))
            out = matmul(hidden, "we_down", jnp.float32)
        else:
            stacks, layer = where
            hidden = _gated_in_place(
                rows, lp["we_gate"], lp["we_up"], sizes, stacks["we_gate"],
                stacks["we_up"], layer)
            out = _in_place(hidden, lp["we_down"], sizes, stacks["we_down"],
                            layer, jnp.float32)
    with jax.named_scope("moe_combine"):
        # back to the pairs' own order, then the weighted sum of each
        # position's K expert outputs, in float32
        out = jnp.take(out, jnp.argsort(order), axis=0).reshape(T, K, H)
        y = jnp.einsum("tkh,tk->th", out, weights).astype(dt)
    live = (jnp.ones((T,), jnp.float32) if mask is None
            else mask.reshape(T).astype(jnp.float32))
    books = {"pairs": jnp.einsum("t,te->e", jnp.repeat(live, K),
                                 onehot.astype(jnp.float32)),
             "prob": jnp.einsum("t,te->e", live, probs),
             "positions": jnp.sum(live)}
    return y.reshape(B, S, H), books


def load_balancing_loss(books: Dict[str, jax.Array], cfg) -> jax.Array:
    """The Switch load-balancing term over all layers' routers together,
    as HuggingFace's ``load_balancing_loss_func`` computes it: ``E`` times
    the sum over experts of (the share of all (layer, position) pairs'
    choices that went to the expert) x (the router's mean probability for
    it). ``books`` is ``expert_ffn``'s, stacked over layers. The gradient
    flows through the probabilities alone."""
    n = jnp.sum(books["positions"])
    share = jnp.sum(books["pairs"], axis=0) / n
    prob = jnp.sum(books["prob"], axis=0) / n
    return cfg.num_experts * jnp.sum(jax.lax.stop_gradient(share) * prob)


def router_load(books: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """For each layer, the pairs the fullest expert took and the pairs the
    mean expert took (``[L]`` float32 each): what a serving step brings to
    the host beside its token ids."""
    return {"fullest": jnp.max(books["pairs"], axis=-1),
            "mean": jnp.mean(books["pairs"], axis=-1)}
