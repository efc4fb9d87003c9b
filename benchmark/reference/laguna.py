"""Plain float32 reference of Laguna XS.2 (``poolside/Laguna-XS.2``,
``model_type: laguna``): pre-norm blocks of grouped-query attention at two
head counts, 64 query heads on 8 key/value heads under a sliding window and
plain rope in three layers of four, 48 on 8 over every causal key under
YaRN over the first half of the head in the fourth (and in layer 0), a
sigmoid gate a head on attention's output, then a dense SwiGLU (layer 0) or
routed experts under a sigmoid router beside a shared expert; untied head.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed (one stacked pytree a kind
under ``params["layers"]``: ``attention_dense``, ``sliding_routed``,
``attention_routed``, a model of one kind the stack itself; ``wq [L,
hidden, the kind's heads, head_dim]``, ``wk``/``wv`` at the key/value
heads, ``wo [L, heads, head_dim, hidden]``, ``w_head_gate [L, hidden,
heads]``, ``router [L, hidden, E]``, ``we_gate``/``we_up [L, E, hidden,
width]``, ``we_down [L, E, width, hidden]``, ``ws_gate``/``ws_up [L,
hidden, shared width]``, ``ws_down``, a dense layer's ``w_gate``/``w_up``/
``w_down``, the two block norms; ``embed``, ``final_norm``, ``lm_head
[hidden, vocab]``). The RMSNorm is the dense reference's; the two ropes,
YaRN, the masked attention, the gate and the router are written out here
from the configuration's numbers and import nothing of the program's.

With ``u = n1(x)`` and ``r = n2(x)`` the block's two RMSNorms, layer ``l``
of kind ``c = layer_types[l]``, ``H_c = num_attention_heads_per_layer[l]``::

    q, k, v = split_Hc(Wq u), split_8(Wk u), split_8(Wv u)
    q, k = rot_c(q), rot_c(k)
    a = softmax(q k^T / sqrt(head_dim) over the keys seen_c) v
        # query head n reads key/value head n // (H_c / 8)
    x += Wo (a * sigmoid(Wg u)[:, :, None])               # a gate a head
    s = sigmoid_f32(r W_r);  (s_k, e_k) = top_k(s)        # k = 8, E = 256
    w = 2.5 s_k / sum(s_k)
    x += sum_k w_k SwiGLU_{e_k}(r) + SwiGLU_shared(r)     # layer 0: dense

``sliding_attention``: ``seen`` is keys ``t - sliding_window + 1 .. t``
(``q - k < sliding_window``) and ``rot`` turns the whole head under rope at
the sliding layers' ``rope_theta`` (rotate-half). ``full_attention``:
``seen`` is every causal key and ``rot`` turns the FIRST
``partial_rotary_factor`` of the head alone (64 of 128 dims, pairs ``(d, d
+ 32)``) under YaRN reckoned over those 64 dims, cos and sin times
``attention_factor``; the other dims pass through as they are, the
amplitude not on them. Then the final RMSNorm and the head.

Departures from the published description, and what is assumed (the
configuration file's ``assumed`` has each in full): ``gating: true`` read
as the head-wise sigmoid gate; the router's sigmoid and renormalisation; no
norm over the heads, no gate on the shared expert, ``silu``. HF gathers,
for each expert, the positions that chose it; here each expert is computed
at every position and its output multiplied by the position's weight for
it, which is exactly 0 where the router did not choose it. One sequence at
a time, attention in blocks of ``QUERY_BLOCK`` queries and ``HEAD_BLOCK``
heads so that the float32 scores of 6144 positions fit beside 7.74 GB of
served weights; the window and the causal mask are booleans over ALL the
keys; a sequence is padded on the right to a multiple of ``PAD_TO`` and
the result cut back (fewer shapes to compile).

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers run in a Python loop,
the experts of a layer in a ``fori_loop`` that casts one expert's three
matrices to float32 at a time.

``logits`` takes switches that plant the family's own faults, for the
controls of the cell's check (``tools/laguna_probe.py``); none is the
model: ``gate=False`` (no head-wise gate), ``partial=False`` (the full
layers turn the whole head), ``sliding_theta`` (another base for the
sliding layers' rope), ``yarn=False`` (the full layers under plain rope at
amplitude 1), ``window=False`` (the sliding layers see every causal key),
``window_keys`` (another window), ``full_group`` (query head ``n`` of a
full layer reads key/value head ``n // full_group``), ``scaling`` (another
``moe_routed_scaling_factor``), ``shared=False`` (no shared expert),
``scores="softmax"`` (the router's scores).

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import rms_norm
# what the two families of window-and-full grouped-query attention share
# to the letter: a kind's stacked leaves by layer, the rotary frequencies of
# one entry of `rope_parameters` over a rotary width (plain, or YaRN as
# HuggingFace's `_compute_yarn_parameters` has it: here the width is
# `head_dim * partial_rotary_factor`, as it is there), a block of queries
# against all the keys under booleans, a dense SwiGLU
from benchmark.reference.mellum import (
    _attend_block, _dense_ffn, _f32, inverse_frequencies, layer_leaves)

QUERY_BLOCK = 256
HEAD_BLOCK = 8
# a sequence is padded on the right to a multiple of this (everything here
# is causal, so what follows a position does not reach it) and the result
# cut back: the cell's prompts then meet six lengths and not two dozen, and
# each length is a dozen float32 programs to compile in a checkout's first
# run
PAD_TO = 1024

def rotate(x, positions, inv_freq, scale: float):
    """x [S, heads, head_dim]: the first ``2 len(inv_freq)`` dims of each
    head, pairs ``(d, d + len(inv_freq))``, rotated by ``position *
    inv_freq[d]`` (rotate-half), cos and sin times ``scale``; the dims
    after them as they are."""
    half = inv_freq.shape[0]
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


# --------------------------------------------------------------- attention
def masked_attention(q, k, v, *, window: Optional[int],
                     group: Optional[int] = None):
    """q [S, H, D], k, v [S, KVH, D] -> [S, H, D]; query head ``n`` reads
    key/value head ``n // group``, ``group`` being ``H / KVH`` (another is
    a control: the heads past the last key/value head's group read the
    last). In blocks of queries and heads."""
    S, H = q.shape[:2]
    kvh = k.shape[1]
    reads = jnp.minimum(jnp.arange(H) // (group or H // kvh), kvh - 1)
    k, v = jnp.take(k, reads, axis=1), jnp.take(v, reads, axis=1)
    rows = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        rows.append(jnp.concatenate([
            _attend_block(qb[:, h:h + HEAD_BLOCK], k[:, h:h + HEAD_BLOCK],
                          v[:, h:h + HEAD_BLOCK], start, window=window)
            for h in range(0, H, HEAD_BLOCK)], axis=1))
    return jnp.concatenate(rows, axis=0)


@partial(jax.jit, static_argnames=("eps", "scale"))
def _project(x, layers, j, positions, inv_freq, *, eps, scale):
    """(q, k rotated, v, the gate's sigmoid [S, heads])."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    u = rms_norm(x, at("attn_norm"), eps)
    q = jnp.einsum("sh,hnd->snd", u, at("wq"))
    k = jnp.einsum("sh,hnd->snd", u, at("wk"))
    v = jnp.einsum("sh,hnd->snd", u, at("wv"))
    gate = jax.nn.sigmoid(u @ at("w_head_gate"))
    return (rotate(q, positions, inv_freq, scale),
            rotate(k, positions, inv_freq, scale), v, gate)


@jax.jit
def _out(x, a, layers, j):
    return x + jnp.einsum("snd,ndh->sh", a, _f32(layers["wo"][j]))


def attention(x, kind: str, layers, j: int, positions, m: Dict[str, Any],
              *, gate: bool = True, partial: bool = True,
              sliding_theta: Optional[float] = None, yarn: bool = True,
              window: bool = True, window_keys: Optional[int] = None,
              full_group: Optional[int] = None):
    """x [S, hidden] -> x + the layer's gated attention on its norm. The
    switches are controls (module docstring); none is the model."""
    sliding = kind.split("_")[0] == "sliding"
    rope = dict(m["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"])
    if sliding and sliding_theta is not None:
        rope["rope_theta"] = sliding_theta
    if not sliding and not yarn:  # plain rope at the same base
        rope = {"rope_type": "default", "rope_theta": rope["rope_theta"],
                "partial_rotary_factor": rope["partial_rotary_factor"]}
    share = rope.get("partial_rotary_factor", 1.0) if partial else 1.0
    q, k, v, g = _project(
        x, layers, j, positions,
        inverse_frequencies(rope, int(m["head_dim"] * share)),
        eps=float(m["rms_norm_eps"]),
        scale=float(rope.get("attention_factor", 1.0)))
    keys = None
    if sliding and window:
        keys = int(m["sliding_window"] if window_keys is None
                   else window_keys)
    a = masked_attention(q, k, v, window=keys,
                         group=None if sliding else full_group)
    if gate:
        a = a * g[:, :, None]
    return _out(x, a, layers, j)


# ------------------------------------------------------------ feed-forward
@partial(jax.jit, static_argnames=("eps", "top_k", "scores"))
def _route(x, layers, j, scaling, *, eps, top_k, scores):
    """(n2(x), weights [S, k], experts [S, k]): the scores of all experts
    in float32, the ``top_k`` largest, renormalised to sum to 1 and times
    ``scaling``."""
    r = rms_norm(x, _f32(layers["mlp_norm"][j]), eps)
    products = r @ _f32(layers["router"][j])
    s = (jax.nn.sigmoid(products) if scores == "sigmoid"
         else jax.nn.softmax(products, axis=-1))
    weights, experts = jax.lax.top_k(s, top_k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * scaling
    return r, weights, experts


@jax.jit
def _routed(r, layers, j, weights, experts):
    """sum over ALL experts of (the expert at every position) times (each
    position's weight for it, 0 where it was not chosen)."""
    def one(e, y):
        gate = r @ _f32(layers["we_gate"][j, e])
        up = r @ _f32(layers["we_up"][j, e])
        out = (jax.nn.silu(gate) * up) @ _f32(layers["we_down"][j, e])
        w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return y + w[:, None] * out

    return jax.lax.fori_loop(0, layers["we_gate"].shape[1], one,
                             jnp.zeros_like(r))


@jax.jit
def _shared(r, layers, j):
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    return (jax.nn.silu(r @ at("ws_gate")) * (r @ at("ws_up"))) @ at("ws_down")


def expert_ffn(x, layers, j: int, m: Dict[str, Any], *,
               scaling: Optional[float] = None, shared: bool = True,
               scores: str = "sigmoid"):
    """x [S, hidden] (before the second norm) -> the feed-forward's output,
    without the residual. The three switches are controls."""
    r, weights, experts = _route(
        x, layers, j,
        float(m["moe_routed_scaling_factor"] if scaling is None
              else scaling),
        eps=float(m["rms_norm_eps"]), top_k=int(m["num_experts_per_tok"]),
        scores=scores)
    y = _routed(r, layers, j, weights, experts)
    return y + _shared(r, layers, j) if shared else y


# ------------------------------------------------------------------- model
FFN_CONTROLS = ("scaling", "shared", "scores")


def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any],
                  **controls):
    """tokens [S] int -> final hidden states [S, hidden], after the norm.
    ``controls``: ``attention``'s switches and ``expert_ffn``'s."""
    ffn = {k: controls.pop(k) for k in FFN_CONTROLS if k in controls}
    length = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -length % PAD_TO))
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        positions = jnp.arange(tokens.shape[0])
        for kind, layers, j in layer_leaves(params, m):
            x = attention(x, kind, layers, j, positions, m, **controls)
            if "router" in layers:
                x = x + expert_ffn(x, layers, j, m, **ffn)
            else:
                x = x + _dense_ffn(x, layers, j,
                                   eps=float(m["rms_norm_eps"]))
        return rms_norm(x[:length], params["final_norm"],
                        float(m["rms_norm_eps"]))


def logits(params: Dict[str, Any], tokens, m: Dict[str, Any], **controls):
    """[S, vocab] float32."""
    x = hidden_states(params, tokens, m, **controls)
    with jax.default_matmul_precision("highest"):
        return x @ _f32(params["lm_head"])


def last_logits(params: Dict[str, Any], tokens, m: Dict[str, Any],
                **controls):
    """[vocab] float32: the logits after the last token of the prompt."""
    x = hidden_states(params, tokens, m, **controls)
    with jax.default_matmul_precision("highest"):
        return x[-1] @ _f32(params["lm_head"])
