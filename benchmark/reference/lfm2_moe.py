"""Plain float32 reference of LFM2's mixture-of-experts decoder
(``LiquidAI/LFM2-24B-A2B``, ``model_type: lfm2_moe``): pre-norm blocks whose
operator is a gated short convolution or grouped-query attention by
``layer_types``, whose feed-forward is a dense SwiGLU in the first
``num_dense_layers`` layers and routed experts after them, a last RMSNorm
and a head tied to the embedding.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed. That tree keeps one stacked
pytree a kind of layer, ``params["layers"]["<operator>_<feed-forward>"]``
with ``operator`` ``attention`` or ``conv`` and ``feed-forward`` ``routed``
or ``dense`` (a model of one kind keeps the stack under ``params["layers"]``
itself); layer ``l`` is entry ``j`` of its kind's stack, ``j`` the number of
earlier layers of that kind. Leaves, each with a leading dim over its
kind's layers: ``attn_norm``, ``mlp_norm`` ``[hidden]`` (the operator's
and the feed-forward's norm); ``wq [hidden, heads, head_dim]``, ``wk``,
``wv`` at the key/value heads, ``wo [heads, head_dim, hidden]``,
``q_norm``, ``k_norm`` ``[head_dim]``; ``conv_in [hidden, 3 x hidden]``,
``conv_w [hidden, L]``, ``conv_out [hidden, hidden]``; ``w_gate``, ``w_up``
``[hidden, width]``, ``w_down``; ``router [hidden, E]``, ``router_bias
[E]``, ``we_gate``, ``we_up`` ``[E, hidden, width]``, ``we_down``. Beside
them ``embed [vocab, hidden]`` and ``final_norm``.

The equations (ISSUE 33; HuggingFace's ``modeling_lfm2_moe`` as far as it
is known here, there being no network to read it). Block ``l``::

    u = RMSNorm(x; attn_norm);  h = x + Op_l(u)
    y = h + FFN_l(RMSNorm(h; mlp_norm))

- Short conv, ``L = conv_L_cache`` taps: ``[B_t, C_t, X_t] = W_in u_t``
  split in that order; ``z_t = B_t * X_t``; ``c_t = sum_j w[:, j] *
  z_{t-(L-1)+j}``, depthwise and causal, ``z`` of a negative index 0;
  ``o_t = W_out (C_t * c_t)``. No bias.
- Attention: 32 query heads over 8 key/value heads of 64; ``q`` and ``k``
  each RMSNorm over the head's 64 dims with one learned weight shared by
  the heads, before the rotation; rotate-half rope over the whole head;
  causal softmax, scale ``head_dim^-0.5``.
- Dense FFN: ``W_down (silu(W_gate r) * W_up r)``.
- Routed FFN: ``s = sigmoid(W_r r)``; ``chosen = top_k(s + b)``;
  ``w = s[chosen]``, the scores without the bias; ``w = w / (sum(w) +
  1e-6)`` if ``norm_topk_prob``; ``w = w * routed_scaling_factor``; the
  output is ``sum_k w_k . W_down[e_k](silu(W_gate[e_k] r) * W_up[e_k] r)``.

Departures from that description, none of which changes a result:

- HF gathers, for each expert, the positions that chose it. Here each
  expert is computed at every position and its output multiplied by the
  position's weight for it, which is exactly 0 where the router did not
  choose it: static shapes, the same sums.
- HF's decode keeps ``L`` columns of the convolution's input a sequence;
  there is no cache here: one sequence, all its positions at once, the
  convolution a sum of ``L`` shifted copies of ``z``.
- ``conv_bias`` is false and nothing else has a bias: none is written.
- The head is the embedding's transpose (``tie_word_embeddings``, assumed:
  the configuration file says so under ``assumed``).
- The training loss is the mean next-token cross-entropy alone: the
  published configuration has no load-balancing coefficient, and the bias
  ``b`` is a buffer that no gradient reaches.

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers and experts run in
Python loops and one expert's three matrices are cast to float32 at a time
(38 MB at the published widths), so that on the chip the reference fits
beside 10.4 GB of served weights.

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (
    grouped_causal_attention, rms_norm, rotary)

OPERATORS = {"full_attention": "attention", "conv": "conv"}


def _f32(a):
    return a.astype(jnp.float32)


def layer_leaves(params: Dict[str, Any], m: Dict[str, Any]
                 ) -> List[Tuple[Dict[str, Any], int]]:
    """For each layer of the model, in order: (its kind's stacked leaves,
    its index in them)."""
    kinds = [OPERATORS[op] + ("_dense" if l < m["num_dense_layers"]
                              else "_routed")
             for l, op in enumerate(m["layer_types"])]
    stacks = (params["layers"] if len(set(kinds)) > 1
              else {kinds[0]: params["layers"]})
    return [(stacks[kind], kinds[:l].count(kind))
            for l, kind in enumerate(kinds)]


@partial(jax.jit, static_argnames=("eps",))
def short_conv(x, layers, j, *, eps):
    """x [S, hidden] -> x + the gated short convolution of its norm."""
    u = rms_norm(x, _f32(layers["attn_norm"][j]), eps)
    b, c, xx = jnp.split(u @ _f32(layers["conv_in"][j]), 3, axis=-1)
    z = b * xx
    w = _f32(layers["conv_w"][j])                          # [hidden, L]
    taps, S = w.shape[1], x.shape[0]
    zp = jnp.concatenate([jnp.zeros((taps - 1, z.shape[1])), z], axis=0)
    conv = sum(w[:, t] * zp[t:t + S] for t in range(taps))
    return x + (c * conv) @ _f32(layers["conv_out"][j])


@partial(jax.jit, static_argnames=("eps", "theta"))
def attention(x, layers, j, positions, *, eps, theta):
    """x [S, hidden] -> x + attention of its norm."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    u = rms_norm(x, at("attn_norm"), eps)
    q = jnp.einsum("sh,hnd->snd", u, at("wq"))
    k = jnp.einsum("sh,hnd->snd", u, at("wk"))
    v = jnp.einsum("sh,hnd->snd", u, at("wv"))
    q = rms_norm(q, at("q_norm"), eps)  # over each head's head_dim
    k = rms_norm(k, at("k_norm"), eps)
    a = grouped_causal_attention(rotary(q, positions, theta),
                                 rotary(k, positions, theta), v)
    return x + jnp.einsum("snd,ndh->sh", a, at("wo"))


@partial(jax.jit, static_argnames=("eps",))
def dense_ffn(x, layers, j, *, eps):
    r = rms_norm(x, _f32(layers["mlp_norm"][j]), eps)
    gate = r @ _f32(layers["w_gate"][j])
    up = r @ _f32(layers["w_up"][j])
    return x + (jax.nn.silu(gate) * up) @ _f32(layers["w_down"][j])


@partial(jax.jit, static_argnames=("eps", "top_k", "renormalise", "scaling"))
def route(x, layers, j, *, eps, top_k, renormalise, scaling):
    """(the feed-forward's normed input r, scores [S, E], weights [S, k],
    experts [S, k])."""
    r = rms_norm(x, _f32(layers["mlp_norm"][j]), eps)
    scores = jax.nn.sigmoid(r @ _f32(layers["router"][j]))
    on = scores
    if "router_bias" in layers:  # use_expert_bias: it moves the choice only
        on = scores + _f32(layers["router_bias"][j])
    _, experts = jax.lax.top_k(on, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return r, scores, weights * scaling, experts


@jax.jit
def _one_expert(r, layers, j, e, weights, experts):
    """Expert ``e`` of the layer at every position, times each position's
    weight for it (0 where it was not chosen)."""
    gate = r @ _f32(layers["we_gate"][j, e])
    up = r @ _f32(layers["we_up"][j, e])
    out = (jax.nn.silu(gate) * up) @ _f32(layers["we_down"][j, e])
    w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
    return w[:, None] * out


def routed_ffn(x, layers, j: int, m: Dict[str, Any]):
    """x [S, hidden] (before the feed-forward's norm) -> the routed
    feed-forward's output [S, hidden], without the residual."""
    with jax.default_matmul_precision("highest"):
        r, _, weights, experts = route(
            x, layers, j, eps=float(m["norm_eps"]),
            top_k=int(m["num_experts_per_tok"]),
            renormalise=bool(m["norm_topk_prob"]),
            scaling=float(m["routed_scaling_factor"]))
        y = jnp.zeros_like(r)
        for e in range(m["num_experts"]):
            y = y + _one_expert(r, layers, j, e, weights, experts)
    return y


def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """tokens [S] int -> final hidden states [S, hidden], after the norm."""
    eps = float(m["norm_eps"])
    theta = float(m["rope_parameters"]["rope_theta"])
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        positions = jnp.arange(tokens.shape[0])
        for layers, j in layer_leaves(params, m):
            if "conv_in" in layers:
                x = short_conv(x, layers, j, eps=eps)
            else:
                x = attention(x, layers, j, positions, eps=eps, theta=theta)
            if "router" in layers:
                x = x + routed_ffn(x, layers, j, m)
            else:
                x = dense_ffn(x, layers, j, eps=eps)
        return rms_norm(x, params["final_norm"], eps)


def logits(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """[S, vocab] float32."""
    x = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return x @ _f32(params["embed"]).T


def last_logits(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """What ``drivers/serve.py``'s check asks of a reference, under the
    driver's name for it; for this family ``[vocab]`` float32, the mean
    over the prompt's positions of the logits
    (``families/lfm2_moe.py::Served.last_position_logits`` says why)."""
    return jnp.mean(logits(params, tokens, m), axis=0)


def loss(params: Dict[str, Any], inputs, targets, m: Dict[str, Any]):
    """Mean next-token cross-entropy of one sequence (inputs, targets [S])."""
    lg = logits(params, inputs, m)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
