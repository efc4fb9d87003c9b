"""Plain float32 reference of OLMoE (``allenai/OLMoE-1B-7B-0125-Instruct``):
pre-norm blocks with an RMSNorm over the whole query and key projections,
rotary, causal attention, a feed-forward of routed experts with no shared
expert, untied head; cross-entropy plus the routers' load-balancing term.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed (layers stacked on a leading
axis: ``wq [L, hidden, heads, head_dim]``, ``wk``/``wv`` at the key/value
heads, ``wo [L, heads, head_dim, hidden]``, ``q_norm [L, heads x head_dim]``,
``k_norm``, ``router [L, hidden, E]``, ``we_gate``/``we_up``
``[L, E, hidden, width]``, ``we_down [L, E, width, hidden]``, the two block
norms, ``embed``, ``final_norm``, ``lm_head [hidden, vocab]``). The norm,
the rotary embedding and the blocked causal attention are the dense
reference's (``reference/dense_decoder.py``): the same mathematics.

It follows HuggingFace's ``modeling_olmoe``. With ``n1``, ``n2`` the
block's two RMSNorms::

    x += Wo . attn(rope(split(q_norm(Wq n1(x)))),
                   rope(split(k_norm(Wk n1(x)))), split(Wv n1(x)))
    p = softmax_f32(n2(x) W_r);  (w, e) = top_k(p)     # k = 8, E = 64
    w = w / sum(w)  only if norm_topk_prob (false as published)
    x += sum_k w_k . W_down[e_k](silu(W_gate[e_k] n2(x)) * W_up[e_k] n2(x))

causal, scale ``head_dim^-0.5``, rotate-half rope, then the final RMSNorm
and the head. The training loss is the mean next-token cross-entropy plus
``router_aux_loss_coef`` times the Switch load-balancing term as
``load_balancing_loss_func`` computes it over all layers' router logits
concatenated: ``E x sum_e (share of the (layer, position, k) choices that
went to e) x (mean router probability of e)``.

Departures from that description, none of which changes a result:

- ``q_norm``/``k_norm`` weights are stored flat over ``heads x head_dim``,
  as HF stores them, while ``wq``/``wk`` are stored split into heads; the
  projection is flattened for the norm and split again.
- HF gathers, for each expert, the positions that chose it. Here each
  expert is computed at every position and its output multiplied by the
  position's weight for it, which is exactly 0 where the router did not
  choose it: static shapes, the same sums.
- ``clip_qkv`` is null, there are no biases and no dropout: none is
  written.
- One sequence at a time, no padding mask in the load-balancing term.

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers and experts run in
Python loops and one expert's three matrices are cast to float32 at a time
(25 MB at the published widths), so that on the chip the reference fits
beside 13.84 GB of served weights.

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import (
    grouped_causal_attention, rms_norm, rotary)


def _f32(a):
    return a.astype(jnp.float32)


@partial(jax.jit, static_argnames=("eps", "theta"))
def _attention_half(x, layers, i, positions, *, eps, theta):
    """x [S, hidden] -> x + attention, for layer ``i`` of the stacked
    attention leaves."""
    at = lambda name: _f32(layers[name][i])  # noqa: E731
    h = rms_norm(x, at("attn_norm"), eps)
    q = jnp.einsum("sh,hnd->snd", h, at("wq"))
    k = jnp.einsum("sh,hnd->snd", h, at("wk"))
    v = jnp.einsum("sh,hnd->snd", h, at("wv"))
    S = x.shape[0]
    q = rms_norm(q.reshape(S, -1), at("q_norm"), eps).reshape(q.shape)
    k = rms_norm(k.reshape(S, -1), at("k_norm"), eps).reshape(k.shape)
    a = grouped_causal_attention(rotary(q, positions, theta),
                                 rotary(k, positions, theta), v)
    return x + jnp.einsum("snd,ndh->sh", a, at("wo"))


@partial(jax.jit, static_argnames=("eps", "top_k", "renormalise"))
def _route(x, layers, i, *, eps, top_k, renormalise):
    """(n2(x), router logits [S, E], weights [S, k], experts [S, k])."""
    h = rms_norm(x, _f32(layers["mlp_norm"][i]), eps)
    logits = h @ _f32(layers["router"][i])
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return h, logits, weights, experts


@jax.jit
def _one_expert(h, layers, i, e, weights, experts):
    """Expert ``e`` of layer ``i`` at every position, times each
    position's weight for it (0 where it was not chosen)."""
    gate = h @ _f32(layers["we_gate"][i, e])
    up = h @ _f32(layers["we_up"][i, e])
    out = (jax.nn.silu(gate) * up) @ _f32(layers["we_down"][i, e])
    w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
    return w[:, None] * out


def expert_ffn(h_in, layers, i: int, m: Dict[str, Any]):
    """x [S, hidden] (before the second norm) -> (the routed
    feed-forward's output [S, hidden], router logits [S, E])."""
    with jax.default_matmul_precision("highest"):
        h, logits, weights, experts = _route(
            h_in, layers, i, eps=float(m["rms_norm_eps"]),
            top_k=int(m["num_experts_per_tok"]),
            renormalise=bool(m["norm_topk_prob"]))
        y = jnp.zeros_like(h)
        for e in range(m["num_experts"]):
            y = y + _one_expert(h, layers, i, e, weights, experts)
    return y, logits


def block(x, layers, i: int, positions, m: Dict[str, Any]):
    """One block: x [S, hidden] -> (x, router logits [S, E])."""
    with jax.default_matmul_precision("highest"):
        x = _attention_half(x, layers, i, positions,
                            eps=float(m["rms_norm_eps"]),
                            theta=float(m["rope_theta"]))
    y, logits = expert_ffn(x, layers, i, m)
    return x + y, logits


def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any]
                  ) -> Tuple[jax.Array, List[jax.Array]]:
    """tokens [S] int -> (final hidden states [S, hidden] after the norm,
    every layer's router logits)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        positions = jnp.arange(tokens.shape[0])
        router_logits = []
        for i in range(m["num_hidden_layers"]):
            x, logits = block(x, params["layers"], i, positions, m)
            router_logits.append(logits)
        return rms_norm(x, params["final_norm"],
                        float(m["rms_norm_eps"])), router_logits


def logits(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """[S, vocab] float32."""
    x, _ = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return x @ _f32(params["lm_head"])


def last_logits(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """[vocab] float32: the logits after the last token of the prompt."""
    x, _ = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return x[-1] @ _f32(params["lm_head"])


def load_balancing_loss(router_logits: List[jax.Array], m: Dict[str, Any]):
    """HF's ``load_balancing_loss_func`` without a padding mask: all
    layers' logits concatenated to [L x S, E]."""
    E, k = m["num_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(jnp.concatenate(router_logits, axis=0), axis=-1)
    _, chosen = jax.lax.top_k(probs, k)
    expert_mask = jax.nn.one_hot(chosen, E, dtype=jnp.float32)  # [N, k, E]
    tokens_per_expert = jnp.mean(expert_mask, axis=0)           # [k, E]
    router_prob_per_expert = jnp.mean(probs, axis=0)            # [E]
    return E * jnp.sum(tokens_per_expert * router_prob_per_expert[None, :])


def loss(params: Dict[str, Any], inputs, targets, m: Dict[str, Any]):
    """Mean next-token cross-entropy of one sequence (inputs, targets [S])
    plus ``router_aux_loss_coef`` times the load-balancing term."""
    x, router_logits = hidden_states(params, inputs, m)
    with jax.default_matmul_precision("highest"):
        lg = x @ _f32(params["lm_head"])
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked) + float(m["router_aux_loss_coef"]) \
        * load_balancing_loss(router_logits, m)
