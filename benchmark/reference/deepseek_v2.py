"""Plain float32 reference of DeepSeek-V2's decoder
(``deepseek-ai/DeepSeek-V2``, ``model_type: deepseek_v2``): pre-norm blocks
of latent attention (MLA) and a feed-forward that is a dense SwiGLU in the
first ``first_k_dense_replace`` layers and, after them, shared experts
beside routed experts under a group-limited choice; a last RMSNorm and an
untied head.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed. That tree keeps one stacked
pytree a kind of layer, ``params["layers"]["latent_dense"]`` and
``["latent_routed"]`` (a model of one kind keeps the stack under
``params["layers"]`` itself); layer ``l`` is entry ``j`` of its kind's
stack, ``j`` the number of earlier layers of that kind. Leaves, each with a
leading dim over its kind's layers: ``attn_norm``, ``mlp_norm`` ``[hidden]``;
``wq_a [hidden, q_lora_rank]``, ``q_a_norm [q_lora_rank]``, ``wq_b
[q_lora_rank, heads, qk_nope + qk_rope]``, ``wkv_a [hidden, kv_lora_rank +
qk_rope]``, ``kv_a_norm [kv_lora_rank]``, ``wkv_b [kv_lora_rank, heads,
qk_nope + v_head_dim]``, ``wo [heads, v_head_dim, hidden]``; ``w_gate``,
``w_up`` ``[hidden, width]``, ``w_down``; ``router [hidden, E]``,
``we_gate``, ``we_up`` ``[held, hidden, width]``, ``we_down``; ``ws_gate``,
``ws_up`` ``[hidden, n_shared x width]``, ``ws_down``. Beside them ``embed
[vocab, hidden]``, ``final_norm`` and ``lm_head [hidden, vocab]``.

The equations (ISSUE 35; HuggingFace's ``modeling_deepseek.py`` of the
source as far as it is known here, there being no network to read it).
Block: ``x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x))``.

- MLA: ``c_q = RMSNorm(x W_qa)``; ``q = c_q W_qb``, a head ``[q_nope (128)
  | q_pe (64)]``. ``[c_kv (512) | k_pe (64)] = x W_kva``; ``c_kv =
  RMSNorm(c_kv)``; ``c_kv W_kvb`` gives a head ``[k_nope (128) | v (128)]``.
  ``q = [q_nope | rope(q_pe)]``, ``k = [k_nope | rope(k_pe)]`` with the one
  ``k_pe`` row a position given to every head; causal softmax of ``q k^T *
  s``, ``s = 192^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim * ln(factor) +
  1``; the heads' 128 values through ``W_o``. No bias anywhere.
- Rotary, over the 64: the source stores the rotary dims interleaved,
  ``(x0, y0, x1, y1, ...)``, and un-interleaves them to ``(x0, x1, ...,
  y0, y1, ...)`` before its rotate-half; so does this (``pairs_apart``).
  YaRN's frequencies: each a blend of ``theta^(-2i/64)`` and that over
  ``factor`` by the linear ramp between the two correction dims (``floor``
  of the dim that turns ``beta_fast`` times over
  ``original_max_position_embeddings`` positions, ``ceil`` of
  ``beta_slow``'s); cos and sin times ``yarn_mscale(factor, mscale) /
  yarn_mscale(factor, mscale_all_dim)``.
- Routed FFN: ``scores = softmax(r W_g)`` in float32 over all ``E``
  experts; ``group_limited_greedy``: the ``E`` in ``n_group`` groups of
  neighbours, a group's score the largest of its experts', the best
  ``topk_group`` groups kept, the other groups' scores set to 0, the
  ``num_experts_per_tok`` largest chosen; ``w`` the chosen scores, not
  renormalised (``norm_topk_prob`` false), times ``routed_scaling_factor``;
  ``y = shared(r) + sum_k w_k E_k(r)``, ``shared`` one SwiGLU of width
  ``n_shared_experts x moe_intermediate_size``, each expert a SwiGLU.

The share. The tree holds ``held`` of the router's ``E`` experts, the ones
from ``m["expert_share"]["first"]`` on, as one chip of the configuration's
deployment does. The router scores and chooses over all ``E``; the sum
runs over the held experts; what the absent ones would add is left out,
as the program leaves it out (the model-configs guide's usual cut).
``routed_part`` and ``shared_part`` give the two summands by themselves,
so that a test can add the shares up.

Departures from the description, none of which changes a result: HF
gathers for each expert the positions that chose it, and here each held
expert is computed at every position and multiplied by the position's
weight for it, which is 0 where it was not chosen; there is no cache: one
sequence, all its positions at once, attention in blocks of queries so
that 128 heads' scores fit beside the served weights; ``topk`` breaks ties
towards the lower index (``jax.lax.top_k``), which is what the program's
does; the training loss is the mean next-token cross-entropy alone
(``seq_aux``'s balancing term is not in it).

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers and experts run in
Python loops, one matrix cast to float32 at a time.

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.dense_decoder import rms_norm

QUERY_BLOCK = 256


def _f32(a):
    return a.astype(jnp.float32)


def layer_leaves(params: Dict[str, Any], m: Dict[str, Any]
                 ) -> List[Tuple[Dict[str, Any], int]]:
    """For each layer of the model, in order: (its kind's stacked leaves,
    its index in them)."""
    dense = m["first_k_dense_replace"]
    kinds = ["latent_dense" if l < dense else "latent_routed"
             for l in range(m["num_hidden_layers"])]
    stacks = (params["layers"] if len(set(kinds)) > 1
              else {kinds[0]: params["layers"]})
    return [(stacks[kind], kinds[:l].count(kind))
            for l, kind in enumerate(kinds)]


# ------------------------------------------------------------------ rotary
def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs: Dict[str, Any]) -> np.ndarray:
    """The ``dim / 2`` frequencies under ``rope_scaling`` ``rs``."""
    i = np.arange(0, dim, 2, dtype=np.float64)
    extrapolated = 1.0 / theta ** (i / dim)
    interpolated = extrapolated / rs["factor"]

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    # the ramp's 0 keeps the frequency as it is, its 1 divides it by factor
    return (interpolated * ramp + extrapolated * (1.0 - ramp)).astype(
        np.float32)


def softmax_scale(m: Dict[str, Any]) -> float:
    rs = m["rope_scaling"]
    width = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    return width ** -0.5 * yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2


def pairs_apart(x):
    """``(x0, y0, x1, y1, ...)`` on the last axis to ``(x0, x1, ..., y0,
    y1, ...)``."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


# --------------------------------------------------------------- attention
def _scores_to_values(q, k, v, scale):
    """q, k [S, H, Dk], v [S, H, Dv] -> [S, H, Dv], causal, in blocks of
    queries."""
    S = q.shape[0]
    kv_pos = jnp.arange(S)
    outs = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        q_pos = start + jnp.arange(qb.shape[0])
        scores = jnp.where(q_pos[:, None] >= kv_pos[None, :], scores,
                           -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


def _rotate(x, cos, sin):
    """x [S, ..., R] as the source stores it -> un-interleaved, then
    rotate-half by ``cos``, ``sin`` ``[S, R / 2]``."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x = pairs_apart(x)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("eps", "nope", "kvr", "scale"))
def _latent_attention(x, layers, j, cos, sin, *, eps, nope, kvr, scale):
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    u = rms_norm(x, at("attn_norm"), eps)
    c_q = rms_norm(u @ at("wq_a"), at("q_a_norm"), eps)
    q = jnp.einsum("sr,rnd->snd", c_q, at("wq_b"))
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = u @ at("wkv_a")
    c_kv, k_pe = rms_norm(ckv[:, :kvr], at("kv_a_norm"), eps), ckv[:, kvr:]
    kv = jnp.einsum("sr,rnd->snd", c_kv, at("wkv_b"))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rotate(q_pe, cos, sin)
    k_pe = _rotate(k_pe, cos, sin)                       # [S, 64]
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    # the one rotary key of a position, given to every head
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :],
                                  k_nope.shape[:2] + k_pe.shape[1:])], -1)
    a = _scores_to_values(q, k, v, scale)
    return x + jnp.einsum("snd,ndh->sh", a, at("wo"))


def yarn_cos_sin(positions, m: Dict[str, Any]):
    """cos and sin ``[S, 32]`` at YaRN's frequencies and amplitude."""
    rs = m["rope_scaling"]
    inv_freq = jnp.asarray(yarn_inv_freq(m["qk_rope_head_dim"],
                                         float(m["rope_theta"]), rs))
    amplitude = (yarn_mscale(rs["factor"], rs["mscale"])
                 / yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang) * amplitude, jnp.sin(ang) * amplitude


def latent_attention(x, layers, j: int, positions, m: Dict[str, Any], *,
                     scale=None):
    """x [S, hidden] -> x + MLA of its norm. ``scale``: the softmax scale,
    for a control that leaves YaRN's ``m^2`` out."""
    cos, sin = yarn_cos_sin(positions, m)
    return _latent_attention(
        x, layers, j, cos, sin, eps=float(m["rms_norm_eps"]),
        nope=m["qk_nope_head_dim"], kvr=m["kv_lora_rank"],
        scale=float(softmax_scale(m) if scale is None else scale))


# ------------------------------------------------------------ feed-forward
def _swiglu(r, gate, up, down):
    return (jax.nn.silu(r @ _f32(gate)) * (r @ _f32(up))) @ _f32(down)


@partial(jax.jit, static_argnames=("eps",))
def dense_ffn(x, layers, j, *, eps):
    r = rms_norm(x, _f32(layers["mlp_norm"][j]), eps)
    return x + _swiglu(r, layers["w_gate"][j], layers["w_up"][j],
                       layers["w_down"][j])


@partial(jax.jit, static_argnames=("top_k", "groups", "kept_groups",
                                   "renormalise", "scaling"))
def route(r, router, *, top_k, groups, kept_groups, renormalise, scaling):
    """r [S, hidden] (normed) -> (scores [S, E], weights [S, k], experts
    [S, k]) over all ``E`` of the router's columns; ``groups`` 0 is a
    plain top-k."""
    scores = jax.nn.softmax(r @ _f32(router), axis=-1)
    on = scores
    if groups:
        S, E = scores.shape
        best = jnp.max(scores.reshape(S, groups, E // groups), axis=-1)
        _, kept = jax.lax.top_k(best, kept_groups)
        stays = jnp.zeros((S, groups), bool).at[
            jnp.arange(S)[:, None], kept].set(True)
        on = jnp.where(jnp.repeat(stays, E // groups, axis=1), scores, 0.0)
    weights, experts = jax.lax.top_k(on, top_k)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return scores, weights * scaling, experts


@jax.jit
def _one_expert(r, layers, j, e, expert_id, weights, experts):
    """Held expert ``e`` of the layer at every position, times each
    position's weight for its id among all the experts (0 where it was not
    chosen)."""
    w = jnp.sum(jnp.where(experts == expert_id, weights, 0.0), axis=-1)
    return w[:, None] * _swiglu(r, layers["we_gate"][j, e],
                                layers["we_up"][j, e], layers["we_down"][j, e])


def routed_part(r, layers, j: int, m: Dict[str, Any], *,
                group_limited: bool = True):
    """r [S, hidden] (normed) -> the held experts' part of the routed sum."""
    grouped = group_limited and m["topk_method"] == "group_limited_greedy"
    _, weights, experts = route(
        r, layers["router"][j], top_k=int(m["num_experts_per_tok"]),
        groups=int(m["n_group"]) if grouped else 0,
        kept_groups=int(m["topk_group"]),
        renormalise=bool(m["norm_topk_prob"]),
        scaling=float(m["routed_scaling_factor"]))
    first = m["expert_share"]["first"]
    y = jnp.zeros_like(r)
    for e in range(layers["we_gate"].shape[1]):
        y = y + _one_expert(r, layers, j, e, first + e, weights, experts)
    return y


@jax.jit
def shared_part(r, layers, j):
    return _swiglu(r, layers["ws_gate"][j], layers["ws_up"][j],
                   layers["ws_down"][j])


def moe_ffn(x, layers, j: int, m: Dict[str, Any], *,
            group_limited: bool = True, shared: bool = True):
    """x [S, hidden] (before the feed-forward's norm) -> the feed-forward's
    output, without the residual. The two switches are controls."""
    with jax.default_matmul_precision("highest"):
        r = rms_norm(x, _f32(layers["mlp_norm"][j]), float(m["rms_norm_eps"]))
        y = routed_part(r, layers, j, m, group_limited=group_limited)
        if shared and m["n_shared_experts"]:
            y = y + shared_part(r, layers, j)
    return y


# ------------------------------------------------------------------- model
def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any],
                  **controls):
    """tokens [S] int -> final hidden states [S, hidden], after the norm.
    ``controls``: ``scale`` for the attention, ``group_limited`` and
    ``shared`` for the feed-forward; none is the model."""
    attn = {k: v for k, v in controls.items() if k == "scale"}
    ffn = {k: v for k, v in controls.items() if k != "scale"}
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        positions = jnp.arange(tokens.shape[0])
        for layers, j in layer_leaves(params, m):
            x = latent_attention(x, layers, j, positions, m, **attn)
            if "router" in layers:
                x = x + moe_ffn(x, layers, j, m, **ffn)
            else:
                x = dense_ffn(x, layers, j, eps=float(m["rms_norm_eps"]))
        return rms_norm(x, params["final_norm"], float(m["rms_norm_eps"]))


def logits(params: Dict[str, Any], tokens, m: Dict[str, Any], **controls):
    """[S, vocab] float32."""
    x = hidden_states(params, tokens, m, **controls)
    with jax.default_matmul_precision("highest"):
        return x @ _f32(params["lm_head"])


def last_logits(params: Dict[str, Any], tokens, m: Dict[str, Any],
                **controls):
    """What ``drivers/serve.py``'s check asks of a reference, under the
    driver's name for it; for this family ``[vocab]`` float32, the mean
    over the prompt's positions of the logits
    (``families/deepseek_v2.py::Served.last_position_logits`` says why)."""
    return jnp.mean(logits(params, tokens, m, **controls), axis=0)


def loss(params: Dict[str, Any], inputs, targets, m: Dict[str, Any]):
    """Mean next-token cross-entropy of one sequence (inputs, targets [S])."""
    lg = logits(params, inputs, m)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
