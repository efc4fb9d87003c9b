"""Plain float32 reference of Ling-3.0-flash's language model
(``inclusionAI/Ling-3.0-flash``, ``model_type: bailing_hybrid``; the
multi-token-prediction module is left out: a served model without
speculation does not run it): pre-norm blocks whose operator is Kimi delta
attention (KDA) in five layers of every ``layer_group_size`` 6 and latent
attention (MLA) with full-rank queries in the sixth, and whose feed-forward
is a dense SwiGLU in the first ``first_k_dense_replace`` layers and, after
them, one shared expert beside routed experts under a sigmoid router whose
groups score by their two best; a last RMSNorm and an untied head.

Independent of ``ray_tpu/models`` and of ``ray_tpu/ops/kda.py``: it shares
nothing with the program but the layout of the parameter tree it is
handed. That tree keeps one stacked pytree a kind of layer,
``params["layers"][kind]``, ``kind`` one of ``kda_dense``, ``kda_routed``,
``latent_dense``, ``latent_routed`` (a model of one kind keeps the stack
under ``params["layers"]`` itself); layer ``l`` is entry ``j`` of its
kind's stack, ``j`` the number of earlier layers of that kind. Leaves, each
with a leading dim over its kind's layers: ``attn_norm``, ``mlp_norm``
``[hidden]``; of a KDA layer ``kda_in [hidden, 5 x inner]`` (columns ``[q |
k | v | a | z]``, ``inner`` = heads x head_dim), ``kda_beta [hidden,
heads]``, ``kda_conv_w [3 x inner, taps]`` (over ``[q | k | v]``),
``kda_a_log [heads]``, ``kda_dt_bias [inner]``, ``kda_norm [head_dim]``,
``kda_out [inner, hidden]``; of an MLA layer ``wq [hidden, heads, nope +
rope]``, ``wkv_a [hidden, kv_rank + rope]``, ``kv_a_norm``, ``wkv_b
[kv_rank, heads, nope + v]``, ``wo [heads, v, hidden]``, ``w_head_gate
[hidden, heads]``; ``w_gate``, ``w_up``, ``w_down``; ``router [hidden,
E]``, ``router_bias [E]``, ``we_gate``, ``we_up`` ``[held, hidden,
width]``, ``we_down``; ``ws_gate``, ``ws_up``, ``ws_down``. Beside them
``embed``, ``final_norm`` and ``lm_head [hidden, vocab]``.

The equations (ISSUE 52; there is no network to read the source's modeling
code, so each mechanism is written in its published form, and every reading
that is not a key of the catalog's ``config`` is in the configuration
file's ``assumed``). ``u`` is the layer's RMS-normed input, epsilon
``rms_norm_eps``; no projection has a bias; block: ``x += Op(u); x +=
FFN(RMSNorm(x))``. Layer ``l`` is MLA where ``(l + 1) % layer_group_size ==
0``, else KDA.

- KDA (Kimi Linear, arXiv:2510.26692; 32 heads, ``d_k = d_v = 128``): ``q~
  = u W_q``, ``k~ = u W_k``, ``v~ = u W_v``; ``q = silu(taps(q~))`` and
  likewise ``k``, ``v``: ``short_conv_kernel_size`` 4 depthwise causal taps
  without bias, zeros before the sequence; ``q`` and ``k`` L2-normed a head
  (``x / sqrt(sum x^2 + 1e-6)``), ``q`` then times ``d_k ** -0.5``; ``beta
  = sigmoid(u W_beta)`` a head; the decay a channel: ``a = u W_f``, ``g =
  kda_lower_bound * sigmoid(exp(A_log[h]) * (a + dt_bias))`` in (-5, 0),
  ``alpha = exp(g)``. A head's state ``S [d_k, d_v]``, zeros at the start:
  ``S' = Diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t - S'^T
  k_t)^T``; ``o_t = S_t^T q_t``. ``y = W_o (RMSNorm_head(o_t) * sigmoid(u
  W_g))``: one weight ``[128]`` over each head's channels, the gate after
  the norm. No rotation. The recurrence runs position by position
  (``lax.scan``): no chunk, no kernel, no cache.
- MLA (DeepSeek-V2's, ``q_lora_rank`` null): ``[q_nope | q_pe] = u W_q`` a
  head of 128 + 64; ``[c_kv | k_pe] = u W_kva``, ``c_kv = RMSNorm(c_kv)``;
  ``[k_nope | v] = c_kv W_kvb`` a head; rope at ``rope_theta`` on the 64
  rotary dims of ``q_pe`` and of ``k_pe`` (one row a position, every
  head's), stored interleaved and un-interleaved before rotate-half;
  causal softmax of the scores times ``192 ** -0.5``; head ``n``'s output
  times ``sigmoid(u W_hg)[n]`` before ``W_o``.
- Routed FFN (DeepSeek-V3's ``noaux_tc``): ``s = sigmoid(r W_r)`` in
  float32 over all ``E``; on ``s + b``: ``n_group`` groups of neighbours, a
  group's score the sum of its two largest, the best ``topk_group`` groups
  stay (the others' experts at 0), the ``num_experts_per_tok`` largest are
  chosen; their weights are ``s`` without the bias over their sum plus
  1e-20, times ``routed_scaling_factor``; ``y = shared(r) + sum_k w_k
  E_k(r)``. No clamp on a SwiGLU: the layers whose
  ``expert_swiglu_limit_list`` is not 0 are not in a cut that the
  configuration's ``check`` lets through.

The share: as ``reference/deepseek_v2.py``. The tree holds ``held`` of the
router's ``E`` experts, from ``m["expert_share"]["first"]`` on; the router
scores and chooses over all ``E``; the sum runs over the held experts.

Departures from the description, none of which changes a result: each held
expert is computed at every position and multiplied by the position's
weight for it (0 where it was not chosen); no cache: one sequence, all its
positions at once; attention as a masked softmax in blocks of queries;
``top_k`` breaks ties towards the lower index.

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers and experts run in
Python loops, one matrix cast to float32 at a time.

``logits`` takes the controls of the cell's limit as keyword arguments,
each a fault planted in the reference (``tools/ling3_probe.py`` reads
them): ``delta`` False (what the state already answers to ``k_t`` is not
taken off ``v_t``), ``beta_one`` True, ``lower_bound`` (another bound of
the decay's gate), ``gate_first`` True (the output gate before the head's
norm), ``drop_state_every`` (the state zeroed at every multiple of that
many positions: a kernel that loses it at its chunks' edges), ``head_gate``
False (MLA's head-wise gate left out) and ``group_score`` ``"max"`` (a
group scored by its best expert alone).

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import rms_norm
from benchmark.reference.deepseek_v2 import (
    _f32, _one_expert, _rotate, _scores_to_values, dense_ffn, shared_part)


def layer_kinds(m: Dict[str, Any]) -> List[str]:
    period, dense = m["layer_group_size"], m["first_k_dense_replace"]
    return [("latent" if (l + 1) % period == 0 else "kda")
            + ("_dense" if l < dense else "_routed")
            for l in range(m["num_hidden_layers"])]


def layer_leaves(params: Dict[str, Any], m: Dict[str, Any]
                 ) -> List[Tuple[str, Dict[str, Any], int]]:
    """For each layer of the model, in order: (its kind, its kind's stacked
    leaves, its index in them)."""
    kinds = layer_kinds(m)
    stacks = (params["layers"] if len(set(kinds)) > 1
              else {kinds[0]: params["layers"]})
    return [(kind, stacks[kind], kinds[:l].count(kind))
            for l, kind in enumerate(kinds)]


# -------------------------------------------------- Kimi delta attention
def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@partial(jax.jit, static_argnames=(
    "eps", "heads", "lower_bound", "delta", "beta_one", "gate_first",
    "drop_state_every"))
def kda_layer(x, layers, j, *, eps, heads, lower_bound, delta=True,
              beta_one=False, gate_first=False, drop_state_every=0):
    """x [S, hidden] -> x + Kimi delta attention of its norm."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    S = x.shape[0]
    u = rms_norm(x, at("attn_norm"), eps)
    proj = u @ at("kda_in")
    inner = proj.shape[1] // 5
    hd = inner // heads
    w = at("kda_conv_w")                                    # [3 inner, taps]
    taps = w.shape[1]
    qkv = proj[:, :3 * inner]
    padded = jnp.concatenate([jnp.zeros((taps - 1, 3 * inner)), qkv], axis=0)
    qkv = jax.nn.silu(sum(w[:, t] * padded[t:t + S] for t in range(taps)))
    q, k, v = (qkv[:, i * inner:(i + 1) * inner].reshape(S, heads, hd)
               for i in range(3))
    q, k = l2_norm(q) * hd ** -0.5, l2_norm(k)
    beta = jax.nn.sigmoid(u @ at("kda_beta"))               # [S, heads]
    if beta_one:
        beta = jnp.ones_like(beta)
    a = proj[:, 3 * inner:4 * inner].reshape(S, heads, hd)
    g = lower_bound * jax.nn.sigmoid(
        jnp.exp(at("kda_a_log"))[:, None]
        * (a + at("kda_dt_bias").reshape(heads, hd)))       # [S, heads, hd]
    alpha = jnp.exp(g)

    def a_position(state, inp):
        q_t, k_t, v_t, alpha_t, beta_t, t = inp
        if drop_state_every:
            state = jnp.where(t % drop_state_every == 0, 0.0, state)
        state = alpha_t[:, :, None] * state                 # [heads, dk, dv]
        left = v_t
        if delta:
            left = v_t - jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + (beta_t[:, None] * k_t)[:, :, None] * left[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(a_position, jnp.zeros((heads, hd, hd), jnp.float32),
                        (q, k, v, alpha, beta, jnp.arange(S)))
    z = jax.nn.sigmoid(proj[:, 4 * inner:].reshape(S, heads, hd))
    if gate_first:
        o = rms_norm(o * z, at("kda_norm"), eps)
    else:
        o = rms_norm(o, at("kda_norm"), eps) * z
    return x + o.reshape(S, inner) @ at("kda_out")


# ---------------------------------------------------- latent attention
@partial(jax.jit, static_argnames=("eps", "nope", "kvr", "scale", "gate"))
def _latent_attention(x, layers, j, cos, sin, *, eps, nope, kvr, scale,
                      gate):
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    u = rms_norm(x, at("attn_norm"), eps)
    q = jnp.einsum("sh,hnd->snd", u, at("wq"))
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    ckv = u @ at("wkv_a")
    c_kv, k_pe = rms_norm(ckv[:, :kvr], at("kv_a_norm"), eps), ckv[:, kvr:]
    kv = jnp.einsum("sr,rnd->snd", c_kv, at("wkv_b"))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rotate(q_pe, cos, sin)
    k_pe = _rotate(k_pe, cos, sin)                          # [S, rope]
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    # the one rotary key of a position, given to every head
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :],
                                  k_nope.shape[:2] + k_pe.shape[1:])], -1)
    a = _scores_to_values(q, k, v, scale)
    if gate:
        a = a * jax.nn.sigmoid(u @ at("w_head_gate"))[..., None]
    return x + jnp.einsum("snd,ndh->sh", a, at("wo"))


def latent_attention(x, layers, j: int, positions, m: Dict[str, Any], *,
                     head_gate: bool = True):
    """x [S, hidden] -> x + MLA of its norm."""
    rope = m["qk_rope_head_dim"]
    inv_freq = float(m["rope_theta"]) ** (
        -jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return _latent_attention(
        x, layers, j, jnp.cos(ang), jnp.sin(ang),
        eps=float(m["rms_norm_eps"]), nope=m["qk_nope_head_dim"],
        kvr=m["kv_lora_rank"],
        scale=float((m["qk_nope_head_dim"] + rope) ** -0.5),
        gate=bool(head_gate))


# ------------------------------------------------------------ feed-forward
@partial(jax.jit, static_argnames=("top_k", "groups", "kept_groups",
                                   "group_score", "renormalise", "scaling"))
def route(r, router, bias, *, top_k, groups, kept_groups, group_score,
          renormalise, scaling):
    """r [S, hidden] (normed) -> (scores [S, E], weights [S, k], experts
    [S, k]) over all ``E`` of the router's columns: groups and experts
    chosen on the sigmoid scores plus the bias, the weights the scores
    without it."""
    scores = jax.nn.sigmoid(r @ _f32(router))
    on = scores + _f32(bias)
    S, E = scores.shape
    members = on.reshape(S, groups, E // groups)
    if group_score == "top2":
        of_group = jnp.sum(jax.lax.top_k(members, 2)[0], axis=-1)
    else:
        of_group = jnp.max(members, axis=-1)
    _, kept = jax.lax.top_k(of_group, kept_groups)
    stays = jnp.zeros((S, groups), bool).at[
        jnp.arange(S)[:, None], kept].set(True)
    on = jnp.where(jnp.repeat(stays, E // groups, axis=1), on, 0.0)
    _, experts = jax.lax.top_k(on, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return scores, weights * scaling, experts


def routed_part(r, layers, j: int, m: Dict[str, Any], *,
                group_score: str = "top2"):
    """r [S, hidden] (normed) -> the held experts' part of the routed sum."""
    _, weights, experts = route(
        r, layers["router"][j], layers["router_bias"][j],
        top_k=int(m["num_experts_per_tok"]), groups=int(m["n_group"]),
        kept_groups=int(m["topk_group"]), group_score=group_score,
        renormalise=bool(m["norm_topk_prob"]),
        scaling=float(m["routed_scaling_factor"]))
    first = m["expert_share"]["first"]
    y = jnp.zeros_like(r)
    for e in range(layers["we_gate"].shape[1]):
        y = y + _one_expert(r, layers, j, e, first + e, weights, experts)
    return y


def moe_ffn(x, layers, j: int, m: Dict[str, Any], *,
            group_score: str = "top2"):
    """x [S, hidden] (before the feed-forward's norm) -> the feed-forward's
    output, without the residual."""
    with jax.default_matmul_precision("highest"):
        r = rms_norm(x, _f32(layers["mlp_norm"][j]), float(m["rms_norm_eps"]))
        y = routed_part(r, layers, j, m, group_score=group_score)
        if m["num_shared_experts"]:
            y = y + shared_part(r, layers, j)
    return y


# ------------------------------------------------------------------- model
def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any], *,
                  delta: bool = True, beta_one: bool = False,
                  lower_bound: Optional[float] = None,
                  gate_first: bool = False, drop_state_every: int = 0,
                  head_gate: bool = True, group_score: str = "top2"):
    """tokens [S] int -> final hidden states [S, hidden], after the norm.
    The keyword arguments are the module docstring's controls."""
    eps = float(m["rms_norm_eps"])
    bound = float(m["kda_lower_bound"] if lower_bound is None
                  else lower_bound)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        positions = jnp.arange(tokens.shape[0])
        for kind, layers, j in layer_leaves(params, m):
            if kind.startswith("kda"):
                x = kda_layer(x, layers, j, eps=eps,
                              heads=m["num_attention_heads"],
                              lower_bound=bound, delta=delta,
                              beta_one=beta_one, gate_first=gate_first,
                              drop_state_every=drop_state_every)
            else:
                x = latent_attention(x, layers, j, positions, m,
                                     head_gate=head_gate)
            if "router" in layers:
                x = x + moe_ffn(x, layers, j, m, group_score=group_score)
            else:
                x = dense_ffn(x, layers, j, eps=eps)
        return rms_norm(x, params["final_norm"], eps)


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
