"""Plain float32 reference of dots3-note-prev's language model
(``dots-studio/dots3-note-prev``, ``model_type: dots3_note``; the vision
and audio towers and the multi-token-prediction module are not in the
catalog's ``config`` and are not here): pre-norm blocks whose operator is
latent attention at one of two geometries and whose feed-forward is a dense
SwiGLU in the first ``first_k_dense_replace`` layers and, after them, one
shared expert beside routed experts under a sigmoid router with a
bias-corrected choice; a last RMSNorm and an untied head.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed. That tree keeps one stacked
pytree a kind of layer, ``params["layers"][kind]``, ``kind`` one of
``indexed_dense``, ``indexed_routed``, ``window_dense``, ``window_routed``
(``full_attention`` layers are ``indexed``, ``sliding_attention`` layers
``window``; a model of one kind keeps the stack under ``params["layers"]``
itself); layer ``l`` is entry ``j`` of its kind's stack, ``j`` the number
of earlier layers of that kind. Leaves, each with a leading dim over its
kind's layers: ``attn_norm``, ``mlp_norm`` ``[hidden]``; ``wq_a [hidden,
q_rank]``, ``q_a_norm``, ``wq_b [q_rank, heads, nope + rope]``, ``wkv_a
[hidden, kv_rank + rope]``, ``kv_a_norm``, ``wkv_b [kv_rank, heads, nope +
v]``, ``wo [heads, v, hidden]``, ``w_head_gate [hidden, heads]``; of an
indexed layer also ``wi_q [q_rank, index_heads, index_head_dim]``, ``wi_k
[hidden, index_head_dim]``, ``wi_k_norm``, ``wi_k_bias``, ``wi_w [hidden,
index_heads]``; ``w_gate``, ``w_up``, ``w_down``; ``router [hidden, E]``,
``router_bias [E]``, ``we_gate``, ``we_up`` ``[held, hidden, width]``,
``we_down``; ``ws_gate``, ``ws_up``, ``ws_down``. Beside them ``embed``,
``final_norm`` and ``lm_head [hidden, vocab]``.

The equations (ISSUE 43; there is no network to read the source's
modeling code, so each mechanism is written in the published form of the
release that introduced it). ``u`` is the layer's RMS-normed input; no
projection has a bias; block: ``x += Attn(u); x += FFN(RMSNorm(x))``.

- Latent attention, both geometries (DeepSeek-V2's MLA): ``c_q =
  RMSNorm(u W_qa)``; ``[q_nope | q_pe] = c_q W_qb`` a head; ``[c_kv | k_pe]
  = u W_kva``, ``c_kv = RMSNorm(c_kv)``; ``[k_nope | v] = c_kv W_kvb`` a
  head; rope on ``q_pe`` and on ``k_pe``, which is one row a position and
  every head's; scores ``(q_nope k_nope^T + q_pe k_pe^T) (nope +
  rope)^-0.5``; softmax over the keys the layer allows. Full layers: 128
  heads, ranks 1024 and 512, 128 + 64 against values of 128, ``rope_theta``
  8e7. Sliding layers: the ``swa_`` keys: 64 heads, ranks 1024 and 1024,
  192 + 64 against 128, ``swa_rope_theta`` 5e4.
- ``apply_mla_qkv_lora_rescale`` (ASSUMED, LongCat-Flash's convention for
  the same correction): ``c_q`` times ``(hidden / q_rank)^0.5`` and
  ``c_kv`` times ``(hidden / kv_rank)^0.5`` after their norms; the indexer
  reads the rescaled ``c_q``.
- Rotary: the 64 rotary dims of ``q_pe`` and ``k_pe`` are stored
  interleaved, ``(x0, y0, x1, y1, ...)``, and un-interleaved before
  rotate-half, as ``deepseek_v2`` does it; ``rope_scaling`` null.
- Head-wise gate (``attention_gate_type``, ``swa_attention_gate_type``
  ``headwise``): ``g = sigmoid(u W_g)``, one scalar a head and position;
  head ``n``'s attention output times ``g_n`` before ``W_o`` (ASSUMED: the
  gate reads the operator's own input).
- Window (``sliding_window_size`` 513): query ``t`` sees keys ``s`` with
  ``0 <= t - s <= 512`` (ASSUMED: the count includes the query's own
  position).
- Indexer and selection, full layers (DeepSeek-V3.2's lightning indexer;
  ``index_n_heads`` 64, ``index_head_dim`` 128, ``index_topk`` 2048):
  ``q_i = c_q W_iq``, 64 heads of 128; ``k_i = LayerNorm(u W_ik)``, ONE row
  of 128 a position, weight and bias, epsilon ``rms_norm_eps``; rope at the
  layer's ``rope_theta`` over the first 64 dims of both, rotate-half over
  those dims as they lie (ASSUMED: the rotary split, its order and the
  LayerNorm are that release's); ``w = u W_iw``, 64 a position; ``I[t, s] =
  sum_j w[t, j] ReLU(q_i[t, j] . k_i[s])`` for ``s <= t``. Query ``t``
  attends the 2048 keys of largest ``I[t, .]``, all of them while ``t <
  2048``. The constant scales on ``w``, the Hadamard rotation and the fp8
  of that release's kernels change no choice and are left out.
- Routed FFN (DeepSeek-V3's ``noaux_tc`` without groups): ``scores =
  sigmoid(r W_g)`` in float32 over all ``E``; the ``num_experts_per_tok``
  experts of largest ``scores + bias`` are chosen; their weights are the
  scores without the bias, renormalised over their sum plus 1e-20, times
  ``routed_scaling_factor``; ``y = shared(r) + sum_k w_k E_k(r)``.

The share: as ``reference/deepseek_v2.py``. The tree holds ``held`` of the
router's ``E`` experts, from ``m["expert_share"]["first"]`` on; the router
scores and chooses over all ``E``; the sum runs over the held experts.
``routed_part`` and ``shared_part`` give the two summands by themselves.

Departures from the description, none of which changes a result: each held
expert is computed at every position and multiplied by the position's
weight for it (0 where it was not chosen); no cache: one sequence, all its
positions at once; attention as a masked softmax, the window and the
chosen keys boolean masks, in blocks of queries and of heads so that 5120
positions' scores fit beside the served weights; the index scores in the
same blocks of queries; ``top_k`` breaks ties towards the lower index.

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers and experts run in
Python loops, one matrix cast to float32 at a time.

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import rms_norm
from benchmark.reference.deepseek_v2 import (
    _f32, _one_expert, dense_ffn, pairs_apart, shared_part)

QUERY_BLOCK = 256
HEAD_BLOCK = 16
# the program's name for each published operator
OPERATORS = {"full_attention": "indexed", "sliding_attention": "window"}


def layer_kinds(m: Dict[str, Any]) -> List[str]:
    dense = m["first_k_dense_replace"]
    return [OPERATORS[t] + ("_dense" if l < dense else "_routed")
            for l, t in enumerate(m["layer_types"])]


def layer_leaves(params: Dict[str, Any], m: Dict[str, Any]
                 ) -> List[Tuple[str, Dict[str, Any], int]]:
    """For each layer of the model, in order: (its kind, its kind's stacked
    leaves, its index in them)."""
    kinds = layer_kinds(m)
    stacks = (params["layers"] if len(set(kinds)) > 1
              else {kinds[0]: params["layers"]})
    return [(kind, stacks[kind], kinds[:l].count(kind))
            for l, kind in enumerate(kinds)]


def geometry(m: Dict[str, Any], operator: str) -> Dict[str, Any]:
    """A latent operator's widths: the ``swa_`` keys for a window layer."""
    pre = "swa_" if operator == "window" else ""
    return {"heads": m[pre + "num_attention_heads"],
            "q_rank": m[pre + "q_lora_rank"],
            "kv_rank": m[pre + "kv_lora_rank"],
            "nope": m[pre + "qk_nope_head_dim"],
            "rope": m[pre + "qk_rope_head_dim"],
            "theta": float(m[pre + "rope_theta"])}


# ------------------------------------------------------------------ rotary
def _cos_sin(positions, dim: int, theta: float):
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rotate_half(x, cos, sin):
    """x [S, ..., R], rotate-half by ``cos``, ``sin`` ``[S, R / 2]``."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * _f32(w) + _f32(b))


# --------------------------------------------------------------- attention
def chosen_keys(scores, top_k: int):
    """scores [Q, S] float32 with -inf where a query may not look ->
    [Q, S] bool: each query's ``top_k`` keys of largest score, among those
    it may look at."""
    _, idx = jax.lax.top_k(scores, min(top_k, scores.shape[1]))
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return picked & (scores > -jnp.inf)


@partial(jax.jit, static_argnames=("top_k",))
def _index_block(q_i, k_i, w, start, *, top_k: int):
    """The choice of the queries ``start`` on: q_i [Q, J, D], k_i [S, D],
    w [Q, J] -> [Q, S] bool."""
    products = jnp.einsum("qjd,sd->jqs", q_i, k_i)
    scores = jnp.einsum("jqs,qj->qs", jax.nn.relu(products), w)
    q_pos = start + jnp.arange(q_i.shape[0])
    causal = q_pos[:, None] >= jnp.arange(k_i.shape[0])[None, :]
    return chosen_keys(jnp.where(causal, scores, -jnp.inf), top_k)


@partial(jax.jit, static_argnames=("scale", "window"))
def _attend_block(q, k, v, allowed, start, *, scale: float, window):
    """q [Q, H, Dk] (queries ``start`` on), k [S, H, Dk], v [S, H, Dv],
    allowed [Q, S] bool or None -> [Q, H, Dv]: a masked softmax."""
    q_pos = start + jnp.arange(q.shape[0])
    kv_pos = jnp.arange(k.shape[0])
    seen = q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        seen = seen & (q_pos[:, None] - kv_pos[None, :] < window)
    if allowed is not None:
        seen = seen & allowed
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def _masked_attention(q, k, v, allowed, *, scale: float, window):
    """In blocks of queries and of heads. ``allowed``: None, or a function
    of a query block's start and length that gives its [Q, S] bool."""
    S, H = q.shape[:2]
    rows = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        ok = None if allowed is None else allowed(start, qb.shape[0])
        rows.append(jnp.concatenate([
            _attend_block(qb[:, h:h + HEAD_BLOCK], k[:, h:h + HEAD_BLOCK],
                          v[:, h:h + HEAD_BLOCK], ok, start, scale=scale,
                          window=window)
            for h in range(0, H, HEAD_BLOCK)], axis=1))
    return jnp.concatenate(rows, axis=0)


@partial(jax.jit, static_argnames=("eps", "nope", "kvr", "rescale", "hidden"))
def _project(x, layers, j, cos, sin, *, eps, nope, kvr, rescale, hidden):
    """x [S, hidden] -> (u, c_q, q [S, H, nope + rope], k, v)."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    u = rms_norm(x, at("attn_norm"), eps)
    c_q = rms_norm(u @ at("wq_a"), at("q_a_norm"), eps)
    ckv = u @ at("wkv_a")
    c_kv, k_pe = rms_norm(ckv[:, :kvr], at("kv_a_norm"), eps), ckv[:, kvr:]
    if rescale:
        c_q = c_q * (hidden / c_q.shape[-1]) ** 0.5
        c_kv = c_kv * (hidden / kvr) ** 0.5
    q = jnp.einsum("sr,rnd->snd", c_q, at("wq_b"))
    kv = jnp.einsum("sr,rnd->snd", c_kv, at("wkv_b"))
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _rotate_half(pairs_apart(q[..., nope:]), cos, sin)
    k_pe = _rotate_half(pairs_apart(k_pe), cos, sin)
    q = jnp.concatenate([q[..., :nope], q_pe], axis=-1)
    # the one rotary key of a position, given to every head
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, None, :],
                                  k_nope.shape[:2] + k_pe.shape[1:])], -1)
    return u, c_q, q, k, v


@partial(jax.jit, static_argnames=("eps", "rope"))
def _indexer(u, c_q, layers, j, cos, sin, *, eps, rope):
    """-> (q_i [S, J, D], k_i [S, D], w [S, J]), rotated."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    q_i = jnp.einsum("sr,rjd->sjd", c_q, at("wi_q"))
    k_i = layer_norm(u @ at("wi_k"), layers["wi_k_norm"][j],
                     layers["wi_k_bias"][j], eps)
    q_i = jnp.concatenate([_rotate_half(q_i[..., :rope], cos, sin),
                           q_i[..., rope:]], axis=-1)
    k_i = jnp.concatenate([_rotate_half(k_i[..., :rope], cos, sin),
                           k_i[..., rope:]], axis=-1)
    return q_i, k_i, u @ at("wi_w")


@jax.jit
def _gate_and_out(x, u, a, layers, j, gated):
    g = jax.nn.sigmoid(u @ _f32(layers["w_head_gate"][j]))
    a = jnp.where(gated, a * g[..., None], a)
    return x + jnp.einsum("snd,ndh->sh", a, _f32(layers["wo"][j]))


def latent_attention(x, kind: str, layers, j: int, positions,
                     m: Dict[str, Any], *, selection: bool = True,
                     window: bool = True, gate: bool = True,
                     rescale: bool = True):
    """x [S, hidden] -> x + the layer's operator on its norm. The four
    switches are controls: the indexer's choice ignored (every causal key
    attended), the window ignored, the gate left out, the rescale left
    out; none is the model."""
    operator = kind.split("_")[0]
    g = geometry(m, operator)
    cos, sin = _cos_sin(positions, g["rope"], g["theta"])
    eps = float(m["rms_norm_eps"])
    u, c_q, q, k, v = _project(
        x, layers, j, cos, sin, eps=eps, nope=g["nope"], kvr=g["kv_rank"],
        rescale=bool(rescale and m["apply_mla_qkv_lora_rescale"]),
        hidden=m["hidden_size"])
    allowed = None
    if operator == "indexed" and selection:
        q_i, k_i, w = _indexer(u, c_q, layers, j, cos, sin, eps=eps,
                               rope=g["rope"])

        def allowed(start, n):
            return _index_block(q_i[start:start + n], k_i,
                                w[start:start + n], start,
                                top_k=int(m["index_topk"]))
    a = _masked_attention(
        q, k, v, allowed, scale=float((g["nope"] + g["rope"]) ** -0.5),
        window=int(m["sliding_window_size"])
        if operator == "window" and window else None)
    return _gate_and_out(x, u, a, layers, j, bool(gate))


# ------------------------------------------------------------ feed-forward
@partial(jax.jit, static_argnames=("top_k", "renormalise", "scaling"))
def route(r, router, bias, *, top_k, renormalise, scaling):
    """r [S, hidden] (normed) -> (scores [S, E], weights [S, k], experts
    [S, k]) over all ``E`` of the router's columns: the choice on the
    sigmoid scores plus the bias, the weights the scores without it."""
    scores = jax.nn.sigmoid(r @ _f32(router))
    _, experts = jax.lax.top_k(scores + _f32(bias), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if renormalise:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return scores, weights * scaling, experts


def routed_part(r, layers, j: int, m: Dict[str, Any]):
    """r [S, hidden] (normed) -> the held experts' part of the routed sum."""
    _, weights, experts = route(
        r, layers["router"][j], layers["router_bias"][j],
        top_k=int(m["num_experts_per_tok"]),
        renormalise=bool(m["norm_topk_prob"]),
        scaling=float(m["routed_scaling_factor"]))
    first = m["expert_share"]["first"]
    y = jnp.zeros_like(r)
    for e in range(layers["we_gate"].shape[1]):
        y = y + _one_expert(r, layers, j, e, first + e, weights, experts)
    return y


def moe_ffn(x, layers, j: int, m: Dict[str, Any]):
    """x [S, hidden] (before the feed-forward's norm) -> the feed-forward's
    output, without the residual."""
    with jax.default_matmul_precision("highest"):
        r = rms_norm(x, _f32(layers["mlp_norm"][j]), float(m["rms_norm_eps"]))
        y = routed_part(r, layers, j, m)
        if m["n_shared_experts"]:
            y = y + shared_part(r, layers, j)
    return y


# ------------------------------------------------------------------- model
def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any],
                  **controls):
    """tokens [S] int -> final hidden states [S, hidden], after the norm.
    ``controls``: ``latent_attention``'s switches."""
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        positions = jnp.arange(tokens.shape[0])
        for kind, layers, j in layer_leaves(params, m):
            x = latent_attention(x, kind, layers, j, positions, m, **controls)
            if "router" in layers:
                x = x + moe_ffn(x, layers, j, m)
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
    """[vocab] float32: the logits after the last token of the prompt."""
    x = hidden_states(params, tokens, m, **controls)
    with jax.default_matmul_precision("highest"):
        return x[-1] @ _f32(params["lm_head"])


def loss(params: Dict[str, Any], inputs, targets, m: Dict[str, Any]):
    """Mean next-token cross-entropy of one sequence (inputs, targets [S])."""
    lg = logits(params, inputs, m)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
