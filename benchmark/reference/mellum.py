"""Plain float32 reference of Mellum 2 (``JetBrains/Mellum2-12B-A2.5B-
Instruct``, ``model_type: mellum``): pre-norm blocks of grouped-query
attention, three layers of four under a sliding window and plain rope, the
fourth over every causal key under YaRN, then routed experts with no shared
expert; untied head.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed (one stacked pytree a kind
under ``params["layers"]``, ``sliding_routed`` and ``attention_routed``, a
model of one kind the stack itself: ``wq [L, hidden, heads, head_dim]``,
``wk``/``wv`` at the key/value heads, ``wo [L, heads, head_dim, hidden]``,
``q_norm``/``k_norm [L, head_dim]``, ``router [L, hidden, E]``,
``we_gate``/``we_up [L, E, hidden, width]``, ``we_down [L, E, width,
hidden]``, the two block norms; ``embed``, ``final_norm``, ``lm_head
[hidden, vocab]``). The RMSNorm is the dense reference's; the rotary
embedding, YaRN and the masked attention are written out here from the
configuration's numbers and import nothing of the program's.

With ``n1``, ``n2`` the block's two RMSNorms and ``hn`` an RMSNorm over
each head's ``head_dim`` (one weight a layer for the queries, one for the
keys), layer ``l`` of type ``layer_types[l]``::

    q, k, v = split(Wq n1(x)), split(Wk n1(x)), split(Wv n1(x))
    q, k = rot_l(hn_q(q)), rot_l(hn_k(k))
    x += Wo . softmax(q k^T / sqrt(head_dim) over the keys seen_l) v
    p = softmax_f32(n2(x) W_r);  (w, e) = top_k(p)        # k = 8, E = 64
    w = w / sum(w)                                         # norm_topk_prob
    x += sum_k w_k . W_down[e_k](silu(W_gate[e_k] n2(x)) * W_up[e_k] n2(x))

``sliding_attention``: ``seen`` is keys ``t - sliding_window + 1 .. t``
(the query's own among them: ``q - k < sliding_window``, HuggingFace's
sliding-window overlay) and ``rot`` is rope at ``rope_theta`` (rotate-half).
``full_attention``: ``seen`` is every causal key and ``rot`` is YaRN: the
frequencies ``theta ** (-2 i / head_dim)`` blended with themselves over
``factor`` by a linear ramp between the dims that turn ``beta_fast`` and
``beta_slow`` times over ``original_max_position_embeddings`` positions,
and cos and sin times ``attention_factor``; the softmax scale stays
``head_dim ** -0.5``. Then the final RMSNorm and the head.

Departures from the published description, and what is assumed (the
configuration file's ``assumed`` has each in full):

- The RMSNorm over each head of the queries and keys before rope is
  modeling code and no key of ``config.json``: assumed, as the public
  family of sparse models with this file's key names has it.
- ``described_as``'s multi-token-prediction head is not in ``config`` and
  is left out, in the program too.
- HF gathers, for each expert, the positions that chose it. Here each
  expert is computed at every position and its output multiplied by the
  position's weight for it, which is exactly 0 where the router did not
  choose it: static shapes, the same sums.
- No biases (``attention_bias`` false), no dropout: none is written.
- One sequence at a time, attention in blocks of ``QUERY_BLOCK`` queries
  and ``HEAD_BLOCK`` heads so that the float32 scores of 8448 positions fit
  beside 7.59 GB of served weights; the window and the causal mask are
  booleans over ALL the keys (no key block is skipped: that is the
  kernel's trick, not the mathematics').

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers and experts run in
Python loops and one expert's three matrices are cast to float32 at a time
(25 MB at the published widths).

``logits`` takes switches that plant the family's own faults, for the
controls of the cell's check (``tools/mellum2_probe.py``); none is the
model: ``window=False`` (the sliding layers see every causal key),
``window_keys`` (another window than ``sliding_window``: one key short),
``yarn=False`` (the full layers under plain rope at amplitude 1),
``renormalise=False`` (``norm_topk_prob`` off).

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import rms_norm

QUERY_BLOCK = 256
HEAD_BLOCK = 8
# the program's name for each published operator
OPERATORS = {"full_attention": "attention", "sliding_attention": "sliding"}


def _f32(a):
    return a.astype(jnp.float32)


def layer_kinds(m: Dict[str, Any]) -> List[str]:
    return [OPERATORS[t] + ("_dense" if f == "dense" else "_routed")
            for t, f in zip(m["layer_types"], m["mlp_layer_types"])]


def layer_leaves(params: Dict[str, Any], m: Dict[str, Any]
                 ) -> List[Tuple[str, Dict[str, Any], int]]:
    """For each layer of the model, in order: (its kind, its kind's stacked
    leaves, its index in them)."""
    kinds = layer_kinds(m)
    stacks = (params["layers"] if len(set(kinds)) > 1
              else {kinds[0]: params["layers"]})
    return [(kind, stacks[kind], kinds[:l].count(kind))
            for l, kind in enumerate(kinds)]


# ------------------------------------------------------------------ rotary
def inverse_frequencies(rope: Dict[str, Any], dim: int) -> jnp.ndarray:
    """The ``dim / 2`` rotary frequencies of one entry of
    ``rope_parameters``, float32. ``default``: ``theta ** (-2 i / dim)``.
    ``yarn`` (Peng et al. 2023, as HuggingFace's
    ``_compute_yarn_parameters`` has it): dim ``i`` turns
    ``original_max_position_embeddings / (2 pi theta ** (2 i / dim))`` times
    over the original context; the dims that turn more than ``beta_fast``
    times keep their frequency, those that turn fewer than ``beta_slow``
    times get it over ``factor``, and a linear ramp over the dims between
    (the two correction dims, floored and ceiled) blends the two."""
    theta = float(rope["rope_theta"])
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    own = 1.0 / theta ** exponent
    if rope["rope_type"] == "default":
        return own
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def correction_dim(turns: float) -> float:
        return (dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return own / factor * ramp + own * (1.0 - ramp)


def amplitude(rope: Dict[str, Any]) -> float:
    """What cos and sin are multiplied by: ``attention_factor`` under
    YaRN, as the configuration gives it."""
    return float(rope.get("attention_factor", 1.0))


def rotate(x, positions, inv_freq, scale: float):
    """x [S, heads, head_dim]: pairs (d, d + head_dim / 2) rotated by
    ``position * inv_freq[d]`` (rotate-half), cos and sin times ``scale``."""
    half = x.shape[-1] // 2
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# --------------------------------------------------------------- attention
@partial(jax.jit, static_argnames=("window",))
def _attend_block(q, k, v, start, *, window: Optional[int]):
    """q [Q, H, D] (queries ``start`` on), k, v [S, H, D] -> [Q, H, D]: a
    softmax over the keys the booleans leave."""
    q_pos = start + jnp.arange(q.shape[0])
    kv_pos = jnp.arange(k.shape[0])
    seen = q_pos[:, None] >= kv_pos[None, :]
    if window is not None:
        seen = seen & (q_pos[:, None] - kv_pos[None, :] < window)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * (q.shape[-1] ** -0.5)
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def masked_attention(q, k, v, *, window: Optional[int]):
    """q [S, H, D], k, v [S, KVH, D] -> [S, H, D]; query head ``h`` reads
    key/value head ``h // (H / KVH)``. In blocks of queries and heads."""
    S, H = q.shape[:2]
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
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
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    h = rms_norm(x, at("attn_norm"), eps)
    q = jnp.einsum("sh,hnd->snd", h, at("wq"))
    k = jnp.einsum("sh,hnd->snd", h, at("wk"))
    v = jnp.einsum("sh,hnd->snd", h, at("wv"))
    # assumed: an RMSNorm over each head, one weight [head_dim] a layer
    q = rms_norm(q, at("q_norm"), eps)
    k = rms_norm(k, at("k_norm"), eps)
    return (rotate(q, positions, inv_freq, scale),
            rotate(k, positions, inv_freq, scale), v)


@jax.jit
def _out(x, a, layers, j):
    return x + jnp.einsum("snd,ndh->sh", a, _f32(layers["wo"][j]))


def attention(x, kind: str, layers, j: int, positions, m: Dict[str, Any],
              *, window: bool = True, window_keys: Optional[int] = None,
              yarn: bool = True):
    """x [S, hidden] -> x + the layer's attention on its norm. The three
    switches are controls (module docstring); none is the model."""
    sliding = kind.split("_")[0] == "sliding"
    rope = m["rope_parameters"][
        "sliding_attention" if sliding else "full_attention"]
    if not sliding and not yarn:  # plain rope at the same base
        rope = {"rope_type": "default", "rope_theta": rope["rope_theta"]}
    q, k, v = _project(
        x, layers, j, positions, inverse_frequencies(rope, m["head_dim"]),
        eps=float(m["rms_norm_eps"]), scale=amplitude(rope))
    keys = None
    if sliding and window:
        keys = int(m["sliding_window"] if window_keys is None
                   else window_keys)
    return _out(x, masked_attention(q, k, v, window=keys), layers, j)


# ------------------------------------------------------------ feed-forward
@partial(jax.jit, static_argnames=("eps", "top_k", "renormalise"))
def _route(x, layers, j, *, eps, top_k, renormalise):
    """(n2(x), weights [S, k], experts [S, k])."""
    r = rms_norm(x, _f32(layers["mlp_norm"][j]), eps)
    probs = jax.nn.softmax(r @ _f32(layers["router"][j]), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return r, weights, experts


@jax.jit
def _one_expert(r, layers, j, e, weights, experts):
    """Expert ``e`` of layer ``j`` at every position, times each
    position's weight for it (0 where it was not chosen)."""
    gate = r @ _f32(layers["we_gate"][j, e])
    up = r @ _f32(layers["we_up"][j, e])
    out = (jax.nn.silu(gate) * up) @ _f32(layers["we_down"][j, e])
    w = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
    return w[:, None] * out


def expert_ffn(x, layers, j: int, m: Dict[str, Any], *,
               renormalise: bool = True):
    """x [S, hidden] (before the second norm) -> the routed feed-forward's
    output, without the residual. ``renormalise=False`` is a control."""
    r, weights, experts = _route(
        x, layers, j, eps=float(m["rms_norm_eps"]),
        top_k=int(m["num_experts_per_tok"]),
        renormalise=bool(m["norm_topk_prob"] and renormalise))
    y = jnp.zeros_like(r)
    for e in range(m["num_experts"]):
        y = y + _one_expert(r, layers, j, e, weights, experts)
    return y


@partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(x, layers, j, *, eps):
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    r = rms_norm(x, at("mlp_norm"), eps)
    return (jax.nn.silu(r @ at("w_gate")) * (r @ at("w_up"))) @ at("w_down")


# ------------------------------------------------------------------- model
def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any], *,
                  renormalise: bool = True, **controls):
    """tokens [S] int -> final hidden states [S, hidden], after the norm.
    ``controls``: ``attention``'s switches and ``expert_ffn``'s."""
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        positions = jnp.arange(tokens.shape[0])
        for kind, layers, j in layer_leaves(params, m):
            x = attention(x, kind, layers, j, positions, m, **controls)
            if "router" in layers:
                x = x + expert_ffn(x, layers, j, m, renormalise=renormalise)
            else:
                x = x + _dense_ffn(x, layers, j,
                                   eps=float(m["rms_norm_eps"]))
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
