"""Plain float32 reference of Keye-VL-2.0-30B-A3B's language model
(``Kwai-Keye/Keye-VL-2.0-30B-A3B``, ``model_type: KeyeVL2``; the vision
tower is not in the catalog's ``config`` and is not here): pre-norm blocks,
every one alike, of grouped-query attention (32 query heads on 4 key/value
heads of 128) whose query attends the 2048 keys that a learned indexer
chooses, under multi-axis rope, then routed experts under a softmax router
with no shared expert; a last RMSNorm and an untied head.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed (a model of one kind keeps
its layers as one stacked pytree under ``params["layers"]``, each leaf with
a leading dim over the layers: ``attn_norm``, ``mlp_norm`` ``[hidden]``;
``wq [hidden, heads, head_dim]``, ``wk``/``wv`` at the key/value heads,
``wo [heads, head_dim, hidden]``, ``q_norm``/``k_norm`` ``[head_dim]``;
the indexer's ``wi_q [hidden, index heads, index head dim]``, ``wi_k
[hidden, index head dim]``, ``wi_k_norm``, ``wi_k_bias``, ``wi_w [hidden,
index heads]``; ``router [hidden, E]``, ``we_gate``/``we_up [E, hidden,
width]``, ``we_down [E, width, hidden]``; beside them ``embed``,
``final_norm``, ``lm_head [hidden, vocab]``). The RMSNorm is the dense
reference's, the LayerNorm ``reference/dots3_note.py``'s and the sum over
the experts ``reference/laguna.py``'s; everything else is written out here
from the configuration's numbers.

The equations (ISSUE 62; there is no network to read the source's modeling
code, so what no key says is taken from ``Qwen3MoeConfig``'s model, whose
keys the language model's are to the letter, and the indexer from
DeepSeek-V3.2's release: the configuration file's ``assumed`` has each).
With ``u = n1(x)`` and ``r = n2(x)`` the block's two RMSNorms (weight, eps
``rms_norm_eps``)::

    q, k, v = split_32(u Wq), split_4(u Wk), split_4(u Wv)     # no bias
    q, k = rot(n_q(q)), rot(n_k(k))        # an RMSNorm a head, then rope
    q_i = rot_t(split_16(u W_iq));  k_i = rot_t(LayerNorm(u W_ik))
    w = u W_iw                                                  # [S, 16]
    I[t, s] = sum_j w[t, j] ReLU(q_i[t, j] . k_i[s])            # s <= t
    keep[t] = the 2048 keys of largest I[t, .]   (all while t < 2048)
    a = softmax(q k^T / sqrt(128) over keep) v
        # query head n reads key/value head n // 8
    x += Wo a
    s = softmax_f32(r W_r);  (s_k, e_k) = top_8(s);  w_k = s_k / sum(s_k)
    x += sum_k w_k SwiGLU_{e_k}(r)

Positions. A token has three, ``(p_t, p_h, p_w)``; a text token's are all
its index. ``rot``: frequencies ``f_i = rope_theta ** (-2 i / 128)``, ``i``
in 0..63, rotate-half (dim ``i`` pairs with dim ``i + 64``); pair ``i``
turns by ``p_t f_i`` for ``i`` in 0..15, ``p_h f_i`` in 16..39, ``p_w
f_i`` in 40..63 (``mrope_section [16, 24, 24]``, as ``Qwen2-VL``'s
``apply_multimodal_rotary_pos_emb`` deals them). ``rot_t``: the indexer's
whole 64 dims (32 pairs, ``rope_theta ** (-2 i / 64)``) by ``p_t``.

Departures from the description, none of which changes a result: each
expert is computed at every position and its output multiplied by the
position's weight for it, exactly 0 where the router did not choose it; no
cache: one sequence, all its positions at once; attention as a masked
softmax over ALL the keys in blocks of ``QUERY_BLOCK`` queries and
``HEAD_BLOCK`` heads, the causal mask and the choice booleans, the index
scores in the same blocks of queries, so that 8192 positions' scores fit
beside 8.75 GB of served weights; a sequence is padded on the right to a
multiple of ``PAD_TO`` and the result cut back (fewer shapes to compile).
The choice: a query's ``topk``-th largest score among its causal keys is
found (``lax.top_k``) and every causal key at or above it stays, so keys
that TIE with the ``topk``-th all stay, as the program's
``_chosen_keys`` keeps them (``lax.top_k``'s own indices would keep the
lowest among them).

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``. Layers run in a Python loop,
the experts of a layer in a ``fori_loop`` that casts one expert's three
matrices to float32 at a time.

``logits`` takes switches that plant the family's own faults, for the
controls of the cell's check (``tools/keye_probe.py``); none is the model:
``selection=False`` (the choice ignored: every causal key), ``topk``
(another count), ``index_relu=False`` (the indexer's ReLU left out),
``index_weights=False`` (its heads' weights all 1), ``index_key_norm=False``
(its key without the LayerNorm), ``index_rope=False`` (its rope left out),
``head_norms=False`` (no RMSNorm over the heads of q and k), ``theta``
(another base), ``group`` (query head ``n`` reads key/value head ``n //
group % 4``), ``renormalise=False`` (the chosen experts' weights as the
softmax gave them), ``experts_per_token`` (another count), and, for the
CPU tests alone, ``swap_streams`` (two of the three position streams
changed places).

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import rms_norm
# what the families share to the letter: the index key's LayerNorm (weight
# and bias) of the other indexed model, and the sum over ALL experts of a
# layer, each at every position times the position's weight for it
from benchmark.reference.dots3_note import _f32, layer_norm
from benchmark.reference.laguna import _routed

QUERY_BLOCK = 256
HEAD_BLOCK = 8
# a sequence is padded on the right to a multiple of this (everything here
# is causal, so what follows a position does not reach it) and the result
# cut back: the cell's prompts then meet five lengths and not a dozen
PAD_TO = 1024


# ------------------------------------------------------------------ rotary
def _rotate_half(x, angles):
    """x [S, ..., D], pairs ``(d, d + D / 2)`` turned by ``angles [S, D /
    2]``."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(angles).reshape(shape), jnp.sin(angles).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def head_angles(positions, dim: int, theta: float, sections):
    """positions [3, S] -> [S, dim / 2]: pair ``i`` by the stream whose
    section it falls in, at ``theta ** (-2 i / dim)``."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    parts, first = [], 0
    for stream, n in enumerate(sections):
        parts.append(_f32(positions[stream])[:, None]
                     * inv_freq[None, first:first + n])
        first += n
    return jnp.concatenate(parts, axis=-1)


# --------------------------------------------------------------- attention
@partial(jax.jit, static_argnames=("top_k", "relu"))
def _index_block(q_i, k_i, w, start, *, top_k: int, relu: bool):
    """The choice of the queries ``start`` on: q_i [Q, J, D], k_i [S, D],
    w [Q, J] -> [Q, S] bool: the causal keys whose score is at or above the
    query's ``top_k``-th largest among them (ties stay)."""
    products = jnp.einsum("qjd,sd->jqs", q_i, k_i)
    if relu:
        products = jax.nn.relu(products)
    scores = jnp.einsum("jqs,qj->qs", products, w)
    q_pos = start + jnp.arange(q_i.shape[0])
    causal = q_pos[:, None] >= jnp.arange(k_i.shape[0])[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    kth = jax.lax.top_k(scores, min(top_k, scores.shape[1]))[0][:, -1:]
    return causal & (scores >= kth)


@jax.jit
def _attend_block(q, k, v, allowed, start):
    """q [Q, H, D] (queries ``start`` on), k, v [S, H, D], allowed [Q, S]
    bool or None -> [Q, H, D]: a masked softmax over all the keys."""
    q_pos = start + jnp.arange(q.shape[0])
    seen = q_pos[:, None] >= jnp.arange(k.shape[0])[None, :]
    if allowed is not None:
        seen = seen & allowed
    scores = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)


def masked_attention(q, k, v, allowed, *, group: Optional[int] = None):
    """q [S, H, D], k, v [S, KVH, D] -> [S, H, D]; query head ``n`` reads
    key/value head ``n // (H / KVH)`` (with ``group``, a control, ``n //
    group % KVH``). ``allowed``: None, or a function of a query block's
    start and length that gives its [Q, S] bool. In blocks of queries and
    heads."""
    S, H = q.shape[:2]
    kvh = k.shape[1]
    reads = (jnp.arange(H) // (H // kvh) if group is None
             else jnp.arange(H) // group % kvh)
    k, v = jnp.take(k, reads, axis=1), jnp.take(v, reads, axis=1)
    rows = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        ok = None if allowed is None else allowed(start, qb.shape[0])
        rows.append(jnp.concatenate([
            _attend_block(qb[:, h:h + HEAD_BLOCK], k[:, h:h + HEAD_BLOCK],
                          v[:, h:h + HEAD_BLOCK], ok, start)
            for h in range(0, H, HEAD_BLOCK)], axis=1))
    return jnp.concatenate(rows, axis=0)


@partial(jax.jit, static_argnames=("eps", "head_norms"))
def _project(x, layers, j, angles, *, eps, head_norms):
    """x [S, hidden] -> (u, q, k rotated, v)."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    u = rms_norm(x, at("attn_norm"), eps)
    q = jnp.einsum("sh,hnd->snd", u, at("wq"))
    k = jnp.einsum("sh,hnd->snd", u, at("wk"))
    v = jnp.einsum("sh,hnd->snd", u, at("wv"))
    if head_norms:
        q, k = rms_norm(q, at("q_norm"), eps), rms_norm(k, at("k_norm"), eps)
    return u, _rotate_half(q, angles), _rotate_half(k, angles), v


@partial(jax.jit, static_argnames=("eps", "key_norm", "rope", "weights"))
def _indexer(u, layers, j, angles, *, eps, key_norm, rope, weights):
    """-> (q_i [S, J, D], k_i [S, D], w [S, J])."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    q_i = jnp.einsum("sh,hjd->sjd", u, at("wi_q"))
    k_i = u @ at("wi_k")
    if key_norm:
        k_i = layer_norm(k_i, layers["wi_k_norm"][j],
                         layers["wi_k_bias"][j], eps)
    if rope:
        q_i, k_i = _rotate_half(q_i, angles), _rotate_half(k_i, angles)
    w = u @ at("wi_w")
    return q_i, k_i, w if weights else jnp.ones_like(w)


@jax.jit
def _out(x, a, layers, j):
    return x + jnp.einsum("snd,ndh->sh", a, _f32(layers["wo"][j]))


def attention(x, layers, j: int, positions, m: Dict[str, Any], *,
              selection: bool = True, topk: Optional[int] = None,
              index_relu: bool = True, index_weights: bool = True,
              index_key_norm: bool = True, index_rope: bool = True,
              head_norms: bool = True, theta: Optional[float] = None,
              group: Optional[int] = None):
    """x [S, hidden], positions [3, S] -> x + the layer's attention on its
    norm. The switches are controls (module docstring); none is the
    model."""
    eps, sa = float(m["rms_norm_eps"]), m["sa_config"]
    theta = float(m["rope_theta"] if theta is None else theta)
    u, q, k, v = _project(
        x, layers, j,
        head_angles(positions, m["head_dim"], theta,
                    m["rope_scaling"]["mrope_section"]),
        eps=eps, head_norms=bool(head_norms))
    allowed = None
    if selection:
        ihd = sa["indexer_head_dim"]
        # the indexer's whole head by the temporal stream alone
        q_i, k_i, w = _indexer(
            u, layers, j,
            head_angles(positions[:1], ihd, theta, (ihd // 2,)), eps=eps,
            key_norm=bool(index_key_norm), rope=bool(index_rope),
            weights=bool(index_weights))

        def allowed(start, n):
            return _index_block(
                q_i[start:start + n], k_i, w[start:start + n], start,
                top_k=int(sa["topk"] if topk is None else topk),
                relu=bool(index_relu))
    return _out(x, masked_attention(q, k, v, allowed, group=group), layers,
                j)


# ------------------------------------------------------------ feed-forward
@partial(jax.jit, static_argnames=("eps", "top_k", "renormalise"))
def _route(x, layers, j, *, eps, top_k, renormalise):
    """(n2(x), weights [S, k], experts [S, k]): the softmax of all experts'
    scores in float32, the ``top_k`` largest, renormalised to sum to 1."""
    r = rms_norm(x, _f32(layers["mlp_norm"][j]), eps)
    s = jax.nn.softmax(r @ _f32(layers["router"][j]), axis=-1)
    weights, experts = jax.lax.top_k(s, top_k)
    if renormalise:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return r, weights, experts


def expert_ffn(x, layers, j: int, m: Dict[str, Any], *,
               renormalise: bool = True,
               experts_per_token: Optional[int] = None):
    """x [S, hidden] (before the second norm) -> the feed-forward's output,
    without the residual. The two switches are controls."""
    r, weights, experts = _route(
        x, layers, j, eps=float(m["rms_norm_eps"]),
        top_k=int(m["num_experts_per_tok"] if experts_per_token is None
                  else experts_per_token),
        renormalise=bool(renormalise and m["norm_topk_prob"]))
    return _routed(r, layers, j, weights, experts)


# ------------------------------------------------------------------- model
FFN_CONTROLS = ("renormalise", "experts_per_token")


def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any], *,
                  positions=None, swap_streams: bool = False, **controls):
    """tokens [S] int -> final hidden states [S, hidden], after the norm.
    ``positions [3, S]``: a token's temporal, height and width positions
    (None: a text's, all three the token's index). ``controls``:
    ``attention``'s switches and ``expert_ffn``'s."""
    ffn = {k: controls.pop(k) for k in FFN_CONTROLS if k in controls}
    length = tokens.shape[0]
    tokens = jnp.pad(tokens, (0, -length % PAD_TO))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[0]),
                                     (3, tokens.shape[0]))
    else:  # the padding's positions are zeros: nothing reads them
        positions = jnp.pad(jnp.asarray(positions),
                            ((0, 0), (0, tokens.shape[0] - length)))
    if swap_streams:
        positions = positions[jnp.array([0, 2, 1])]
    layers = params["layers"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0))
        for j in range(m["num_hidden_layers"]):
            x = attention(x, layers, j, positions, m, **controls)
            x = x + expert_ffn(x, layers, j, m, **ffn)
        return rms_norm(x[:length], params["final_norm"],
                        float(m["rms_norm_eps"]))


def logits(params: Dict[str, Any], tokens, m: Dict[str, Any], **controls):
    """[S, vocab] float32."""
    x = hidden_states(params, tokens, m, **controls)
    with jax.default_matmul_precision("highest"):
        return x @ _f32(params["lm_head"])
