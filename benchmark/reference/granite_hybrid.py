"""Plain float32 reference of Granite 4.0-H's dense hybrid decoder
(``ibm-granite/granite-4.0-h-micro``, ``model_type: granitemoehybrid`` with
``num_local_experts`` 0): pre-norm blocks whose operator is a Mamba-2
state-space mixer or grouped-query attention without rope by
``layer_types``, a SwiGLU of ``shared_intermediate_size`` in every block,
a last RMSNorm and a head tied to the embedding, under the family's four
scalars.

Independent of ``ray_tpu/models`` and of ``ray_tpu/ops/ssm.py``: it shares
nothing with the program but the layout of the parameter tree it is
handed. That tree keeps one stacked pytree a kind of layer,
``params["layers"]["mamba_dense"]`` and ``["attention_dense"]`` (a model of
one kind keeps the stack under ``params["layers"]`` itself); layer ``l``
is entry ``j`` of its kind's stack, ``j`` the number of earlier layers of
that kind. Leaves, each with a leading dim over its kind's layers:
``attn_norm``, ``mlp_norm`` ``[hidden]`` (the operator's and the
feed-forward's norm); ``wq [hidden, heads, head_dim]``, ``wk``, ``wv`` at
the key/value heads, ``wo [heads, head_dim, hidden]``; ``mamba_in [hidden,
inner + conv + heads]`` (columns ``[z | x B C | dt]``), ``mamba_conv_w
[conv, taps]``, ``mamba_conv_b [conv]``, ``mamba_dt_bias``, ``mamba_a_log``,
``mamba_d`` ``[heads]``, ``mamba_norm [inner]``, ``mamba_out [inner,
hidden]``; ``w_gate``, ``w_up`` ``[hidden, width]``, ``w_down``. Beside
them ``embed [vocab, hidden]`` and ``final_norm``.

The equations (ISSUE 46; ``transformers``' ``granitemoehybrid`` and
``bamba`` as far as they are known here, there being no network to read
them). ``x0 = embedding_multiplier * embed(ids)``. Block ``l``::

    x = x + residual_multiplier * Op_l(RMSNorm(x; attn_norm))
    x = x + residual_multiplier * SwiGLU(RMSNorm(x; mlp_norm))

- Mixer (``u`` its normed input): ``[z | xBC | dt] = u W_in``; ``xBC_t =
  silu(b + sum_j w[:, j] xBC_{t-(K-1)+j})``, depthwise, zeros before the
  sequence; ``[x | B | C] = xBC``, ``x`` as ``mamba_n_heads`` heads of
  ``mamba_d_head``, ONE ``B`` and ``C`` of ``mamba_d_state`` for all heads;
  ``dt = softplus(dt + dt_bias)``, no clamp; ``A = -exp(A_log)``. State
  ``h [heads, d_head, d_state]``, zeros at the start: ``h_t = exp(dt_t A)
  h_{t-1} + dt_t x_t B_t^T``; ``y_t = h_t C_t + D x_t``. ``y = RMSNorm(y *
  silu(z)) * g`` over the whole inner width, the gate BEFORE the norm.
  ``Op = y W_out``. The recurrence runs position by position
  (``lax.scan``): no chunk, no kernel, no cache.
- Attention: 32 query heads over 8 key/value heads of 64, NO rotation;
  causal softmax of ``q k^T * attention_multiplier``, a masked softmax.
- ``SwiGLU(r) = W_down (silu(W_gate r) * W_up r)``.
- Logits: ``RMSNorm(x; final_norm) E^T / logits_scaling``.

Everything is computed in float32 with
``jax.default_matmul_precision("highest")``; layers run in a Python loop
and one layer's matrices are cast to float32 at a time (an in-projection
is 70 MB), so that on the chip the reference fits beside 6.4 GB of served
weights.

``logits`` takes the controls of the cell's limit as keyword arguments,
each a fault planted in the reference (``tools/granite_probe.py`` reads
them): ``drop_state_every`` (the state zeroed at every multiple of that
many positions: a scan that loses the state at its chunks' edges),
``skip`` False (``D x`` left out), ``conv_bias`` False, ``gate_first``
False (the norm before the gate), ``residual`` (another
``residual_multiplier``), ``attention_scale`` (another softmax scale) and
``rope`` True (the rotation left on).

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmark.reference.dense_decoder import rms_norm, rotary

KINDS = {"mamba": "mamba_dense", "attention": "attention_dense"}
QUERY_BLOCK = 256


def _f32(a):
    return a.astype(jnp.float32)


def layer_leaves(params: Dict[str, Any], m: Dict[str, Any]
                 ) -> List[Tuple[Dict[str, Any], int]]:
    """For each layer of the model, in order: (its kind's stacked leaves,
    its index in them)."""
    kinds = [KINDS[op] for op in m["layer_types"]]
    stacks = (params["layers"] if len(set(kinds)) > 1
              else {kinds[0]: params["layers"]})
    return [(stacks[kind], kinds[:l].count(kind))
            for l, kind in enumerate(kinds)]


@partial(jax.jit, static_argnames=(
    "eps", "heads", "state", "residual", "drop_state_every", "skip",
    "conv_bias", "gate_first"))
def mixer(x, layers, j, *, eps, heads, state, residual,
          drop_state_every=0, skip=True, conv_bias=True, gate_first=True):
    """x [S, hidden] -> x + residual x the state-space mixer of its norm."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    S = x.shape[0]
    u = rms_norm(x, at("attn_norm"), eps)
    inner = at("mamba_norm").shape[0]
    zxbcdt = u @ at("mamba_in")
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:-heads],
                  zxbcdt[:, -heads:])
    w = at("mamba_conv_w")                                  # [conv, taps]
    taps = w.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, xbc.shape[1])), xbc], axis=0)
    conv = sum(w[:, t] * padded[t:t + S] for t in range(taps))
    if conv_bias:
        conv = conv + at("mamba_conv_b")
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :inner].reshape(S, heads, inner // heads)
    b, c = xbc[:, inner:inner + state], xbc[:, inner + state:]
    dt = jax.nn.softplus(dt + at("mamba_dt_bias"))          # [S, heads]
    a = -jnp.exp(at("mamba_a_log"))                         # [heads]

    def a_position(h, inp):
        x_t, dt_t, b_t, c_t, t = inp
        if drop_state_every:
            h = jnp.where(t % drop_state_every == 0, 0.0, h)
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, c_t)

    h0 = jnp.zeros((heads, inner // heads, state), jnp.float32)
    _, y = jax.lax.scan(a_position, h0, (xs, dt, b, c, jnp.arange(S)))
    if skip:
        y = y + at("mamba_d")[:, None] * xs
    y = y.reshape(S, inner)
    if gate_first:
        y = rms_norm(y * jax.nn.silu(z), at("mamba_norm"), eps)
    else:
        y = rms_norm(y, at("mamba_norm"), eps) * jax.nn.silu(z)
    return x + residual * (y @ at("mamba_out"))


def masked_softmax_attention(q, k, v, scale):
    """q [S, H, D], k/v [S, KVH, D] -> [S, H, D]; query head h reads
    key/value head h // (H / KVH); the scores of a block of queries
    against every key, those after the query masked out."""
    S, H, _ = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    key_pos = jnp.arange(S)
    outs = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        q_pos = start + jnp.arange(qb.shape[0])
        scores = jnp.where(q_pos[:, None] >= key_pos[None, :], scores,
                           -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(scores, axis=-1), v))
    return jnp.concatenate(outs, axis=0)


@partial(jax.jit, static_argnames=("eps", "scale", "residual", "rope"))
def attention(x, layers, j, *, eps, scale, residual, rope=0.0):
    """x [S, hidden] -> x + residual x attention of its norm; ``rope``
    not 0 rotates the queries and keys at that base (a control)."""
    at = lambda name: _f32(layers[name][j])  # noqa: E731
    u = rms_norm(x, at("attn_norm"), eps)
    q = jnp.einsum("sh,hnd->snd", u, at("wq"))
    k = jnp.einsum("sh,hnd->snd", u, at("wk"))
    v = jnp.einsum("sh,hnd->snd", u, at("wv"))
    if rope:
        positions = jnp.arange(x.shape[0])
        q, k = rotary(q, positions, rope), rotary(k, positions, rope)
    a = masked_softmax_attention(q, k, v, scale)
    return x + residual * jnp.einsum("snd,ndh->sh", a, at("wo"))


@partial(jax.jit, static_argnames=("eps", "residual"))
def swiglu(x, layers, j, *, eps, residual):
    r = rms_norm(x, _f32(layers["mlp_norm"][j]), eps)
    gate = r @ _f32(layers["w_gate"][j])
    up = r @ _f32(layers["w_up"][j])
    return x + residual * ((jax.nn.silu(gate) * up)
                           @ _f32(layers["w_down"][j]))


def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any], *,
                  drop_state_every: int = 0, skip: bool = True,
                  conv_bias: bool = True, gate_first: bool = True,
                  residual: Optional[float] = None,
                  attention_scale: Optional[float] = None,
                  rope: bool = False):
    """tokens [S] int -> final hidden states [S, hidden], after the norm.
    The keyword arguments are the module docstring's controls."""
    eps = float(m["rms_norm_eps"])
    residual = float(m["residual_multiplier"] if residual is None
                     else residual)
    scale = float(m["attention_multiplier"] if attention_scale is None
                  else attention_scale)
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(params["embed"], tokens, axis=0)) \
            * float(m["embedding_multiplier"])
        for layers, j in layer_leaves(params, m):
            if "mamba_in" in layers:
                x = mixer(x, layers, j, eps=eps, heads=m["mamba_n_heads"],
                          state=m["mamba_d_state"], residual=residual,
                          drop_state_every=drop_state_every, skip=skip,
                          conv_bias=conv_bias, gate_first=gate_first)
            else:
                x = attention(x, layers, j, eps=eps, scale=scale,
                              residual=residual,
                              rope=float(m["rope_theta"]) if rope else 0.0)
            x = swiglu(x, layers, j, eps=eps, residual=residual)
        return rms_norm(x, params["final_norm"], eps)


def logits(params: Dict[str, Any], tokens, m: Dict[str, Any], **controls):
    """[S, vocab] float32."""
    x = hidden_states(params, tokens, m, **controls)
    with jax.default_matmul_precision("highest"):
        return x @ _f32(params["embed"]).T / float(m["logits_scaling"])
