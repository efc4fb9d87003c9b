"""Plain float32 reference of a dense decoder: RMSNorm, rotary, grouped
causal attention, SwiGLU, untied head, cross-entropy.

Independent of ``ray_tpu/models``: it shares nothing with the program but
the layout of the parameter tree it is handed (the layers stacked on a
leading axis, ``wq [L, hidden, heads, head_dim]``, ``wk``/``wv`` at the
key/value heads, ``wo [L, heads, head_dim, hidden]``, ``w_gate``/``w_up``
``[L, hidden, mlp]``, ``w_down [L, mlp, hidden]``, the two norms, ``embed
[vocab, hidden]``, ``final_norm``, ``lm_head [hidden, vocab]``). It follows
the published Mistral-7B description: pre-norm blocks, rotary embedding on
the two halves of each head (the "rotate half" convention of the
HuggingFace implementation), ``num_key_value_heads`` shared by groups of
query heads, no biases, no sliding window (v0.3), SiLU gate.

Everything is computed in float32 with
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise done in bf16 passes). No kernels, no cache, no batching: one
sequence at a time, the layers in a Python loop, attention in blocks of
queries so that the scores of a 4096-token sequence fit beside a model.

``m`` is the configuration file's dict (HuggingFace key names).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rotary(x, positions, theta):
    """x [S, heads, head_dim]: pairs (d, d + head_dim/2) rotated by
    position * theta^(-2d/head_dim)."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def grouped_causal_attention(q, k, v):
    """q [S, H, D], k/v [S, KVH, D] -> [S, H, D]; query head h reads
    key/value head h // (H / KVH)."""
    S, H, D = q.shape
    rep = H // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    kv_pos = jnp.arange(S)
    outs = []
    for start in range(0, S, QUERY_BLOCK):
        qb = q[start:start + QUERY_BLOCK]
        scores = jnp.einsum("qhd,khd->hqk", qb, k) * (D ** -0.5)
        q_pos = start + jnp.arange(qb.shape[0])
        scores = jnp.where(q_pos[:, None] >= kv_pos[None, :], scores,
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    return jnp.concatenate(outs, axis=0)


@partial(jax.jit, static_argnames=("eps", "theta"))
def _block(x, lp, positions, *, eps, theta):
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = rms_norm(x, lp["attn_norm"], eps)
    q = jnp.einsum("sh,hnd->snd", h, f32(lp["wq"]))
    k = jnp.einsum("sh,hnd->snd", h, f32(lp["wk"]))
    v = jnp.einsum("sh,hnd->snd", h, f32(lp["wv"]))
    a = grouped_causal_attention(rotary(q, positions, theta),
                                 rotary(k, positions, theta), v)
    x = x + jnp.einsum("snd,ndh->sh", a, f32(lp["wo"]))
    h = rms_norm(x, lp["mlp_norm"], eps)
    gate = jnp.einsum("sh,hm->sm", h, f32(lp["w_gate"]))
    up = jnp.einsum("sh,hm->sm", h, f32(lp["w_up"]))
    return x + jnp.einsum("sm,mh->sh", jax.nn.silu(gate) * up,
                          f32(lp["w_down"]))


def hidden_states(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """tokens [S] int -> final hidden states [S, hidden], after the norm."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        positions = jnp.arange(tokens.shape[0])
        for i in range(m["num_hidden_layers"]):
            lp = {k: a[i] for k, a in params["layers"].items()}
            x = _block(x, lp, positions, eps=float(m["rms_norm_eps"]),
                       theta=float(m["rope_theta"]))
        return rms_norm(x, params["final_norm"], float(m["rms_norm_eps"]))


def logits(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """[S, vocab] float32."""
    x = hidden_states(params, tokens, m)
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("sh,hv->sv", x,
                          params["lm_head"].astype(jnp.float32))


def last_logits(params: Dict[str, Any], tokens, m: Dict[str, Any]):
    """[vocab] float32: the logits after the last token of the prompt."""
    x = hidden_states(params, tokens, m)[-1]
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("h,hv->v", x, params["lm_head"].astype(jnp.float32))


def loss(params: Dict[str, Any], inputs, targets, m: Dict[str, Any]):
    """Mean next-token cross-entropy of one sequence (inputs, targets [S])."""
    lg = logits(params, inputs, m)
    logz = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
