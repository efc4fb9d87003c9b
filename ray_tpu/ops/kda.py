"""Kimi delta attention dispatcher: the gated delta rule with a decay a
channel (Kimi Linear's KDA, arXiv:2510.26692) by a chunked Pallas kernel,
or position by position in plain XLA.

One head's state ``S [Dk, Dv]`` (its key channels against its value
channels), zeros at a sequence's start unless ``s0`` says otherwise::

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

which is ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t
v_t^T``: every key channel forgets at its own rate, and what the state
already answers to ``k_t`` is taken off ``v_t`` before it is written.

``q``, ``k`` ``[B, S, H, Dk]`` and ``v [B, S, H, Dv]`` as the caller made
them (the L2 norms of ``q`` and ``k`` and ``q``'s scale are the caller's,
or with ``l2_norm`` made here: each head's ``q`` and ``k`` over ``sqrt(sum
x^2 + 1e-6)``, ``q`` then times ``Dk ** -0.5``, in float32);
``g [B, S, H, Dk]`` float32, the LOG of the decay, at most 0 and, for the
kernel, no lower than ``G_LOWER_BOUND`` a position; ``beta [B, S, H]``
float32. The state, the decays and their sums are float32 everywhere.
Returns ``o [B, S, H, Dv]`` in ``q``'s type and the state after the last
position ``[B, H, Dk, Dv]`` float32: all a decode keeps of a row.

``lengths [B]`` int32 says how many of a row's positions are its own where
a batch's rows are padded on the right to one length (a serving step's
are). The rule is causal, so a row's own outputs do not depend on it; the
kernel stops at the end of the chunk that holds the row's last position,
which is its whole time for the chunks after it, and every ``o`` past that
chunk is zeros by either impl. The state the kernel then hands back is the
one after that chunk, not after position ``S`` (the recurrence's, which
runs every position whatever the lengths) and not after the row's last
position: nothing in the tree reads a padded row's state.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# the least log-decay a position the kernel is safe for: it forms decays
# as exp of a difference of running sums over at most 8 positions, and
# exp(8 x 5) and its inverse are far inside float32 (ops/pallas/
# kda_chunk.py)
G_LOWER_BOUND = -5.0


def _unit(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def reference_kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                  beta: jax.Array, s0: Optional[jax.Array] = None,
                  l2_norm: bool = False) -> Tuple[jax.Array, jax.Array]:
    """The recurrence itself, a ``lax.scan`` over positions in float32: no
    chunk, no kernel. What a single token's decode runs, what the kernel is
    tested against, and the only path with a backward."""
    f32 = jnp.float32
    B, S, H, Dk = q.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, Dk, v.shape[-1]), f32)

    def a_position(s, at):
        q_t, k_t, v_t, g_t, b_t = at         # [B,H,D] x 4, [B,H]
        s = jnp.exp(g_t)[..., None] * s
        left = v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + (b_t[..., None] * k_t)[..., None] * left[:, :, None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    qf, kf = q.astype(f32), k.astype(f32)
    if l2_norm:
        qf, kf = _unit(qf) * Dk ** -0.5, _unit(kf)
    s, o = jax.lax.scan(
        a_position, s0.astype(f32),
        tuple(jnp.moveaxis(a.astype(f32), 1, 0)
              for a in (qf, kf, v, g, beta)))
    return jnp.moveaxis(o, 0, 1).astype(q.dtype), s


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_kda(q, k, v, g, beta, s0, chunk, l2_norm, lengths):
    from ray_tpu.ops.pallas.kda_chunk import kda_chunked

    return kda_chunked(q, k, v, g, beta, s0, chunk, l2_norm, lengths)


def _kernel_kda_fwd(q, k, v, g, beta, s0, chunk, l2_norm, lengths):
    return _kernel_kda(q, k, v, g, beta, s0, chunk, l2_norm, lengths), None


def _kernel_kda_bwd(chunk, l2_norm, res, ct):
    raise NotImplementedError(
        "the chunked Kimi delta attention (ops/pallas/kda_chunk.py) has a "
        "forward only: train such a model with impl='reference'")


_kernel_kda.defvjp(_kernel_kda_fwd, _kernel_kda_bwd)


def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, s0: Optional[jax.Array] = None, chunk: int = 64,
        *, impl: str = "auto", l2_norm: bool = False,
        lengths: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, jax.Array]:
    """The module docstring's rule. impl as ``ssd_scan``'s: ``auto`` (on
    the TPU platform the kernel for a length of whole chunks, else and on
    the CPU platform the reference), ``flash`` (the kernel at any length: a
    ragged last chunk is padded with positions whose ``g``, ``beta`` and
    ``k`` are 0, which neither decay the state nor write to it, and their
    outputs dropped) or ``reference``. ``lengths``: the module docstring's
    last paragraph (None: every row is whole)."""
    S = q.shape[1]
    if impl == "auto":
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"kda impl 'auto' knows the tpu and cpu platforms, not "
                f"{platform!r}; name an impl")
        impl = "flash" if platform == "tpu" and S % chunk == 0 \
            else "reference"
    if impl == "reference":
        o, s = reference_kda(q, k, v, g, beta, s0, l2_norm)
        if lengths is not None:
            # the kernel's zeros: past the chunk that holds a row's end
            ends = -(-lengths // chunk) * chunk
            o = jnp.where((jnp.arange(S) < ends[:, None])[..., None, None],
                          o, 0)
        return o, s
    if impl != "flash":
        raise ValueError(f"unknown kda impl {impl!r}; expected "
                         "auto|flash|reference")
    if s0 is None:
        s0 = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]),
                       jnp.float32)
    pad = -S % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    o, s = _kernel_kda(q, k, v, g, beta, s0, chunk, l2_norm, lengths)
    return o[:, :S], s
