"""Attention dispatcher: reference XLA path, Pallas flash kernel, ring path.

GQA layout everywhere: q [B, S, H, D], k/v [B, S_kv, KVH, D] with
H % KVH == 0. Returns [B, S, H, D] in q.dtype.

Two widths (latent attention's prefill form): the values may be narrower
than the queries and keys, ``v [B, S_kv, KVH, Dv]``, and the queries and
keys may have a second, rotary part whose key is one row a position shared
by every head: ``q_rope [B, S, H, R]``, ``k_rope [B, S_kv, R]``; the scores
are ``q k^T + q_rope k_rope^T``, times ``scale`` (by default the whole
query width ``** -0.5``), and the result is ``[B, S, H, Dv]``.

Fewer keys than the causal ones. ``window``: query ``t`` sees keys ``t -
window + 1 .. t``, its own among them. ``keep [B, S, S_kv]`` (bool): query
``t`` of row ``b`` sees key ``s`` only where ``keep[b, t, s]``, every head
alike (an indexer's choice: ``index_scores`` gives what it is made from).
Both come on top of ``causal``; the flash kernel takes both at two widths
(the window's blocks outside it are not visited; the choice is a mask a
block) and either at equal widths
(``flash_attention.flash_attention_window``,
``flash_attention.flash_attention_selected``: forward only).

``lengths [B]`` int32: how many of a row's positions are its own where a
batch's rows are padded on the right to one length (a serving step's are).
The keys are causal, so a row's own outputs do not depend on it; the flash
forwards, at two widths and at equal ones, under a window or not, compute
no block past a row's end and write zeros there
(``ops/pallas/flash_attention.py``; one device, forward only). The
reference computes every position and does not read it: the outputs past a
row's end are nobody's to read.
"""

from __future__ import annotations

import warnings
from typing import Optional

import jax
import jax.numpy as jnp

_NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    B, S, KVH, D = k.shape
    return jnp.broadcast_to(
        k[:, :, :, None, :], (B, S, KVH, n_rep, D)
    ).reshape(B, S, KVH * n_rep, D)


def reference_attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, causal: bool = True,
    q_offset: Optional[jax.Array] = None,
    valid_kv_len: Optional[jax.Array] = None,
    q_rope: Optional[jax.Array] = None,
    k_rope: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,
) -> jax.Array:
    """Plain einsum attention with fp32 softmax. ``q_offset`` positions the
    query block inside a longer kv sequence (decode with kv cache)."""
    B, Sq, H, D = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    k = _repeat_kv(k, H // KVH)
    v = _repeat_kv(v, H // KVH)
    if q_rope is None:
        scale = D ** -0.5 if scale is None else scale
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
                  * scale)
    else:  # the shared rotary key meets every head
        if scale is None:
            scale = (D + q_rope.shape[3]) ** -0.5
        logits = (jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
                  + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_rope
                               ).astype(jnp.float32)) * scale
    kv_pos = jnp.arange(Skv)
    if causal:
        q_pos = jnp.arange(Sq)
        if q_offset is not None:
            q_pos = q_pos + q_offset
        mask = q_pos[:, None] >= kv_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - kv_pos[None, :] < window
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    elif window is not None:
        raise ValueError("a window is the causal keys' last ones")
    if keep is not None:
        logits = jnp.where(keep[:, None], logits, _NEG_INF)
    if valid_kv_len is not None:
        vmask = kv_pos[None, :] < valid_kv_len[:, None]  # [B, Skv]
        logits = jnp.where(vmask[:, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_per_shard(q: jax.Array, k: jax.Array, v: jax.Array,
                     causal: bool, q_rope: Optional[jax.Array] = None,
                     k_rope: Optional[jax.Array] = None,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     keep: Optional[jax.Array] = None,
                     lengths: Optional[jax.Array] = None) -> jax.Array:
    """The Pallas kernel, run on each device's own shard when a mesh is in
    scope. A ``pallas_call`` has no partitioning rule: left to GSPMD its
    operands are all-gathered and every chip computes the whole batch."""
    from ray_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_selected,
        flash_attention_shared_rope, flash_attention_window)
    from ray_tpu.parallel.sharding import ambient_mesh, logical_to_spec

    mesh = ambient_mesh()
    if q_rope is not None:
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                "flash attention at two widths runs on one device; a mesh "
                "needs impl='reference'")
        if scale is None:
            scale = (q.shape[3] + q_rope.shape[3]) ** -0.5
        return flash_attention_shared_rope(
            q, q_rope, k, k_rope, v, scale, causal, window,
            None if keep is None else keep.astype(jnp.int8), lengths)
    if scale is not None or v.shape[3] != q.shape[3]:
        raise NotImplementedError(
            "the equal-width flash kernels scale by head_dim ** -0.5 and "
            "take values of the keys' width")
    if window is not None or lengths is not None or keep is not None:
        if not causal:
            raise ValueError("a window and a choice are of the causal "
                             "keys, and the rows' lengths are causal's")
        if mesh is not None and mesh.size > 1:
            raise NotImplementedError(
                "the equal-width flash forward under a window, under a "
                "choice of keys or told the rows' lengths runs on one "
                "device; a mesh needs impl='reference'")
        if keep is not None:
            if window is not None:
                raise NotImplementedError(
                    "the equal-width flash forward takes a window or a "
                    "choice of keys, not both")
            return flash_attention_selected(q, k, v, keep.astype(jnp.int8),
                                            lengths)
        if window is not None:
            return flash_attention_window(q, k, v, window, lengths)
        return flash_attention(q, k, v, causal, lengths)
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, causal)
    if mesh.shape.get("seq", 1) > 1:
        raise ValueError(
            "flash attention keeps each sequence on one device; a mesh "
            "with seq > 1 needs the model layer's 'ring_seq' path")
    q_spec = logical_to_spec(("batch", "seq", "heads", "head_dim"))
    kv_spec = logical_to_spec(("batch", "seq", "kv_heads", "head_dim"))
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal),
        in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
        check_vma=False)(q, k, v)


def _device_memory_bytes() -> Optional[int]:
    """What the first device says it can hold, where it says."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def attention(
    q: jax.Array, k: jax.Array, v: jax.Array,
    *, impl: str = "auto", causal: bool = True,
    q_offset: Optional[jax.Array] = None,
    valid_kv_len: Optional[jax.Array] = None,
    q_rope: Optional[jax.Array] = None,
    k_rope: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    window: Optional[int] = None,
    keep: Optional[jax.Array] = None,
    lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """impl: auto (on the TPU platform the flash kernel, on the CPU platform
    the reference), flash, reference. Ring attention is invoked explicitly
    via ops.ring_attention by the seq-parallel layer, not through this
    dispatcher. ``q_rope``, ``k_rope`` and ``scale`` are the module
    docstring's two widths, ``window`` and ``keep`` its fewer keys,
    ``lengths`` its last paragraph.

    ``auto`` on a TPU still takes the reference for what the kernel has no
    path for (cached decode, lengths off the 128 grid, widths it does not
    take: ``flash_attention.takes_head_dim``) and says so once per shape;
    where the reference's float32 scores alone would not fit the device's
    memory it raises instead. ``flash`` raises for cached decode and hands
    the kernel any shape."""
    if impl == "auto":
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"attention impl 'auto' knows the tpu and cpu platforms, "
                f"not {platform!r}; name an impl")
        impl = "reference"
        if platform == "tpu":
            from ray_tpu.ops.pallas.flash_attention import takes_head_dim

            shared = 0 if q_rope is None else q_rope.shape[3]
            kernel_takes_it = (
                q_offset is None and valid_kv_len is None
                and q.shape[1] == k.shape[1]
                and q.shape[1] % 128 == 0
                and (q_rope is not None or scale is None)
                and (keep is None or keep.shape == (
                    q.shape[0], q.shape[1], k.shape[1]))
                and takes_head_dim(q.shape[3] + shared, v.shape[3],
                                   shared_dim=shared))
            if kernel_takes_it:
                impl = "flash"
            else:
                shapes = f"q{tuple(q.shape)} k{tuple(k.shape)}" + (
                    "" if q_rope is None else f" q_rope{tuple(q_rope.shape)}")
                scores = 4 * q.shape[0] * q.shape[2] * q.shape[1] * k.shape[1]
                holds = _device_memory_bytes()
                if holds is not None and scores > holds:
                    raise ValueError(
                        f"attention impl 'auto' on TPU: the flash kernel "
                        f"has no path for {shapes} v{tuple(v.shape)}, and "
                        f"the reference's float32 scores are {scores} "
                        f"bytes where the device holds {holds}")
                warnings.warn(
                    "attention impl 'auto' on TPU: reference path for "
                    f"{shapes} (cached decode, "
                    "a length off the flash kernel's 128 grid, or a head "
                    "dim it does not take)",
                    stacklevel=2)
    if impl == "flash":
        if q_offset is not None or valid_kv_len is not None:
            raise NotImplementedError(
                "flash attention does not support q_offset/valid_kv_len; "
                "use impl='reference' for cached decode")
        fewer = {name: given for name, given in (
            ("window", window), ("keep", keep), ("lengths", lengths))
            if given is not None}
        return _flash_per_shard(q, k, v, causal, q_rope, k_rope, scale,
                                **fewer)
    if impl != "reference":
        raise ValueError(
            f"unknown attention impl {impl!r}; expected "
            "auto|flash|reference "
            "(ring attention is the model layer's 'ring_seq' path)")
    return reference_attention(q, k, v, causal=causal, q_offset=q_offset,
                               valid_kv_len=valid_kv_len, q_rope=q_rope,
                               k_rope=k_rope, scale=scale, window=window,
                               keep=keep)


def reference_index_scores(q: jax.Array, k: jax.Array,
                           weights: jax.Array) -> jax.Array:
    """``index_scores`` by plain einsums: every head's ``[S, T]`` float32
    products at once, so for small sizes and a decode's few queries."""
    products = jnp.einsum("bjsd,btd->bjst", q, k,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bjst,bsj->bst", jax.nn.relu(products),
                      weights.astype(jnp.float32))


def index_scores(q: jax.Array, k: jax.Array, weights: jax.Array, *,
                 impl: str = "auto") -> jax.Array:
    """An indexer's scores (DeepSeek-V3.2's lightning indexer): ``q [B, J,
    S, D]`` (``J`` index heads), ``k [B, T, D]`` (ONE key a position) and
    ``weights [B, S, J]`` -> ``I [B, S, T]`` float32, ``I[b, s, t] = sum_j
    weights[b, s, j] ReLU(q[b, j, s] . k[b, t])``. impl as ``attention``'s:
    the kernel (``ops/pallas/index_scores.py``: no ``[J, S, T]`` tensor is
    made; it scores a prefill's square and leaves the blocks above the
    diagonal zero) or the reference."""
    if impl == "auto":
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"index_scores impl 'auto' knows the tpu and cpu platforms, "
                f"not {platform!r}; name an impl")
        impl = ("flash" if platform == "tpu" and q.shape[2] == k.shape[1]
                and q.shape[2] % 128 == 0 else "reference")
    if impl == "flash":
        from ray_tpu.ops.pallas.index_scores import index_scores_causal

        return index_scores_causal(q, k, weights)
    if impl != "reference":
        raise ValueError(f"unknown index_scores impl {impl!r}; expected "
                         "auto|flash|reference")
    return reference_index_scores(q, k, weights)
