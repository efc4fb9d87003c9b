"""State-space scan dispatcher: Mamba-2's recurrence by a chunked Pallas
kernel, or position by position in plain XLA.

One head's state ``h [P, N]`` (its ``P`` channels against ``N`` state
dims), zeros at a sequence's start unless ``h0`` says otherwise::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T
    y_t = h_t C_t + D x_t

``x [B, S, H, P]``; ``dt [B, S, H]`` float32, already through its softplus;
``A [H]`` float32, negative; ``B`` and ``C`` ``[B, S, N]``, ONE row a
position that every head reads (one group); ``D [H]``. The state, the
decays and their sums are float32 everywhere. Returns ``y [B, S, H, P]`` in
``x``'s type and the state after the last position ``[B, H, P, N]``
float32: all a decode keeps of a row.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def reference_ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array,
                       b: jax.Array, c: jax.Array, d: jax.Array,
                       h0: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence itself, a ``lax.scan`` over positions in float32: no
    chunk, no kernel. What a single token's decode runs, what the kernel is
    tested against, and the only path with a backward."""
    f32 = jnp.float32
    B, S, H, P = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, H, P, b.shape[-1]), f32)

    def a_position(h, at):
        x_t, dt_t, b_t, c_t = at             # [B,H,P] [B,H] [B,N] [B,N]
        h = (jnp.exp(dt_t * a)[:, :, None, None] * h
             + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None, :])
        y_t = jnp.einsum("bhpn,bn->bhp", h, c_t) + d[:, None] * x_t
        return h, y_t

    h, ys = jax.lax.scan(
        a_position, h0.astype(f32),
        (jnp.moveaxis(x.astype(f32), 1, 0), jnp.moveaxis(dt.astype(f32), 1, 0),
         jnp.moveaxis(b.astype(f32), 1, 0), jnp.moveaxis(c.astype(f32), 1, 0)))
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), h


@partial(jax.custom_vjp, nondiff_argnums=(7,))
def _kernel_scan(x, dt, a, b, c, d, h0, chunk):
    from ray_tpu.ops.pallas.ssd_scan import ssd_scan_chunked

    return ssd_scan_chunked(x, dt, a, b, c, d, h0, chunk)


def _kernel_scan_fwd(x, dt, a, b, c, d, h0, chunk):
    return _kernel_scan(x, dt, a, b, c, d, h0, chunk), None


def _kernel_scan_bwd(chunk, res, g):
    raise NotImplementedError(
        "the chunked state-space scan (ops/pallas/ssd_scan.py) has a "
        "forward only: train such a model with impl='reference'")


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, d: jax.Array, *, chunk: int = 256,
             h0: Optional[jax.Array] = None, impl: str = "auto"
             ) -> Tuple[jax.Array, jax.Array]:
    """The module docstring's scan. impl as ``attention``'s: ``auto`` (on
    the TPU platform the kernel for a length of whole chunks, else and on
    the CPU platform the reference), ``flash`` (the kernel at any length: a
    ragged last chunk is padded with positions whose ``dt`` is 0, which
    neither decay the state nor add to it, and their outputs dropped) or
    ``reference``."""
    S = x.shape[1]
    if impl == "auto":
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"ssd_scan impl 'auto' knows the tpu and cpu platforms, "
                f"not {platform!r}; name an impl")
        impl = "flash" if platform == "tpu" and S % chunk == 0 \
            else "reference"
    if impl == "reference":
        return reference_ssd_scan(x, dt, a, b, c, d, h0)
    if impl != "flash":
        raise ValueError(f"unknown ssd_scan impl {impl!r}; expected "
                         "auto|flash|reference")
    if h0 is None:
        h0 = jnp.zeros((x.shape[0], x.shape[2], x.shape[3], b.shape[-1]),
                       jnp.float32)
    pad = -S % chunk
    if pad:
        x, dt, b, c = (jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),)
                               * (v.ndim - 2)) for v in (x, dt, b, c))
    y, h = _kernel_scan(x, dt, a, b, c, d, h0, chunk)
    return y[:, :S], h
