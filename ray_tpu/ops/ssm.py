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

``lengths [B]`` int32 says how many of a row's positions are its own where
a batch's rows are padded on the right to one length (a serving step's
are). The scan is causal, so a row's own outputs do not depend on it. With
it ``dt`` is taken for 0 at every position past its row's end, which then
neither decays the state nor adds to it: the state handed back is the one
after the row's last POSITION by either impl (``h0`` for a row of none),
what a decode goes on from. The kernel runs no chunk that starts past a
row's end, which is its whole time for such chunks, and every ``y`` past
the chunk that holds the row's end is zeros by either impl; inside that
chunk a padded position reads the standing state, ``y_t = h C_t + D x_t``.
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
             h0: Optional[jax.Array] = None, impl: str = "auto",
             lengths: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array]:
    """The module docstring's scan. impl as ``attention``'s: ``auto`` (on
    the TPU platform the kernel for a length of whole chunks, else and on
    the CPU platform the reference), ``flash`` (the kernel at any length: a
    ragged last chunk is padded with positions whose ``dt`` is 0, which
    neither decay the state nor add to it, and their outputs dropped) or
    ``reference``. ``lengths``: the module docstring's last paragraph
    (None: every row is whole)."""
    S = x.shape[1]
    if lengths is not None:
        # a position past its row's end takes no step: by either impl the
        # state stops at the row's last position
        own = jnp.arange(S) < lengths[:, None]                 # [B, S]
        dt = jnp.where(own[..., None], dt, 0)
    if impl == "auto":
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise RuntimeError(
                f"ssd_scan impl 'auto' knows the tpu and cpu platforms, "
                f"not {platform!r}; name an impl")
        impl = "flash" if platform == "tpu" and S % chunk == 0 \
            else "reference"
    if impl == "reference":
        y, h = reference_ssd_scan(x, dt, a, b, c, d, h0)
        if lengths is not None:
            # the kernel's zeros: past the chunk that holds a row's end
            ends = -(-lengths // chunk) * chunk
            y = jnp.where((jnp.arange(S) < ends[:, None])[..., None, None],
                          y, 0)
        return y, h
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
    if lengths is None:
        y, h = _kernel_scan(x, dt, a, b, c, d, h0, chunk)
    else:
        # a serving step's call, which nobody differentiates (jax raises a
        # NotImplementedError of its own): under `custom_vjp`, whose trace
        # of a kernel with a branch round its body churns memory, a
        # bucket's warm-up call took 6.0 s on the chip and not 3.2 (PERF.md
        # section 6, PR 59)
        from ray_tpu.ops.pallas.ssd_scan import ssd_scan_chunked

        y, h = ssd_scan_chunked(x, dt, a, b, c, d, h0, chunk, lengths)
    return y[:, :S], h
