"""Mamba-2's state-space scan in its chunked (SSD) form, as a Pallas TPU
kernel. Forward only.

A head's recurrence over a sequence, ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t
B_t^T`` (``h [P, N]``: the head's ``P`` channels against ``N`` state dims,
``B`` and ``C`` one row a position shared by every head of a group) and
``y_t = h_t C_t + D x_t``, is cut into chunks of ``Q`` positions. With
``cum_t`` the running sum of ``dt A`` inside a chunk (float32, made outside
the kernel: ``[B, S, H]`` numbers), a chunk's outputs are

    Y = (L o (C B^T)) (dt x)  +  exp(cum) * (C h_in)  +  D x
    L[t, s] = exp(cum_t - cum_s) for s <= t, else 0

and the state it leaves is ``h_out = exp(cum_last) h_in + sum_s
exp(cum_last - cum_s) dt_s x_s B_s^T``. The decays are exact (every
exponent is at most 0, nothing is factored into a product that could
overflow), the state lives in float32 in VMEM from chunk to chunk and never
in HBM between them, and no ``[Q, Q]`` tensor leaves the kernel.

Layout. ``x`` and ``y`` stay as the projections make and read them, ``[B,
S, H * P]`` with the heads side by side in the lanes; a grid step takes
``hb`` heads (``hb * P`` lanes, whole lane tiles) of one chunk of one row,
and the grid is ``(B, H / hb, S / Q)`` with the chunks innermost and in
order, which is what carries the state. ``C B^T`` is every head's: one
``[Q, Q]`` product a grid step, masked once, not ``hb``. Per head the step
spends one ``[Q, Q]`` exponential and three multiplies of the vector unit
(16 384 exponentials a position a layer at 64 heads and ``Q`` = 256: the
vector unit, not the MXU, bounds the kernel) and three matmuls with bf16
(the operands' type) into the MXU and float32 out: ``[Q, Q] x [Q, P]``,
``[Q, N] x [N, P]`` against the entering state and ``[N, Q] x [Q, P]`` into
the leaving one. The state is kept ``[N, P]`` a head so that all three are
plain products (``B^T`` comes transposed from outside: ``[B, N, S]``).

``hb`` = 8: 512 lanes of ``x`` and ``y`` a step (256 KB each a buffer), a
state of ``8 x [128, 64]`` float32, some 3 MB of scoped VMEM in all; 16
heads would halve the grid's steps (256 at 8 rows of 1024) and the
recomputed ``C B^T`` (an eighth of a step's MXU work at 8) and double the
unrolled body, for a kernel the vector unit bounds either way.

The rows' lengths. A batch's rows are padded on the right to one length,
and a chunk whose first position lies past its row's end holds nothing of
the row. Told how many chunks of each row hold a position of its own
(``lengths``, as ``ceil(length / Q)`` and the last such chunk's index, one
int32 pair a row, prefetched into SMEM before the grid runs), a grid step
past them does none of the work above: it leaves the state as it is and
writes ZEROS to its block of ``y`` (a padded position's output goes on into
the gated norm, the out-projection and the next layers, and what nobody
wrote may be a NaN, which a masked zero does not silence). The six
chunk-indexed operands' index maps hold such a step at the row's last live
chunk, which is the block already in VMEM, so nothing is fetched for it
either; a row of no position fetches its first chunk once and hands ``h0``
back. What the state means is the dispatcher's to say (``ops/ssm.py``: it
takes ``dt`` for 0 past a row's end, so the positions past it inside the
last live chunk, which the kernel runs like any other, neither decay the
state nor add to it, and the state handed back is the one after the row's
last POSITION). Not told (``lengths=None``: training, ``llama_decode``'s
pass over a prompt) the call is the one it was, with no scalar operand and
no branch. The vector unit binds the kernel, so a chunk not run is its time
back but for a skipped grid step's own, 1.4 us: at 8 rows of 1024 and 64
heads (256 grid steps, 32 row-chunks a head group; ms a layer, the kernel
alone, PERF.md section 6, PR 59) 1.07 not told; told, 1.10 with every
row-chunk live (the scalar's read and the branch: 0.024 more), 0.62 with 10
live, 0.54 with 7, 0.46 with 4 and 0.37 with none. The 1.4 us are the grid
step's own (ten operands' index maps, a live step's being 4.2 us in all),
not the zeros' write: with the output's block held too and nothing written
the empty batch read 0.353 for 0.367.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention

# What the device trace calls the kernel (``tpu_custom_call:<this>.N``).
SSD_SCAN_TRACE_NAME = "ssd_scan"
_LANES = flash_attention._LANES


def ssd_heads_a_step(heads: int, head_dim: int) -> int:
    """How many heads a grid step takes: 8 where that divides the heads and
    is whole lane tiles, else all of them (a block that is the whole array
    is always allowed)."""
    if heads % 8 == 0 and (8 * head_dim) % _LANES == 0:
        return 8
    return heads


def _kernel(*refs, hb: int, P: int, Q: int, told: bool):
    # told: the row's live chunks come first, from SMEM (`ssd_scan_chunked`)
    chunks_ref, refs = (refs[0], refs[1:]) if told else (None, refs)
    *of_a_chunk, h0_ref, y_ref, hT_ref, state = refs
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _enter():
        state[...] = h0_ref[0]

    if told:
        live = ic < chunks_ref[0, pl.program_id(0)]

        @pl.when(live)
        def _chunk():
            _a_chunk(*of_a_chunk, y_ref, state, hb=hb, P=P, Q=Q)

        @pl.when(jnp.logical_not(live))
        def _past_the_rows_end():
            y_ref[...] = jnp.zeros_like(y_ref)
    else:
        _a_chunk(*of_a_chunk, y_ref, state, hb=hb, P=P, Q=Q)

    @pl.when(ic == pl.num_programs(2) - 1)
    def _leave():
        hT_ref[0] = state[...]


def _a_chunk(x_ref, dt_ref, cumc_ref, cumr_ref, bt_ref, c_ref, d_ref, y_ref,
             state, *, hb: int, P: int, Q: int):
    """A chunk's whole work (module docstring): ``y`` of its positions from
    the state it enters with, and the state after it."""
    f32 = jnp.float32
    c = c_ref[0]                                           # [Q, N]
    bt = bt_ref[0]                                         # [N, Q]
    mdt = c.dtype                                          # the MXU's operands
    t = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    # every head's C B^T, the keys after a query's own position zeroed
    cb = jnp.where(t >= s, jnp.dot(c, bt, preferred_element_type=f32), 0.0)
    x = x_ref[0]                                           # [Q, hb * P]
    dt_cols = dt_ref[0, 0]                                 # [Q, hb] f32
    cum_cols = cumc_ref[0, 0]                              # [Q, hb] f32
    cum_rows = cumr_ref[0]                                 # [hb, Q] f32
    dx = d_ref[0] * x.astype(f32)                          # D x, every head
    for j in range(hb):
        lanes = slice(j * P, (j + 1) * P)
        cum_c = cum_cols[:, j:j + 1]                       # [Q, 1]
        cum_r = cum_rows[j:j + 1, :]                       # [1, Q]
        xj = x[:, lanes].astype(f32)                       # [Q, P]
        xdt = xj * dt_cols[:, j:j + 1]
        # the decays from s to t: exp of a number <= 0, the mask is in cb
        m = jnp.exp(jnp.minimum(cum_c - cum_r, 0.0)) * cb
        h_in = state[j]                                    # [N, P] f32
        yj = (jnp.dot(m.astype(mdt), xdt.astype(mdt),
                      preferred_element_type=f32)
              + jnp.exp(cum_c) * jnp.dot(c, h_in.astype(mdt),
                                         preferred_element_type=f32)
              + dx[:, lanes])
        y_ref[0, :, lanes] = yj.astype(y_ref.dtype)
        cum_last = cum_c[Q - 1:Q, :]                       # [1, 1]
        xw = xdt * jnp.exp(cum_last - cum_c)               # [Q, P]
        # (a [1, 1] goes to [N, P] in two steps: lanes, then sublanes)
        state[j] = (jnp.exp(jnp.broadcast_to(cum_last, (1, P))) * h_in
                    + jnp.dot(bt, xw.astype(mdt),
                              preferred_element_type=f32))


def ssd_scan_chunked(x: jax.Array, dt: jax.Array, a: jax.Array,
                     b: jax.Array, c: jax.Array, d: jax.Array,
                     h0: jax.Array, chunk: int,
                     lengths: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, jax.Array]:
    """``x [B, S, H, P]``, ``dt [B, S, H]`` float32 (after its softplus),
    ``a [H]`` float32 (negative), ``b`` and ``c`` ``[B, S, N]`` (one group:
    every head's), ``d [H]``, the entering state ``h0 [B, H, P, N]``
    float32 -> (``y [B, S, H, P]`` in ``x``'s type, the state after the
    last position ``[B, H, P, N]`` float32). ``S`` is whole chunks.
    ``lengths [B]`` int32: how many of a row's positions are its own, the
    rest being padding on its right (None: all ``S`` of every row, and the
    call it was before it knew of lengths). A chunk that starts at or past
    a row's length is not run and not read: its ``y`` is zeros, and the
    state is the one after the row's last chunk that ran, ``h0`` for a row
    of no position (module docstring)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    if S % chunk or chunk % _LANES:
        raise ValueError(f"ssd_scan_chunked: a length of {S} is not whole "
                         f"chunks of {chunk}, or a chunk is not whole lane "
                         f"tiles of {_LANES}")
    if b.shape != (B, S, N) or c.shape != (B, S, N) \
            or dt.shape != (B, S, H) or h0.shape != (B, H, P, N) \
            or (lengths is not None and lengths.shape != (B,)):
        raise ValueError(f"ssd_scan_chunked: x{x.shape} dt{dt.shape} "
                         f"b{b.shape} c{c.shape} h0{h0.shape} "
                         f"lengths{getattr(lengths, 'shape', None)}")
    hb = ssd_heads_a_step(H, P)
    G, Q, f32 = H // hb, chunk, jnp.float32
    # the running sum of dt A inside each chunk: [B, S, H] float32 numbers
    # made here, in XLA, where a cumulative sum costs nothing to get right
    step = dt.astype(f32) * a.astype(f32)
    cum = jnp.cumsum(step.reshape(B, S // Q, Q, H), axis=2).reshape(B, S, H)

    def columns(v):                                    # [B, G, S, hb]
        return jnp.transpose(v.reshape(B, S, G, hb), (0, 2, 1, 3))

    operands = (
        x.reshape(B, S, H * P), columns(dt.astype(f32)), columns(cum),
        jnp.swapaxes(cum, 1, 2), jnp.swapaxes(b, 1, 2), c,
        jnp.repeat(d.astype(f32), P).reshape(G, 1, hb * P),
        jnp.swapaxes(h0.astype(f32), 2, 3))
    told = lengths is not None
    if told:
        # a row's live chunks and the last of them, made once, here: the
        # body's test is one compare and an index map one `min` of two
        # scalars (a map is traced again at every lowering, which no
        # compile cache keeps)
        live = (lengths.astype(jnp.int32) + (Q - 1)) // Q
        operands = (jnp.stack([live, jnp.maximum(live - 1, 0)]),) + operands

    def at(block, place, held=False):
        """A block at ``place(i, g, k)``: row ``i``, head group ``g``,
        chunk ``k`` or, ``held`` and told the lengths, the row's last live
        chunk past it: the block already in VMEM, so nothing is fetched."""
        if not told:
            return pl.BlockSpec(block, place)
        return pl.BlockSpec(block, lambda i, g, k, n: place(
            i, g, jax.lax.min(k, n[1, i]) if held else k))

    spec = dict(
        grid=(B, G, S // Q),
        in_specs=[
            at((1, Q, hb * P), lambda i, g, k: (i, k, g), held=True),
            at((1, 1, Q, hb), lambda i, g, k: (i, g, k, 0), held=True),
            at((1, 1, Q, hb), lambda i, g, k: (i, g, k, 0), held=True),
            at((1, hb, Q), lambda i, g, k: (i, g, k), held=True),
            at((1, N, Q), lambda i, g, k: (i, 0, k), held=True),
            at((1, Q, N), lambda i, g, k: (i, k, 0), held=True),
            at((1, 1, hb * P), lambda i, g, k: (g, 0, 0)),
            at((1, hb, N, P), lambda i, g, k: (i, g, 0, 0)),
        ],
        out_specs=[
            at((1, Q, hb * P), lambda i, g, k: (i, k, g)),
            at((1, hb, N, P), lambda i, g, k: (i, g, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((hb, N, P), f32)])
    if told:
        spec = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **spec))
    with jax.named_scope(SSD_SCAN_TRACE_NAME):  # the kernel's alone
        y, hT = pl.pallas_call(
            functools.partial(_kernel, hb=hb, P=P, Q=Q, told=told),
            out_shape=[jax.ShapeDtypeStruct((B, S, H * P), x.dtype),
                       jax.ShapeDtypeStruct((B, H, N, P), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=flash_attention._interpret(),
            **spec,
        )(*operands)
    return y.reshape(B, S, H, P), jnp.swapaxes(hT, 2, 3)
