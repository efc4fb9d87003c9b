"""Kimi delta attention (the gated delta rule with a decay a channel) in
its chunked WY form, as a Pallas TPU kernel. Forward only.

A head's recurrence over a sequence (``ops/kda.py``: ``S' = Diag(exp(g_t))
S_{t-1}``, ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S_t^T
q_t``, ``S [Dk, Dv]``) is cut into chunks of ``C`` positions. With ``G_t``
the running sum of ``g`` inside a chunk (float32, made in the kernel: a
lower-triangular matrix of ones times the chunk's ``g`` at the MXU's
float32 precision; made outside, a cumulative sum over 64 of 3072
positions costs XLA two relayouts of ``g``, PERF.md section 6) and ``S_0``
the state the chunk enters with::

    A_kk[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])   s < t
    A_qk[t, s] =        sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])   s <= t
    v' = (I + A_kk)^-1 Diag(beta) (v - (k * exp(G)) S_0)
    o  = (q * exp(G)) S_0 + A_qk v'
    S_C = Diag(exp(G_C)) S_0 + sum_s (k_s * exp(G_C - G_s)) v'_s^T

``v'`` is the WY form's ``u - w S_0`` (``T = (I + A_kk)^-1 Diag(beta)``,
``w = T (k * exp(G))``, ``u = T v``) with the solve run once, on the
difference, and not on ``k`` and ``v`` apart: the same numbers, half the
right-hand sides.

The decays. ``g`` is at most 0 and at least ``ops.kda.G_LOWER_BOUND`` (-5)
a position, so a running sum reaches -5 C and ``exp(-G)`` alone would
leave float32 within 18 positions. The two matrices are therefore made a
row block of 16 positions at a time against the running sum ``R`` in that
block's MIDDLE: the rows carry ``exp(G_t - R)`` and, inside the block, the
columns ``exp(R - G_s)``, each between ``exp(-8 x 5)`` and ``exp(8 x 5)``,
far inside float32 (and bfloat16) both ways, so that neither a factor nor
its product with a small ``q`` or ``k`` is flushed to zero while the decay
they make together is still of a size that counts (against the block's
START the rows' factor reaches ``exp(-80)``, and its product with ``q``
leaves float32's normal numbers); the columns of the blocks before carry a
factor of at most 1.
The state's terms carry ``exp(G)`` and ``exp(G_C - G_s)``, at most 1. The
solve is a forward substitution in float32: inside a block of 16 on the
vector unit, column by column, and from block to block through the MXU.
bf16 (the operands' type) goes into the MXU and float32 comes out; the
decays, the solve's diagonal blocks and the state are float32, and the
state lives in VMEM from chunk to chunk and never in HBM between them.

With ``l2_norm`` the kernel is handed the queries and keys as their taps
leave them and brings each head's to unit length itself (``x / sqrt(sum
x^2 + 1e-6)``, the queries then times ``D ** -0.5``), in float32 from the
operands' bf16: a sum over a head's 128 lanes where the rows lie in VMEM
anyway, for which XLA needs three passes over ``[B, S, 3 x inner]`` in
float32 and a relayout.

Layout. ``q``, ``k``, ``v``, ``g`` and ``o`` stay as the projections make
and read them, ``[B, S, H * D]`` with the heads side by side in the lanes;
a grid step takes ``hb`` heads of one chunk of one row, and the grid is
``(B, H / hb, S / C)`` with the chunks innermost and in order, which is
what carries the state. The state is kept ``[Dv, Dk]`` a head, so that the
decay of its key channels runs along the lanes.

The rows' lengths. A batch's rows are padded on the right to one length,
and a chunk whose first position lies past its row's end holds nothing of
the row. The kernel is told how many chunks of each row hold a position of
its own (``lengths``, as ``ceil(length / C)`` and the last such chunk's
index, one int32 pair a row, prefetched into SMEM before the grid runs) and
a grid step past them does none of the work above: it leaves the state as
it is and writes ZEROS to its block of ``o`` (a padded position's output
goes on into the gated norm, the out-projection and the next layers, and
what nobody wrote may be a NaN, which a masked zero does not silence). The
blocks' index maps hold such a step at the row's last live chunk, which is
the block already in VMEM, so nothing is fetched for it either. The state
handed back is the one after a row's last live CHUNK: the positions past
the row's end inside that chunk are run like any other, as they were when
the kernel knew no lengths, and a row of no position hands ``s0`` back.
The vector unit binds the kernel, so a chunk not run is its time back but
for 0.5 to 0.7 us a skipped grid step: 106 of 192 chunks live take 6.8 ms a
layer, not 11.3; 8 whole rows 0.3 ms more (PERF.md section 6, PR 53).

``C`` and ``hb``, by the chip (PERF.md section 6, PR 52: 8 rows of 3072,
32 heads of 128, bf16, the kernel alone, ms): chunks of 64, 128 and 256 at
4 heads a step take 19.1, 16.6 and 17.2, and 18.4, 15.9 and 16.9 at 8. The
two ``[C, C]`` matrices cost ``C`` products a position and the row blocks'
column factors ``C / 16`` exponentials a position and channel, both linear
in ``C``, while a chunk's fixed costs (a grid step, the state's two
products of ``128 x 128``) fall as ``1 / C``: the two meet near 128, which
is what a configuration should ask for (``LlamaConfig.kda_chunk``). 8
heads a step are 4 % faster again and were NOT taken: the body is
unrolled over its heads, blocks and columns, and a serving step's
program, which holds a kernel a run of layers, then takes 41 to 46 s to
compile where it took 21 to 25, beside the 55 s that the benchmark's
harness gives a warm-up call.
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
KDA_CHUNK_TRACE_NAME = "kda_chunk"
_LANES = flash_attention._LANES
# a row block of the two matrices, and the solve's diagonal block
_SUB = 16
# the largest exponent of a column's factor inside its block: half a block
# of positions at the bound (module docstring)
_MOST = _SUB // 2 * 5.0


def kda_heads_a_step(heads: int) -> int:
    """How many heads a grid step takes: 4 where that divides the heads
    (a step's fixed cost over four heads, four independent chains for the
    scheduler to interleave; the module docstring has the times, and why
    not 8), else all of them."""
    return 4 if heads % 4 == 0 else heads


def _kernel(chunks_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref,
            sT_ref, state, *, hb: int, D: int, C: int, l2_norm: bool):
    ic = pl.program_id(2)
    # the row's chunks that hold a position of its own (`kda_chunked`)
    live = ic < chunks_ref[0, pl.program_id(0)]

    @pl.when(ic == 0)
    def _enter():
        state[...] = s0_ref[0]

    @pl.when(live)
    def _chunk():
        _a_chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state, hb=hb,
                 D=D, C=C, l2_norm=l2_norm)

    @pl.when(jnp.logical_not(live))
    def _past_the_rows_end():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ic == pl.num_programs(2) - 1)
    def _leave():
        sT_ref[0] = state[...]


def _a_chunk(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state, *, hb: int,
             D: int, C: int, l2_norm: bool):
    """A chunk's whole work (module docstring): ``o`` of its positions from
    the state it enters with, and the state after it."""
    f32 = jnp.float32
    nb = C // _SUB
    mdt = q_ref.dtype                                      # the MXU's operands
    nt = (((1,), (1,)), ((), ()))                          # a @ b^T
    tn = (((0,), (0,)), ((), ()))                          # a^T @ b
    t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same_block = (t // _SUB) == (s // _SUB)
    ones_below = (t >= s).astype(f32)                      # the running sum
    betas = beta_ref[0, 0]                                 # [C, hb] f32
    for j in range(hb):
        lanes = slice(j * D, (j + 1) * D)
        qf = q_ref[0, :, lanes].astype(f32)                # [C, Dk]
        kf = k_ref[0, :, lanes].astype(f32)
        vf = v_ref[0, :, lanes].astype(f32)                # [C, Dv]
        if l2_norm:
            qf = qf * (jax.lax.rsqrt(jnp.sum(qf * qf, axis=-1, keepdims=True)
                                     + 1e-6) * D ** -0.5)
            kf = kf * jax.lax.rsqrt(jnp.sum(kf * kf, axis=-1, keepdims=True)
                                    + 1e-6)
        G = jnp.dot(ones_below, g_ref[0, :, lanes],        # [C, Dk] f32
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=f32)
        beta = betas[:, j:j + 1]                           # [C, 1]
        st = state[j]                                      # [Dv, Dk] f32
        # against the entering state: (q * exp(G)) S_0 and (k * exp(G)) S_0
        e_g = jnp.exp(G)
        qk_g = jnp.concatenate([(qf * e_g).astype(mdt),
                                (kf * e_g).astype(mdt)], axis=0)
        qk_s = jax.lax.dot_general(qk_g, st.astype(mdt), nt,
                                   preferred_element_type=f32)  # [2C, Dv]
        y = beta * (vf - qk_s[C:])                         # the solve's right
        # the two matrices, a row block against its own middle
        mids = [G[_SUB * i + _SUB // 2 - 1:_SUB * i + _SUB // 2]
                for i in range(nb)]
        row_fac = jnp.exp(G - jnp.concatenate(
            [jnp.broadcast_to(r, (_SUB, D)) for r in mids], axis=0))
        qg, kg = (qf * row_fac).astype(mdt), (kf * row_fac).astype(mdt)
        rows_qk, rows_kk = [], []
        for i in range(nb):
            block = slice(_SUB * i, _SUB * (i + 1))
            # the columns past the block are masked out below: the clamp
            # only keeps them finite
            kc = (kf * jnp.exp(jnp.minimum(mids[i] - G, _MOST))
                  ).astype(mdt)                            # [C, Dk]
            both = jax.lax.dot_general(
                jnp.concatenate([qg[block], kg[block]], axis=0), kc, nt,
                preferred_element_type=f32)                # [2 SUB, C]
            rows_qk.append(both[:_SUB])
            rows_kk.append(both[_SUB:])
        a_qk = jnp.where(t >= s, jnp.concatenate(rows_qk, axis=0), 0.0)
        a_kk = jnp.where(t > s, beta * jnp.concatenate(rows_kk, axis=0), 0.0)
        # (I + A_kk) v' = y, forward: a block's earlier blocks through the
        # MXU, the block itself column by column
        below = jnp.where(same_block, 0.0, a_kk).astype(mdt)
        solved = []
        for b in range(nb):
            block = slice(_SUB * b, _SUB * (b + 1))
            yb = y[block]                                  # [SUB, Dv]
            if b:
                done = jnp.concatenate(
                    solved + [jnp.zeros((C - _SUB * b, vf.shape[1]), f32)],
                    axis=0).astype(mdt)
                yb = yb - jnp.dot(below[block], done,
                                  preferred_element_type=f32)
            diag = a_kk[block, block]                      # [SUB, SUB]
            for r in range(_SUB - 1):
                yb = yb - diag[:, r:r + 1] * yb[r:r + 1, :]
            solved.append(yb)
        vp = jnp.concatenate(solved, axis=0).astype(mdt)   # v' [C, Dv]
        o = qk_s[:C] + jnp.dot(a_qk.astype(mdt), vp,
                               preferred_element_type=f32)
        o_ref[0, :, lanes] = o.astype(o_ref.dtype)
        g_last = G[C - 1:C]                                # [1, Dk]
        kd = (kf * jnp.exp(g_last - G)).astype(mdt)
        state[j] = (st * jnp.exp(g_last)
                    + jax.lax.dot_general(vp, kd, tn,
                                          preferred_element_type=f32))


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, s0: jax.Array, chunk: int,
                l2_norm: bool = False, lengths: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """``q``, ``k``, ``v`` ``[B, S, H, D]``, ``g [B, S, H, D]`` float32 (the
    log-decay a channel, in ``[G_LOWER_BOUND, 0]``), ``beta [B, S, H]``
    float32, the entering state ``s0 [B, H, D, D]`` float32 -> (``o [B, S,
    H, D]`` in ``q``'s type, the state after the last position ``[B, H, D,
    D]`` float32). ``S`` is whole chunks; key and value heads are one
    width, whole lane tiles. ``l2_norm``: each head's ``q`` and ``k`` are
    brought to unit length here, ``q`` then times ``D ** -0.5``.
    ``lengths [B]`` int32: how many of a row's positions are its own, the
    rest being padding on its right (None: all ``S`` of every row). A chunk
    that starts at or past a row's length is not run and not read: its
    ``o`` is zeros, and the state is the one after the row's last chunk
    that ran, ``s0`` for a row of no position (module docstring)."""
    B, S, H, D = q.shape
    if S % chunk or chunk % _SUB:
        raise ValueError(f"kda_chunked: a length of {S} is not whole chunks "
                         f"of {chunk}, or a chunk is not whole blocks of "
                         f"{_SUB}")
    if D % _LANES or not (k.shape == v.shape == g.shape == q.shape) \
            or beta.shape != (B, S, H) or s0.shape != (B, H, D, D) \
            or (lengths is not None and lengths.shape != (B,)):
        raise ValueError(f"kda_chunked: q{q.shape} k{k.shape} v{v.shape} "
                         f"g{g.shape} beta{beta.shape} s0{s0.shape} "
                         f"lengths{getattr(lengths, 'shape', None)}")
    hb = kda_heads_a_step(H)
    Gr, C, f32 = H // hb, chunk, jnp.float32
    betas = jnp.transpose(beta.astype(f32).reshape(B, S, Gr, hb),
                          (0, 2, 1, 3))                    # [B, Gr, S, hb]
    # a row's live chunks and the last of them, made once, here: the body's
    # test is one compare and an index map one `min` of two scalars (a map
    # is traced again at every lowering, which no compile cache keeps)
    live = (jnp.full((B,), S // C, jnp.int32) if lengths is None
            else (lengths.astype(jnp.int32) + (C - 1)) // C)
    chunks = jnp.stack([live, jnp.maximum(live - 1, 0)])   # [2, B]

    def held(c, i, chunks_ref):
        """Chunk ``c`` of row ``i``, or the row's last live one past it."""
        return jax.lax.min(c, chunks_ref[1, i])

    rows_in = pl.BlockSpec((1, C, hb * D),
                           lambda i, h, c, n: (i, held(c, i, n), h))
    states = pl.BlockSpec((1, hb, D, D), lambda i, h, c, n: (i, h, 0, 0))
    with jax.named_scope(KDA_CHUNK_TRACE_NAME):  # the kernel's alone
        o, sT = pl.pallas_call(
            functools.partial(_kernel, hb=hb, D=D, C=C, l2_norm=l2_norm),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(B, Gr, S // C),
                in_specs=[rows_in, rows_in, rows_in, rows_in,
                          pl.BlockSpec((1, 1, C, hb),
                                       lambda i, h, c, n:
                                       (i, h, held(c, i, n), 0)),
                          states],
                out_specs=[pl.BlockSpec((1, C, hb * D),
                                        lambda i, h, c, n: (i, c, h)),
                           states],
                scratch_shapes=[pltpu.VMEM((hb, D, D), f32)]),
            out_shape=[jax.ShapeDtypeStruct((B, S, H * D), q.dtype),
                       jax.ShapeDtypeStruct((B, H, D, D), f32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=flash_attention._interpret(),
        )(chunks, q.reshape(B, S, H * D), k.reshape(B, S, H * D),
          v.reshape(B, S, H * D), g.astype(f32).reshape(B, S, H * D), betas,
          jnp.swapaxes(s0.astype(f32), 2, 3))
    return o.reshape(B, S, H, D), jnp.swapaxes(sT, 2, 3)
