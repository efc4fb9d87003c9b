"""Flash attention (forward + backward) as Pallas TPU kernels.

Online-softmax blocked attention: the kv axis is the innermost grid dim, and
running (max, sum, acc) state lives in VMEM scratch that persists across the
sequential TPU grid — the classic FlashAttention-2 schedule mapped onto
Pallas. Causal blocks above the diagonal are skipped with ``pl.when`` (zero
MXU work, the DMA still runs; a fused skip via index_map is a later
optimization).

The forward also emits the per-row logsumexp; the backward recomputes block
scores against it in two kernels (dq with kv innermost; dk/dv with q
innermost), so neither pass materializes [S, S] in HBM — this is what makes
flash usable for TRAINING, where the naive vjp through reference attention
would dominate the step at seq >= 2k.

GQA is handled in the index maps throughout: the forward and dq read
kv head = q head // n_rep; the dk/dv kernel's grid walks each kv head's
whole query group (an extra sequential grid dim), accumulating the group's
contributions in VMEM scratch — so GQA models (Llama-3-class) train under
flash instead of falling back to reference attention.

Tiles. ``flash_tiles`` cuts the two lengths into blocks for the forward and
the backward alike, from the lengths, the head's width and
``tile_vmem_bytes`` alone; no argument, field or variable chooses a tile.
On a v5e a grid step costs more in what surrounds its matmuls (the rescale
of the running max, sum and accumulator at every key block, and the step's
own overhead) than in the matmuls, so few large blocks win for as long as
they fit VMEM: the forward at 1152, 8 x 16 heads, takes 5.05 ms at
128 x 128, 1.62 at 384 x 384 and 0.61 as one block. One rule, for every
length: the largest of its divisors (in multiples of 128) whose square tile
fits VMEM. In the forward that is the whole length up to 1152 and
1024 x 1024 at 2048 and 4096; the backward's two kernels share one tile,
hold more a row, and get the whole length up to 1024 and 1024 x 1024 at
2048 and 4096. At training's shape (2 x 32 heads on 8 of 128 at 4096;
PERF.md, PR 34) a layer takes, at 512 x 512 and at 1024 x 1024: forward
6.10 and 3.20 ms, dq 3.85 and 3.48, dk/dv 6.08 and 4.98. No rectangle of
512 and 1024 beats the square by more than 1 %, and with more scoped VMEM
than the default neither 2048 x 2048 (3.41) nor one block of 4096 (3.26)
beats it in the forward: past 1024 the causal skip loses more blocks than
the rescales save. A key length left at 128 by the rule (a prime number of
128s, too long for one block) takes the widest key block that fits beside
its query block: wide key blocks are what saves the rescales. A length
that 128 does not divide is refused.

Head dims. A block's last dim is the head's whole width, so a width on the
128 lanes is whole lane tiles. A head of 64 (LFM2-24B-A2B's) is half a
lane tile: Mosaic takes a block whose last dim is the array's own, pads it
to the lane width in VMEM and runs the same kernel, the two matmuls at half
the MXU's width (``takes_head_dim``; PERF.md, PR 33, has its time beside a
head of 128). ``tile_vmem_bytes`` is told the head's width and stays above
Mosaic's figure there too (``tests/test_flash_tiles_v5e.py``).

Two widths (latent attention's prefill form: DeepSeek-V2's heads of
128 + 64 against values of 128). ``flash_attention_shared_rope`` is the
forward for queries and keys wider than the values, whose width is in two
parts: one a head (``q``, ``k``: ``[B, S, H, D]``) and a rotary one whose
key is ONE row a position shared by every head (``q_rope [B, S, H, R]``,
``k_rope [B, S, R]``). The scores are ``q k^T + q_rope k_rope^T``: two
dots into one float32 tile, the shared key's block read by its own index
map, which has no head in it, so it is never broadcast to the heads in HBM;
nothing is padded (a contraction over 64 is half a lane tile in VMEM, as
the head of 64 above). The softmax scale is an argument (YaRN's is not
``width ** -0.5``). The operands meet the MXU in their own type with a
float32 accumulator, and ``p`` is rounded to the values' type for its dot:
in bf16 that is the MXU's one-pass rate, where the equal-width kernels'
float32 casts take several passes (left as they are: their cells' numbers
and trace names are the parent's). It runs under a scope of its own,
``SHARED_ROPE_TRACE_NAME``, which is what the device trace calls it; the
equal-width path keeps the name it has there. There is no backward at two
widths: differentiating it raises and says so (no cell trains such a
model; the model layer's ``reference`` path differentiates).

Fewer keys than the causal ones (dots3-note-prev's two operators: at two
widths; the window at equal widths too, below). ``window``: a query sees
its last ``window`` keys, and the
innermost grid dim is as long as the most key blocks a query block's
window reaches (two or three at a window of 513), walked from the
window's first block, so the blocks outside it cost neither a matmul nor a
copy nor a grid step (``WINDOW_TRACE_NAME``). ``keep [B, S, S]`` int8: a
choice of keys a query that every head shares, as an indexer makes it; its
block is read beside the keys' and masks the scores
(``SELECTED_TRACE_NAME``). The choice is known when the program runs and
not before, so no block is skipped for it: the kernel computes the causal
blocks whole, and its share of a roofline reckoned over the KEPT pairs
says so. A call that passes neither compiles what it always did.

The window at equal widths (Mellum2-12B-A2.5B's sliding layers: 32 query
heads on 4 key/value heads of 128 under a window of 1024).
``flash_attention_window`` is ``_flash_fwd`` told the window: the same
kernel body with the second test beside the causal one, the same walk of
the window's key blocks from its first (two blocks of 1024 at a window of
1024 whatever the length, where the causal walk is up to eight at 8192),
the key/value head still ``h // n_rep`` in the index map, under a scope of
its own (``EQUAL_WINDOW_TRACE_NAME``). Forward only: differentiating it
raises and says so, as the two-width forward does; ``_dq_kernel`` and
``_dkv_kernel`` see every causal key (ROADMAP M5). ``flash_attention``
itself is the call it was.

The choice at equal widths (Keye-VL-2.0-30B-A3B's layers: 32 query heads
on 4 key/value heads of 128, a query attending the 2048 keys its indexer
chose; PR 62). ``flash_attention_selected`` is ``_flash_fwd`` handed ``keep
[B, S, S]`` int8: the same kernel body with the choice's ``[block_q,
block_k]`` block riding in beside the keys' (no head in its index map: the
choice is a position's, one for all its heads, and the eight query heads of
a group each fetch it again) and masking the scores before the running
max; told the rows' lengths it skips the blocks past a row's end as every
forward does, and it skips no block for the choice (known when the program
runs and not before: the causal half is computed whole, and a share of a
roofline reckoned over the KEPT pairs says so). The tiles are the plain
rule's. Under a scope of its own, ``EQUAL_SELECTED_TRACE_NAME``
(``flash_fwd_chosen``; the two-width forward under a choice is
``SELECTED_TRACE_NAME``, ``flash_fwd_selected``). Forward only, and no
logsumexp: differentiating it raises and says so.

The window's one step (Laguna-XS.2's sliding layers: 64 query heads on 8
of 128 under a window of 512, HALF the plain tile; PR 61). What a grid step
of ``_fwd_kernel`` costs on a v5e goes by its QUERY rows far more than by
its scores: some 3.2 ns a query row a step whatever the key block's length
(the running max and sum are reduced across lanes and broadcast back, and
the accumulator rescaled, a row at a time) beside 0.5 us a step and 0.6 us
for the scores of 1024 x 768 more keys; so the walk costs its query rows
times the key blocks each visits, and under a window narrower than the
tile a block of 1024 queries visits two blocks of 1024 keys whatever the
window: blocks of 512 x 512, a quarter of the scores a step, are 12 %
SLOWER than 1024 x 1024, and the best rectangle, 512 x 1024, 6 % faster
(PERF.md section 5, PR 61, has the sweep). What a narrow window allows is
to visit each query row ONCE: a query block's keys are its own and the
``tail`` before them that its first query sees, ``window - 1`` rounded up
to a divisor of the block. ``window_step`` says where that holds, by the
length, the window and the head's width alone: the query block is the
shortest divisor of the length from 512 up that has such a divisor and
fits VMEM beside its keys (``tile_vmem_bytes`` at ``block_q`` x ``block_q
+ tail``); not where the plain rule's keys are one block (the walk is one
step a block there), and not where no block holds the tail beside it
(Mellum2's window of 1024: the walk above, its programs the parent's).
There ``_flash_fwd`` runs ``_window_step_kernel`` on a grid of (row, head,
query block) with NO key dim: the keys and values ride in twice, cut into
blocks of ``tail`` and into the query blocks' own, the step joins the two
in VMEM, and its softmax is the plain one over one float32 tile, with
nothing carried and nothing rescaled: the same scores in float32, the same
mask (``q - k < window``), the same keys for every query, under the same
scope. Laguna's window runs at 512 queries over 512 + 512 keys from 2048 to
6144: 4 x 6144 x 64 heads in 7.3 ms where the walk took 14.0. Told the
rows' lengths, a query block past its row's live ones is written as zeros
and fetches nothing (below).

The rows' lengths, in every forward (``lengths [B]`` int32: the two-width
forward since PR 54, the equal-width one, full or under its window, since
PR 56). A batch's rows are padded on the right to one length, and a block
whose first position lies past its row's end holds nothing of the row. The
kernel is told how many query blocks and how many key blocks of each row
hold a position of its own (``_live_blocks``: ``ceil(length / block)`` and
the last such block's index, for either, four int32 a row, prefetched into
SMEM before the grid runs), and a grid step at a block past them computes
nothing (``_both_live``): a dead query block's ``o`` is written as ZEROS (a
padded position's output goes on into ``W_o`` and the next layers' dense
matmuls, and what nobody wrote may be a NaN). The index maps
(``_block_maps``, one copy for both forwards, ``lax`` alone) hold such a
step's operands at the row's last live blocks, which are in VMEM already,
so nothing is fetched for it either. The equal-width forward goes three
steps further, because where a length has ONE block a dead step is a dead
(row, head) and what it costs is what surrounds the matmuls (PERF.md
section 6, PR 56: 2.3 us as above, 0.7 so): told the lengths it makes no
``lse`` (the call is the forward's alone, and the logsumexp, twice the
bytes of ``o``, is the backward's to read), a dead query block is neither
begun nor finished (no scratch set, no division: the zeros' store alone),
and an empty row's steps all name its first head's first blocks, one fetch
a row where each head's own would be one a head. The keys are causal, so
every key skipped was masked for every query of the row's own and a masked
score adds an exact 0: the rows' own
outputs are the same to the bit; the padded queries inside a row's last
live block are run like any other, as they were when the kernel knew no
lengths. With ``None`` every row is whole and the call is the one it was:
no operand, no test, no ``min`` (``flash_attention``'s forward and backward
as training runs them are the parent's program to the character, down to
the ``//`` in the key head's index map, which told the lengths is
``lax.div``). Lengths are a prefill's (causal, the queries' positions the
keys') and the forward's alone: differentiating a call that has them
raises. ``causal_blocks`` counts, on the host and by the kernels' own rule,
the grid steps a forward computes with and without them (PERF.md section
6, PRs 54 and 56, has the times).
"""

from __future__ import annotations

import contextlib
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret() -> bool:
    """Compile for the TPU; interpret on the CPU platform, which a caller
    gets only by asking for it (JAX_PLATFORMS=cpu — the tests). Any other
    platform has no path here and is an error, not an interpreted run."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"flash attention runs on the tpu platform (compiled) or the cpu "
        f"platform (interpreted), not {platform!r}")


_LANES = 128
# Scoped VMEM a v5e kernel gets by default; nothing here asks for more.
VMEM_LIMIT_BYTES = 16 * 2 ** 20


def takes_head_dim(head_dim: int, value_dim: Optional[int] = None, *,
                   shared_dim: int = 0) -> bool:
    """Whether the compiled kernels have run at these widths. ``head_dim``
    is the queries' and keys' whole width, ``value_dim`` the values' (the
    same when not given) and ``shared_dim`` the trailing part of
    ``head_dim`` whose key is one row a position shared by the heads. Equal
    widths with nothing shared: whole lane tiles, or the 64 of the module
    docstring. Two widths: the forward alone
    (``flash_attention_shared_rope``), the head's own part and the values
    whole lane tiles and the shared part whole lane tiles or 64.
    ``attention``'s ``auto`` asks; the interpreted kernels take any width."""
    value_dim = head_dim if value_dim is None else value_dim
    if not shared_dim:
        return value_dim == head_dim and (head_dim % _LANES == 0
                                          or head_dim == 64)
    own = head_dim - shared_dim
    return (own > 0 and own % _LANES == 0 and value_dim % _LANES == 0
            and (shared_dim % _LANES == 0 or shared_dim == 64))


def tile_vmem_bytes(block_q: int, block_k: int, *, head_dim: int = 128,
                    value_dim: Optional[int] = None,
                    backward: bool = False) -> int:
    """Upper reckoning of the VMEM one grid step holds at a tile, every
    element taken at 4 bytes: each block of an operand or a result in its
    two pipeline buffers, the float32 casts and the scratch once, and one
    ``[block_q, block_k]`` float32 tile for the scores and what is made of
    them (Mosaic works through ``s``, ``p``, ``dp`` and ``ds`` in strips
    and holds less than one such tile). The backward's figure is the
    larger of its two kernels'. Mosaic reports less at every tile tried
    (``tests/test_flash_tiles_v5e.py`` compiles for a described v5e): at
    1024 x 1024, 10.0 MB of the 13.1 reckoned in the forward, 9.8 (dq)
    and 11.1 (dk/dv) of 15.7 in the backward. ``value_dim`` is the values'
    width where it is not the queries' and keys' ``head_dim`` (the forward
    alone; a part of ``head_dim`` that is half a lane tile counts as the
    whole tile it fills in VMEM)."""
    d, lanes = 4 * head_dim, 4 * _LANES
    tile = 4 * block_q * block_k
    if not backward:
        # a query row: q twice and its cast (3 d), o twice, acc and its
        # rescaled copy (4 dv); lse twice, m and l (4 lanes). A key row:
        # k and v twice, their casts
        dv = d if value_dim is None else 4 * value_dim
        return (tile + block_q * (3 * d + 4 * dv + 4 * lanes)
                + block_k * 3 * (d + dv))
    if value_dim not in (None, head_dim):
        raise ValueError("the backward kernels take one width")
    # dq. A query row: q, do and dq twice, the casts of q and do, dq's
    # scratch (9 d); lse and delta twice (4 lanes). A key row: k and v
    # twice, their casts
    dq = block_q * (9 * d + 4 * lanes) + block_k * 6 * d
    # dk/dv. A query row: q and do twice, their casts; lse and delta twice.
    # A key row: k, v, dk and dv twice, the casts of k and v, dk's and
    # dv's scratch
    dkv = block_q * (6 * d + 4 * lanes) + block_k * 12 * d
    return tile + max(dq, dkv)


def _divisors(n: int) -> List[int]:
    """The multiples of 128 that divide ``n``, ascending."""
    return [b for b in range(_LANES, n + 1, _LANES) if n % b == 0]


def flash_tiles(sq: int, skv: int, *, head_dim: int = 128,
                value_dim: Optional[int] = None,
                backward: bool = False) -> Tuple[int, int]:
    """``(block_q, block_k)`` for query and key lengths: the module
    docstring's rule. Pure: the lengths, the widths and which pass holds
    the tile are all it reads."""
    if sq % _LANES or skv % _LANES:
        raise ValueError(f"seq lens ({sq},{skv}) must divide by 128")

    def fits(bq: int, bk: int) -> bool:
        return tile_vmem_bytes(bq, bk, head_dim=head_dim,
                               value_dim=value_dim,
                               backward=backward) <= VMEM_LIMIT_BYTES

    def block(n: int) -> int:
        return max(b for b in _divisors(n) if fits(b, b))

    block_q, block_k = block(sq), block(skv)
    if block_k == _LANES < skv:
        block_k = max(b for b in _divisors(skv) if fits(block_q, b))
    return block_q, block_k


# The shortest query block the window's step takes: under it a step's own
# cost outweighs the scores a shorter block saves (PERF.md section 5, PR 61).
_WINDOW_QUERY_FLOOR = 512


def window_step(seq: int, window: Optional[int], *, head_dim: int = 128
                ) -> Optional[Tuple[int, int]]:
    """``(block_q, tail)`` where the equal-width forward under ``window``
    at a prefill of ``seq`` runs ONE grid step a query block, over the
    block's own keys and the ``tail`` keys before them (module docstring:
    the window's one step): the shortest divisor of ``seq`` from 512 up that
    has a divisor of its own no shorter than ``window - 1``, the tail, and
    fits VMEM with it. None where it walks key blocks as the causal forward
    does: with no window, where the plain rule's keys are one block (the
    walk is one step a block already), or where no query block holds the
    window's tail beside it. Pure: the length, the window and the head's
    width."""
    if window is None or flash_tiles(seq, seq, head_dim=head_dim)[1] == seq:
        return None
    for block_q in _divisors(seq):
        if block_q < _WINDOW_QUERY_FLOOR:
            continue
        tail = next((t for t in _divisors(block_q) if t >= window - 1), None)
        if tail and tile_vmem_bytes(block_q, block_q + tail,
                                    head_dim=head_dim) <= VMEM_LIMIT_BYTES:
            return block_q, tail
    return None


def _fwd_kernel(*refs, scale: float, causal: bool, block_q: int,
                block_k: int, window: Optional[int] = None,
                told: bool = False, selected: bool = False):
    """With ``window`` the innermost grid dim walks the key blocks that the
    query block's window reaches, from the window's first
    (``_first_key_block``), and a query sees its last ``window`` keys; a
    row of a block in which it sees none fills with ``exp(0)`` and the
    first block in which it sees one rescales that away, as in
    ``_fwd_shared_rope_kernel``. With ``told`` a first operand rides in
    front, ``blocks_ref [4, B]`` (``_live_blocks``), and there is no
    ``lse_ref`` (told the lengths the call is the forward's alone, and the
    logsumexp is the backward's to read): a step at a block past its row's
    live ones computes nothing, and a query block past them is neither
    begun nor finished, its ``o`` written as zeros. With ``selected`` a
    block of ``keep [B, S, S_kv]`` (int8) rides in after the values and
    masks the scores before the running max, every head alike, as in
    ``_fwd_shared_rope_kernel``: a query's row of a block in which its
    choice holds no key fills with ``exp(0)``, and the first block in which
    it holds one rescales that away (every query's choice holds a key
    somewhere)."""
    refs = list(refs)
    blocks_ref = refs.pop(0) if told else None
    keep_ref = refs.pop(3) if selected else None
    q_ref, k_ref, v_ref, o_ref, *lse_ref, m_scr, l_scr, acc_scr = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)
    if told:  # a dead query block costs its zeros' store and no more
        live = jax.lax.lt(iq, blocks_ref[0, pl.program_id(0)])

        @pl.when(jax.lax.bitwise_and(ik == nk - 1,
                                     jax.lax.bitwise_not(live)))
        def _dead():
            o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])

    def of_a_live_block(edge):
        """At the walk's first or last step, of a query block that holds
        a position of its row's own (told no lengths: every one does)."""
        return jax.lax.bitwise_and(edge, live) if told else edge

    @pl.when(of_a_live_block(ik == 0))
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # the key block this step is at: under a window the walk starts at the
    # window's first block
    at = ik if window is None else ik + _first_key_block(
        iq, block_q, block_k, window)
    # Skip fully-masked blocks (strictly above the causal diagonal).
    run = True
    if causal:
        run = at * block_k <= iq * block_q + block_q - 1
    if told:
        run = _both_live(run, blocks_ref, iq, at)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = at * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = q_pos >= k_pos
            if window is not None:
                seen = seen & (q_pos - k_pos < window)
            s = jnp.where(seen, s, _NEG_INF)
        if selected:
            s = jnp.where(keep_ref[0].astype(jnp.int32) != 0, s, _NEG_INF)
        m_prev = m_scr[:, :1]                        # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)              # [bq, 1]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(of_a_live_block(ik == nk - 1))
    def _finalize():
        l = l_scr[:, :1]
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # logsumexp row stats for the backward (lse layout [bq, 128]: the
        # row value broadcast across lanes — keeps stores 2D/tiled)
        for ref in lse_ref:
            ref[0, 0] = jnp.broadcast_to(
                m_scr[:, :1] + jnp.log(jnp.maximum(l, 1e-30)),
                ref[0, 0].shape)


def _flash_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
               causal: bool, window: Optional[int] = None,
               lengths: Optional[jax.Array] = None,
               keep: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """q [B,H,S,D], k/v [B,KVH,S,D] → (o [B,H,S,D], lse [B,H,S,128]).
    ``window``: a query sees its last ``window`` keys, and the innermost
    grid dim is as long as the most key blocks a query block's window
    reaches (module docstring). ``lengths [B]`` int32: the right-padded
    rows' own lengths, past which no block is computed (module docstring:
    ``o`` is zeros there, and ``lse`` is None: no backward reads it).
    ``keep [B,S,S]`` int8: a choice of keys a query, every head's (module
    docstring: the choice at equal widths). None, each, is the call it
    always was."""
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    n_rep = H // KVH
    scale = D ** -0.5
    if (window is not None or lengths is not None or keep is not None) \
            and not (causal and Sq == Skv):
        raise ValueError("a window, a choice of keys or the rows' lengths "
                         "are a prefill's: causal, the queries' positions "
                         "the keys'")
    if lengths is not None and lengths.shape != (B,):
        raise ValueError(f"lengths{lengths.shape} for {B} rows")
    if keep is not None and (window is not None
                             or keep.shape != (B, Sq, Skv)):
        raise ValueError(f"keep{keep.shape}: a choice is [rows, queries, "
                         "keys] and comes without a window")
    step = window_step(Sq, window, head_dim=D)
    if step is not None:
        return _flash_fwd_window_step(q, k, v, window, lengths, *step), None
    block_q, block_k = flash_tiles(Sq, Skv, head_dim=D)
    key_blocks, told = Skv // block_k, {}
    if window is not None:
        key_blocks = _window_key_blocks(Sq, block_q, block_k, window)
        told["window"] = window
    # no lengths, no operand: the call is the one it was
    prefetched = []
    if lengths is not None:
        prefetched = [_live_blocks(lengths, block_q, block_k)]
        told["told"] = True
    query_block, key_block = _block_maps(block_q, block_k, window)

    def kv_head(h):
        # told the lengths the maps hold `lax` alone; the call that is not
        # told keeps the text it has, whose `//` is a `jit` in the map
        return h // n_rep if lengths is None else jax.lax.div(h, n_rep)

    def rows(b, h, iq, ik, *n):
        return (b, _row_head(b, h, *n), query_block(b, iq, *n), 0)

    def keys(b, h, iq, ik, *n):
        return (b, kv_head(_row_head(b, h, *n)),
                key_block(b, iq, ik, *n), 0)

    # the choice: a block of it beside the keys', no head in its index
    chosen, chosen_specs = [], []
    if keep is not None:
        told["selected"] = True
        chosen = [keep]
        chosen_specs = [pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, h, iq, ik, *n: (b, query_block(b, iq, *n),
                                      key_block(b, iq, ik, *n)))]
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, **told)
    # the windowed call and the one under a choice each under a scope of
    # its own, which is what the device trace then calls it; the other
    # keeps its caller's
    scope = (jax.named_scope(EQUAL_WINDOW_TRACE_NAME) if window is not None
             else jax.named_scope(EQUAL_SELECTED_TRACE_NAME)
             if keep is not None else contextlib.nullcontext())
    # every block of a result is written, a dead one of `o` with zeros;
    # told the lengths, or under a choice, there is `o` alone
    forward_alone = lengths is not None or keep is not None
    results = [(D, q.dtype)] if forward_alone else [
        (D, q.dtype), (128, jnp.float32)]
    with scope:
        o, *lse = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetched),
                grid=(B, H, Sq // block_q, key_blocks),
                in_specs=[
                    pl.BlockSpec((1, 1, block_q, D), rows),
                    pl.BlockSpec((1, 1, block_k, D), keys),
                    pl.BlockSpec((1, 1, block_k, D), keys),
                    *chosen_specs,
                ],
                out_specs=[
                    pl.BlockSpec((1, 1, block_q, width),
                                 lambda b, h, iq, ik, *n: (b, h, iq, 0))
                    for width, _ in results],
                scratch_shapes=[
                    pltpu.VMEM((block_q, 128), jnp.float32),   # running max
                    pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
                    pltpu.VMEM((block_q, D), jnp.float32),     # accumulator
                ]),
            out_shape=[jax.ShapeDtypeStruct((B, H, Sq, width), dtype)
                       for width, dtype in results],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=_interpret(),
        )(*prefetched, q, k, v, *chosen)
    return o, (lse[0] if lse else None)


def _window_step_kernel(*refs, scale: float, window: int, block_q: int,
                        tail: int, told: bool = False):
    """One grid step a query block under a window no longer than ``tail +
    1``: the scores of the block's ``block_q`` queries over the ``tail``
    keys before the block (``kt_ref``, ``vt_ref``) and the block's own
    (``k_ref``, ``v_ref``), side by side in one float32 tile, a plain
    softmax over it (every key a query sees is in the tile, so there is no
    running max, sum or accumulator to carry and none to rescale), and its
    dot with the values. The row's first block has no keys before it: its
    tail is the block's own first keys again (the index map's clamp),
    masked as positions below 0. With ``told`` a first operand rides in
    front, ``blocks_ref [4, B]`` (``_live_blocks``): a query block past its
    row's live ones is written as zeros and computes nothing."""
    blocks_ref = None
    if told:
        blocks_ref, *refs = refs
    q_ref, kt_ref, vt_ref, k_ref, v_ref, o_ref = refs
    iq = pl.program_id(2)

    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [bq, d]
        k = jnp.concatenate([kt_ref[0, 0], k_ref[0, 0]],
                            axis=0).astype(jnp.float32)  # [tail + bq, d]
        v = jnp.concatenate([vt_ref[0, 0], v_ref[0, 0]],
                            axis=0).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, tail + bq]
        q_pos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        k_pos = iq * block_q - tail + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        seen = (q_pos >= k_pos) & (q_pos - k_pos < window) & (k_pos >= 0)
        s = jnp.where(seen, s, _NEG_INF)
        p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
        # a query sees its own key: the sum is at least 1
        o_ref[0, 0] = (jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
            / jnp.sum(p, axis=-1, keepdims=True)).astype(o_ref.dtype)

    if not told:
        _compute()
        return
    live = jax.lax.lt(iq, blocks_ref[0, pl.program_id(0)])
    pl.when(live)(_compute)

    @pl.when(jax.lax.bitwise_not(live))
    def _dead():
        o_ref[0, 0] = jnp.zeros_like(o_ref[0, 0])


def _flash_fwd_window_step(q: jax.Array, k: jax.Array, v: jax.Array,
                           window: int, lengths: Optional[jax.Array],
                           block_q: int, tail: int) -> jax.Array:
    """``_flash_fwd`` under a window at ``window_step``'s ``(block_q,
    tail)``: q [B,H,S,D], k/v [B,KVH,S,D] → o [B,H,S,D], a grid of (row,
    head, query block) and no key dim. The keys and the values are each
    passed twice, once cut into blocks of ``tail`` (a query block's tail is
    the block of them that ends where it begins) and once into the query
    blocks' own. Told the rows' lengths, a step past a row's last live
    query block names that block's operands again, an empty row's its
    first head's, and fetches nothing (``_block_maps``' way)."""
    B, H, S, D = q.shape
    n_rep = H // k.shape[1]
    prefetched = [] if lengths is None else [
        _live_blocks(lengths, block_q, block_q)]
    block, _ = _block_maps(block_q, block_q, None)

    def rows(b, h, iq, *n):
        return (b, _row_head(b, h, *n), block(b, iq, *n), 0)

    def own(b, h, iq, *n):
        return (b, jax.lax.div(_row_head(b, h, *n), n_rep),
                block(b, iq, *n), 0)

    def before(b, h, iq, *n):
        return (b, jax.lax.div(_row_head(b, h, *n), n_rep), jax.lax.max(
            block(b, iq, *n) * (block_q // tail) - 1, 0), 0)

    kernel = functools.partial(
        _window_step_kernel, scale=D ** -0.5, window=window,
        block_q=block_q, tail=tail, told=lengths is not None)
    with jax.named_scope(EQUAL_WINDOW_TRACE_NAME):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetched),
                grid=(B, H, S // block_q),
                in_specs=[
                    pl.BlockSpec((1, 1, block_q, D), rows),
                    pl.BlockSpec((1, 1, tail, D), before),
                    pl.BlockSpec((1, 1, tail, D), before),
                    pl.BlockSpec((1, 1, block_q, D), own),
                    pl.BlockSpec((1, 1, block_q, D), own),
                ],
                out_specs=pl.BlockSpec(
                    (1, 1, block_q, D),
                    lambda b, h, iq, *n: (b, h, iq, 0))),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=_interpret(),
        )(*prefetched, q, k, v, k, v)


# ----------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale: float, causal: bool, block_q: int,
               block_k: int):
    """Grid (B, H, iq, ik): kv innermost, accumulate dq for one q block."""
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = ik * block_k <= iq * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)                 # [bk, d]
        do = do_ref[0, 0].astype(jnp.float32)               # [bq, d]
        lse = lse_ref[0, 0][:, :1]                          # [bq, 1]
        delta = delta_ref[0, 0][:, :1]                      # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                                # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        ds = p * (dp - delta) * scale                       # [bq, bk]
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, d]

    @pl.when(ik == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, block_q: int, block_k: int):
    """Grid (B, KVH, ik, r, iq): q-head-in-group then q blocks innermost,
    accumulating dk/dv for one kv block across the WHOLE q-head group —
    this is the GQA backward (n_rep > 1): each kv head's gradient sums
    contributions from its n_rep query heads (VERDICT r2 item 6)."""
    ik = pl.program_id(2)
    r = pl.program_id(3)
    iq = pl.program_id(4)
    n_rep = pl.num_programs(3)
    nq = pl.num_programs(4)

    @pl.when(jnp.logical_and(r == 0, iq == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:  # block needed iff some q row >= first k row
        run = iq * block_q + block_q - 1 >= ik * block_k

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                 # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)                 # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)                 # [bk, d]
        do = do_ref[0, 0].astype(jnp.float32)               # [bq, d]
        lse = lse_ref[0, 0][:, :1]                          # [bq, 1]
        delta = delta_ref[0, 0][:, :1]                      # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [bq, bk]
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)
        p = jnp.exp(s - lse)                                # [bq, bk]
        # dv += p^T do
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        ds = p * (dp - delta) * scale                       # [bq, bk]
        # dk += ds^T q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]

    @pl.when(jnp.logical_and(r == n_rep - 1, iq == nq - 1))
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, *, causal: bool):
    """q/o/do [B,H,Sq,D], k/v [B,KVH,Skv,D] (lse [B,H,Sq,128]); returns
    (dq [B,H,Sq,D], dk/dv [B,KVH,Skv,D]). GQA (KVH < H) is handled in the
    index maps: dq reads kv head h//n_rep; dk/dv accumulate across the
    n_rep query heads of their group inside the kernel grid."""
    B, H, Sq, D = q.shape
    KVH, Skv = k.shape[1], k.shape[2]
    n_rep = H // KVH
    scale = D ** -0.5
    block_q, block_k = flash_tiles(Sq, Skv, head_dim=D, backward=True)

    # delta_i = rowsum(dO_i * O_i) — cheap elementwise, stays in XLA;
    # broadcast across 128 lanes to match the lse layout
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, 128))

    q_spec = pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 128),
                            lambda b, h, i, j: (b, h, i, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(B, H, Sq // block_q, Skv // block_k),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // n_rep, j, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // n_rep, j, 0)),
            q_spec, row_spec, row_spec,
        ],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    # dk/dv: grid (B, KVH, ik, r, iq) — r walks the kv head's query group
    kv_spec = pl.BlockSpec((1, 1, block_k, D),
                           lambda b, hk, i, r, j: (b, hk, i, 0))
    qg_spec = pl.BlockSpec((1, 1, block_q, D),
                           lambda b, hk, i, r, j: (b, hk * n_rep + r, j, 0))
    qg_row = pl.BlockSpec((1, 1, block_q, 128),
                          lambda b, hk, i, r, j: (b, hk * n_rep + r, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=(B, KVH, Skv // block_k, n_rep, Sq // block_q),
        in_specs=[qg_spec, kv_spec, kv_spec, qg_spec, qg_row, qg_row],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, KVH, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B, KVH, Skv, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary", "arbitrary")),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# Kernel takes [B,H,S,D]; public API is [B,S,H,D] to match ops.attention.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True,
                    lengths: Optional[jax.Array] = None) -> jax.Array:
    """``lengths [B]`` int32, where given, are the right-padded rows' own
    lengths (module docstring): a prefill's and the forward's alone. None
    is the call it always was, forward and backward."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o, _ = _flash_fwd(qt, kt, vt, causal=causal, lengths=lengths)
    return jnp.swapaxes(o, 1, 2)


# The forward's two results as a remat policy may name them
# (``jax.checkpoint_policies.save_only_these_names``): a ``pallas_call`` is
# no ``dot_general``, so a policy that keeps matmul outputs drops them, and
# the backward then runs the whole forward again to have them.
OUT_RESIDUAL_NAME = "flash_fwd_out"
LSE_RESIDUAL_NAME = "flash_fwd_lse"


def _fa_fwd(q, k, v, causal, lengths):
    # traced under differentiation alone: the primal above, which serving
    # traces, carries no name
    if lengths is not None:
        raise NotImplementedError(
            "the equal-width flash forward told its rows' lengths has no "
            "backward (what lies past a row's end is zeros, not a forward "
            "pass's): differentiate the call without them")
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o, lse = _flash_fwd(qt, kt, vt, causal=causal)
    o = checkpoint_name(o, OUT_RESIDUAL_NAME)
    lse = checkpoint_name(lse, LSE_RESIDUAL_NAME)
    return jnp.swapaxes(o, 1, 2), (qt, kt, vt, o, lse)


def _fa_bwd(causal, res, g):
    qt, kt, vt, o, lse = res
    do = jnp.swapaxes(g, 1, 2)
    dq, dk, dv = _flash_bwd(qt, kt, vt, o, lse, do, causal=causal)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2), None)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


# What the device trace calls the equal-width forward under a window (a
# Pallas call's HLO instruction takes the name of its innermost named
# scope): the full layers' call beside it keeps its caller's name.
EQUAL_WINDOW_TRACE_NAME = "flash_fwd_sliding"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention_window(q: jax.Array, k: jax.Array, v: jax.Array,
                           window: int,
                           lengths: Optional[jax.Array] = None) -> jax.Array:
    """``flash_attention``'s forward under a window (module docstring): q
    ``[B, S, H, D]``, k, v ``[B, S, KVH, D]`` → ``[B, S, H, D]``; causal,
    query ``t`` sees keys ``t - window + 1 .. t``; ``lengths [B]`` int32
    or None as ``flash_attention``'s. Forward only."""
    o, _ = _flash_fwd(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                      jnp.swapaxes(v, 1, 2), causal=True, window=window,
                      lengths=lengths)
    return jnp.swapaxes(o, 1, 2)


def _fa_window_fwd(q, k, v, window, lengths):
    return flash_attention_window(q, k, v, window, lengths), None


def _fa_window_bwd(window, res, g):
    raise NotImplementedError(
        "the equal-width flash forward under a window has no backward "
        "(`_dq_kernel` and `_dkv_kernel` see every causal key): train such "
        "a model with attn_impl='reference'")


flash_attention_window.defvjp(_fa_window_fwd, _fa_window_bwd)


# What the device trace calls the equal-width forward under a choice of
# keys (the two-width forward's under one is ``SELECTED_TRACE_NAME``,
# ``flash_fwd_selected``: a name each, so that one trace tells them apart).
EQUAL_SELECTED_TRACE_NAME = "flash_fwd_chosen"


@jax.custom_vjp
def flash_attention_selected(q: jax.Array, k: jax.Array, v: jax.Array,
                             keep: jax.Array,
                             lengths: Optional[jax.Array] = None
                             ) -> jax.Array:
    """``flash_attention``'s forward under a choice of keys (module
    docstring): q ``[B, S, H, D]``, k, v ``[B, S, KVH, D]``, keep ``[B, S,
    S]`` int8 → ``[B, S, H, D]``; causal, query ``t`` of row ``b`` sees key
    ``s`` where ``keep[b, t, s]`` is not 0, every head alike; ``lengths
    [B]`` int32 or None as ``flash_attention``'s. Forward only."""
    o, _ = _flash_fwd(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                      jnp.swapaxes(v, 1, 2), causal=True, lengths=lengths,
                      keep=keep)
    return jnp.swapaxes(o, 1, 2)


def _fa_selected_fwd(q, k, v, keep, lengths):
    return flash_attention_selected(q, k, v, keep, lengths), None


def _fa_selected_bwd(res, g):
    raise NotImplementedError(
        "the equal-width flash forward under a choice of keys has no "
        "backward (`_dq_kernel` and `_dkv_kernel` see every causal key): "
        "train such a model with attn_impl='reference'")


flash_attention_selected.defvjp(_fa_selected_fwd, _fa_selected_bwd)


# ------------------------------------------- two widths, a shared rotary key

# What the device trace calls the kernel: a Pallas call's HLO instruction
# takes the name of its innermost named scope
# (``tpu_custom_call:<this>.N``). The equal-width kernels have no scope of
# their own and take their caller's: a serving step's forward is
# ``tpu_custom_call:checkpoint.N`` (a run of layers under ``jax.checkpoint``),
# and in a training step under ``remat_policy="dots"`` the forward is
# ``closed_call.N``, dq and dk/dv ``checkpoint.N`` (PERF.md, PR 48: one
# forward a layer; a policy that drops the forward's results runs it again
# as ``rematted_computation.N``).
SHARED_ROPE_TRACE_NAME = "flash_fwd_shared_rope"


# The same forward under fewer keys, by what limits them: a name each in
# the device trace.
WINDOW_TRACE_NAME = "flash_fwd_window"
SELECTED_TRACE_NAME = "flash_fwd_selected"


def _window_key_blocks(sq: int, block_q: int, block_k: int,
                       window: int) -> int:
    """The most key blocks any query block's window reaches: queries
    ``iq * block_q`` on see keys from ``iq * block_q - window + 1`` (block
    ``_first_key_block``) to the block's last query's own."""
    def first(iq):
        return max(iq * block_q - window + 1, 0) // block_k

    return max((iq * block_q + block_q - 1) // block_k - first(iq) + 1
               for iq in range(sq // block_q))


def _first_key_block(iq, block_q: int, block_k: int, window: int):
    """The first key block a query block's window reaches (``iq`` traced)."""
    return jax.lax.div(jax.lax.max(iq * block_q - (window - 1), 0), block_k)


@functools.cache
def _causal_steps(seq: int, block_q: int, block_k: int,
                  window: Optional[int]):
    """``(iq [n], at [n])``: the (query block, key block) of each grid step
    at or under the diagonal of a forward at a prefill of ``seq`` and these
    tiles, under a window its walk's: the ones computed for a whole row."""
    key_blocks = (seq // block_k if window is None else
                  _window_key_blocks(seq, block_q, block_k, window))
    steps = [(iq, at) for iq in range(seq // block_q)
             for at in range(
                 0 if window is None
                 else max(iq * block_q - window + 1, 0) // block_k,
                 seq // block_k)[:key_blocks]
             if at * block_k <= iq * block_q + block_q - 1]
    iq, at = np.asarray(steps, np.int64).T
    return iq, at


def causal_blocks(seq: int, lengths, tiles: Tuple[int, int],
                  window: Optional[int] = None) -> Tuple[int, int]:
    """What the rows' lengths are worth to a forward at a prefill of
    ``seq`` and ``tiles`` (``(block_q, block_k)``), a head: ``(run,
    live)``, the grid steps at or under the diagonal (inside the window's
    walk) over every row of ``lengths [B]`` (numpy), which are the ones
    computed when every row is whole, and those among them whose query
    block and key block both hold a position of their row's own, which are
    the ones computed when the kernel is told the lengths (module
    docstring). Host arithmetic, by the kernels' own rule (``_both_live``),
    for either forward."""
    block_q, block_k = tiles
    iq, at = _causal_steps(seq, block_q, block_k, window)
    lengths = np.asarray(lengths, np.int64)[:, None]
    live = ((iq < -(-lengths // block_q)) & (at < -(-lengths // block_k)))
    return len(lengths) * len(iq), int(live.sum())


def equal_width_blocks(seq: int, lengths, *, head_dim: int,
                       window: Optional[int] = None) -> Tuple[int, int]:
    """``causal_blocks`` by the equal-width forward's own rule: at
    ``flash_tiles``' tiles, the window's walk among them; and where the
    window runs one step a query block (``window_step``), the query blocks
    over every row and those among them that hold a position of their
    row's own."""
    step = window_step(seq, window, head_dim=head_dim)
    if step is None:
        return causal_blocks(seq, lengths, flash_tiles(
            seq, seq, head_dim=head_dim), window)
    lengths = np.asarray(lengths, np.int64)
    return len(lengths) * (seq // step[0]), int((-(-lengths // step[0])).sum())


def shared_rope_blocks(seq: int, lengths, *, head_dim: int, rope_dim: int,
                       value_dim: int, window: Optional[int] = None
                       ) -> Tuple[int, int]:
    """``causal_blocks`` at the two-width forward's tiles: the head's own
    width beside a rotary part that pads to the lane width in VMEM."""
    padded = head_dim + -(-rope_dim // _LANES) * _LANES
    return causal_blocks(seq, lengths, flash_tiles(
        seq, seq, head_dim=padded, value_dim=value_dim), window)


def _live_blocks(lengths: jax.Array, block_q: int, block_k: int
                 ) -> jax.Array:
    """``[4, B]`` int32 of ``lengths [B]``: a row's live query blocks and
    the last of them, then the same two of its key blocks. Made once,
    outside the call and by ``lax`` alone, so that the body's test is two
    compares and an index map a ``min`` and a ``select`` of scalars: a
    ``jnp`` call on a tracer is a jaxpr traced, and a map is traced again
    at every lowering, which no compile cache keeps."""
    n = jax.lax.convert_element_type(lengths, jnp.int32)
    rows = []
    for block in (block_q, block_k):
        live = jax.lax.div(jax.lax.add(n, block - 1), block)
        rows += [live, jax.lax.max(jax.lax.sub(live, 1), 0)]
    return jax.lax.concatenate([row.reshape(1, -1) for row in rows], 0)


def _both_live(run, blocks_ref, iq, at):
    """``run``, and neither the step's query block ``iq`` nor its key block
    ``at`` past its row's end (``blocks_ref``: ``_live_blocks``, in SMEM;
    lengths are causal's): two compares of scalars."""
    row = pl.program_id(0)
    return jax.lax.bitwise_and(run, jax.lax.bitwise_and(
        jax.lax.lt(iq, blocks_ref[0, row]),
        jax.lax.lt(at, blocks_ref[2, row])))


def _row_head(b, h, *blocks_ref):
    """Head ``h`` of row ``b`` in an equal-width forward's index map, or,
    told the rows' lengths (``blocks_ref``: ``_live_blocks``), an empty
    row's first head whatever ``h``: its steps all name its first head's
    first blocks, one fetch a row where each head's would be one a head."""
    return (jax.lax.mul(h, jax.lax.min(blocks_ref[0][0, b], 1))
            if blocks_ref else h)


def _block_maps(block_q: int, block_k: int, window: Optional[int]):
    """``(query_block, key_block)``: the block indices a forward's index
    maps name at a grid step, for both forwards. Each takes the prefetched
    ``blocks_ref`` (``_live_blocks``) last where the kernel is told the
    rows' lengths and nothing where it is not; ``lax`` alone."""

    def query_block(b, iq, *blocks_ref):
        """Query block ``iq`` of row ``b``, or the row's last live one past
        it, which costs no copy."""
        return jax.lax.min(iq, blocks_ref[0][1, b]) if blocks_ref else iq

    def key_block(b, iq, ik, *blocks_ref):
        """The key block of a grid step. Under a window: from its first
        block on, and past the diagonal the diagonal's again, which costs
        no copy; past the row's last live key block, or at a query block
        past its last live one, that key block again, which costs none
        either."""
        at = ik
        if window is not None:
            at = jax.lax.min(
                ik + _first_key_block(iq, block_q, block_k, window),
                jax.lax.div(iq * block_q + block_q - 1, block_k))
        if not blocks_ref:
            return at
        last = blocks_ref[0][3, b]
        return jax.lax.select(jax.lax.gt(iq, blocks_ref[0][1, b]), last,
                              jax.lax.min(at, last))

    return query_block, key_block


def _fwd_shared_rope_kernel(*refs, scale: float, causal: bool, block_q: int,
                            block_k: int, window: Optional[int] = None,
                            selected: bool = False, told: bool = False):
    """``_fwd_kernel`` with the scores in two parts, ``q k^T`` over the
    head's own width and ``q_rope k_rope^T`` over the shared rotary key's;
    operands in their own type, float32 accumulators, no logsumexp (there
    is no backward to hand it to). With ``window`` the innermost grid dim
    walks the key blocks that the query block's window reaches and no
    other; with ``selected`` a block of ``keep [B, S, S_kv]`` (int8) rides
    along and masks the scores, every head alike. A query's row of a block
    in which it sees no key fills with ``exp(0)``; the first block in which
    it sees one rescales that away (``alpha`` is 0), and every query sees
    its window's or its choice's keys somewhere. With ``told`` a first
    operand rides in front, ``blocks_ref [4, B]`` (`_flash_fwd_shared_rope`):
    how many query and key blocks hold a position of the row's own. A step
    at a block past them computes nothing, and a query block past them
    ends as the zeros it began as."""
    blocks_ref = None
    if told:
        blocks_ref, *refs = refs
    if selected:
        (q_ref, qr_ref, k_ref, kr_ref, v_ref, keep_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, qr_ref, k_ref, kr_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # the key block this step is at: under a window the walk starts at the
    # window's first block
    at = ik if window is None else ik + _first_key_block(
        iq, block_q, block_k, window)
    run = True
    if causal:
        run = at * block_k <= iq * block_q + block_q - 1
    if told:
        run = _both_live(run, blocks_ref, iq, at)

    @pl.when(run)
    def _compute():
        contract_last = (((1,), (1,)), ((), ()))
        v = v_ref[0, 0]                                  # [bk, dv]
        s = (jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], contract_last,
            preferred_element_type=jnp.float32)
            + jax.lax.dot_general(
                qr_ref[0, 0], kr_ref[0], contract_last,
                preferred_element_type=jnp.float32)) * scale   # [bq, bk]
        if causal:
            q_pos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = at * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            seen = q_pos >= k_pos
            if window is not None:
                seen = seen & (q_pos - k_pos < window)
            s = jnp.where(seen, s, _NEG_INF)
        if selected:
            s = jnp.where(keep_ref[0].astype(jnp.int32) != 0, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)
                       ).astype(o_ref.dtype)


def _flash_fwd_shared_rope(q: jax.Array, q_rope: jax.Array, k: jax.Array,
                           k_rope: jax.Array, v: jax.Array, *, scale: float,
                           causal: bool, window: Optional[int] = None,
                           keep: Optional[jax.Array] = None,
                           lengths: Optional[jax.Array] = None) -> jax.Array:
    """q [B,H,S,D], q_rope [B,H,S,R], k [B,H,S,D], k_rope [B,S,R] (one row
    a position, every head's), v [B,H,S,Dv], keep [B,S,S] int8 or None,
    lengths [B] int32 or None (the rows' own lengths: module docstring) →
    o [B,H,S,Dv]."""
    B, H, Sq, D = q.shape
    R, Skv, Dv = q_rope.shape[3], k.shape[2], v.shape[3]
    if (window is not None or keep is not None or lengths is not None) \
            and not (causal and Sq == Skv):
        raise ValueError("a window, a choice of keys or the rows' lengths "
                         "are a prefill's: causal, the queries' positions "
                         "the keys'")
    if lengths is not None and lengths.shape != (B,):
        raise ValueError(f"lengths{lengths.shape} for {B} rows")
    # what the blocks fill in VMEM: a part of 64 pads to the lane width
    padded = D + -(-R // _LANES) * _LANES
    block_q, block_k = flash_tiles(Sq, Skv, head_dim=padded, value_dim=Dv)
    kernel = functools.partial(
        _fwd_shared_rope_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, window=window,
        selected=keep is not None, told=lengths is not None)
    key_blocks = Skv // block_k
    if window is not None:
        key_blocks = _window_key_blocks(Sq, block_q, block_k, window)
    # no lengths, no operand: the call is the one it was
    prefetched = [] if lengths is None else [
        _live_blocks(lengths, block_q, block_k)]

    query_block, key_block = _block_maps(block_q, block_k, window)

    def rows(block, width):   # a head's rows: q and q_rope by iq
        return pl.BlockSpec(
            (1, 1, block, width),
            lambda b, h, iq, ik, *n: (b, h, query_block(b, iq, *n), 0))

    def keys(width):          # a head's keys and values by ik
        return pl.BlockSpec(
            (1, 1, block_k, width),
            lambda b, h, iq, ik, *n: (b, h, key_block(b, iq, ik, *n), 0))

    in_specs = [
        rows(block_q, D), rows(block_q, R), keys(D),
        # the shared rotary key: no head in its index
        pl.BlockSpec(
            (1, block_k, R),
            lambda b, h, iq, ik, *n: (b, key_block(b, iq, ik, *n), 0)),
        keys(Dv),
    ]
    operands = [q, q_rope, k, k_rope, v]
    if keep is not None:  # the choice: no head in its index either
        in_specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda b, h, iq, ik, *n: (b, query_block(b, iq, *n),
                                      key_block(b, iq, ik, *n))))
        operands.append(keep)
    name = (SELECTED_TRACE_NAME if keep is not None else
            WINDOW_TRACE_NAME if window is not None else
            SHARED_ROPE_TRACE_NAME)
    with jax.named_scope(name):
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetched),
                grid=(B, H, Sq // block_q, key_blocks),
                in_specs=in_specs,
                # every block of `o` is written, a dead one with zeros
                out_specs=pl.BlockSpec(
                    (1, 1, block_q, Dv),
                    lambda b, h, iq, ik, *n: (b, h, iq, 0)),
                scratch_shapes=[
                    pltpu.VMEM((block_q, 128), jnp.float32),   # running max
                    pltpu.VMEM((block_q, 128), jnp.float32),   # running sum
                    pltpu.VMEM((block_q, Dv), jnp.float32),    # accumulator
                ]),
            out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=_interpret(),
        )(*prefetched, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def flash_attention_shared_rope(q: jax.Array, q_rope: jax.Array,
                                k: jax.Array, k_rope: jax.Array,
                                v: jax.Array, scale: float,
                                causal: bool = True,
                                window: Optional[int] = None,
                                keep: Optional[jax.Array] = None,
                                lengths: Optional[jax.Array] = None
                                ) -> jax.Array:
    """The forward at two widths (module docstring): q, k ``[B, S, H, D]``,
    q_rope ``[B, S, H, R]``, k_rope ``[B, S, R]``, v ``[B, S, H, Dv]`` →
    ``[B, S, H, Dv]``; softmax of ``(q k^T + q_rope k_rope^T) * scale``
    over the causal keys, of which ``window`` leaves a query its last
    ``window`` and ``keep [B, S, S]`` (int8) the ones it marks.
    ``lengths [B]`` int32: how many of a right-padded row's positions are
    its own (None: all of every row); the blocks past them are not
    computed, and their outputs are zeros."""
    o = _flash_fwd_shared_rope(
        jnp.swapaxes(q, 1, 2), jnp.swapaxes(q_rope, 1, 2),
        jnp.swapaxes(k, 1, 2), k_rope, jnp.swapaxes(v, 1, 2),
        scale=scale, causal=causal, window=window, keep=keep,
        lengths=lengths)
    return jnp.swapaxes(o, 1, 2)


def _fa_shared_rope_fwd(q, q_rope, k, k_rope, v, scale, causal, window,
                        keep, lengths):
    return flash_attention_shared_rope(q, q_rope, k, k_rope, v, scale,
                                       causal, window, keep, lengths), None


def _fa_shared_rope_bwd(scale, causal, window, res, g):
    raise NotImplementedError(
        "flash attention at two widths (a shared rotary key beside the "
        "head's own, values narrower than keys) has a forward only: train "
        "such a model with attn_impl='reference'")


flash_attention_shared_rope.defvjp(_fa_shared_rope_fwd, _fa_shared_rope_bwd)
