"""Grouped matmul over rows sorted by group, as a Pallas TPU kernel.

``rows [M, K]`` lie sorted by group; group ``e`` owns the next ``sizes[e]``
of them and multiplies them by ``stack[first_group + e]`` (``[K, N]``).
This is what a sparse feed-forward's three expert matmuls are once the
(position, expert) pairs are sorted by expert (``models/moe.py``).

The schedule. The grid is (column blocks of ``N``, visits), visits
innermost. A visit is one (row tile, group) pair that share rows: row
tiles are aligned to ``tm`` and a group's rows begin wherever the
previous group's end, so a tile that holds a boundary is visited once by
each group in it. The visits walk the rows in order, so a group's visits
are consecutive, and the block of its weights, ``[K, tn]`` with the whole
of ``K``, does not change between them: the pipeline fetches a block again
only when its index changes, so each group's weights are read once
whatever the row tile, and the next group's arrive while this group's last
visit computes. The rows are read once a column block. The visits' tile
and group are a few ``jnp`` operations on ``sizes`` and reach the index
maps as scalar-prefetch operands, with ``first_group``: the stack is read
where it lies (all layers' experts, say), nothing is sliced out of it.
There are at most ``tiles + groups`` visits, and the grid has that many:
the surplus repeat the last visit's blocks, so nothing moves, and compute
nothing. Every shape is static whatever ``sizes`` holds.

A visit computes only the strips of 128 rows of its tile that hold rows of
its group, so a boundary inside a tile costs a strip's matmul and not a
tile's: what is computed follows the rows a group really has, at any tile.
The product is accumulated in float32 over the whole of ``K`` in one dot,
and written under a mask of the group's rows: the other rows of the tile
are the neighbouring visits'. Rows past the last group's end get no visit:
nothing is read, multiplied or written for them, and what the result holds
there is not defined (``jax.lax.ragged_dot`` leaves zeros). Nobody reads
them: ``models/moe.py`` moves the kept pairs' rows alone wherever some
pairs are not kept (a share of the experts, where seven eighths of the
sorted pairs belong to experts on other chips, and a serving step's
padding, four fifths of its pairs), and the rows it does not move are
``unwritten``: an array no operation has filled.

``grouped_swiglu`` is the same kernel with two stacks: one pass over the
rows holds the gate's and the up projection's blocks, accumulates both,
and writes ``silu(gate) * up`` once, from the float32 accumulators.

Tiles. ``gmm_tiles`` gives ``(tm, tn)`` from the shapes and
``gmm_vmem_bytes`` alone; no argument, field or variable chooses a tile.
The rule is what a sweep on a v5e read (PERF.md, PR 32: OLMoE's widths,
8 192 to 73 728 rows in 64 groups with the fullest at three times the
mean): the widest column block first, because every column block is one
more pass over the rows and one more round of visits; then the tallest row
tile of 512, 256 or 128 that fits VMEM beside it, because a grid step has
a cost of its own. With the strips, neither depends on how many rows a
group has: (512, 512) won for the fused pair 2048 -> 1024 and (256, 2048)
for 1024 -> 2048 in float32 at every one of the nine row counts.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention
from ray_tpu.ops.pallas.flash_attention import VMEM_LIMIT_BYTES

_LANES = 128
# What the kernel is called in a device trace: a Pallas call's HLO
# instruction takes the name of its innermost named scope. XLA's own
# grouped matmul is ``ragged-dot-none.N`` there, and the benchmark's readers
# of the expert matmuls find them by that prefix (``match`` in
# ``benchmark/metrics/expert_*.json``); a traced run of ``serve_olmoe_chat``
# in which they find nothing prints no result. The suffix tells the two
# kernels apart.
TRACE_NAME = "ragged-dot-none-pallas"
# rows a visit multiplies at a time: the MXU's own height
_STRIP = 128


def takes(k: int, n: int) -> bool:
    """Whether the kernel has a path for a ``[K, N]`` weight: both dims on
    the lane width (a block is whole lanes, and ``tn`` divides ``N``)."""
    return k % _LANES == 0 and n % _LANES == 0


def gmm_vmem_bytes(tm: int, tn: int, k: int, *, stacks: int = 1,
                   itemsize: int = 2, out_itemsize: int = 4) -> int:
    """Upper reckoning of the VMEM one grid step holds at a tile: the row
    tile, each stack's ``[K, tn]`` block and the result's tile in their two
    pipeline buffers, and a strip's rows and, for each stack, twice its
    float32 product (the epilogue's values; the masked copy of what was
    there). Mosaic reports less at every tile tried
    (``tests/test_flash_tiles_v5e.py``)."""
    pipeline = 2 * (tm * k * itemsize + stacks * k * tn * itemsize
                    + tm * tn * out_itemsize)
    return pipeline + _STRIP * k * itemsize + 2 * stacks * _STRIP * tn * 4


def gmm_tiles(rows: int, k: int, n: int, *, stacks: int = 1,
              itemsize: int = 2, out_itemsize: int = 4) -> Tuple[int, int]:
    """``(tm, tn)``, the row tile and the column block, for ``rows`` rows
    times ``[k, n]``: the module docstring's rule. Pure: the shapes, how
    many stacks share the rows and the element sizes are all it reads."""
    if not takes(k, n):
        raise ValueError(f"weight dims ({k},{n}) must divide by {_LANES}")
    for tn in (d for d in range(n, 0, -_LANES) if n % d == 0):
        for tm in (512, 256, 128):
            if gmm_vmem_bytes(tm, tn, k, stacks=stacks, itemsize=itemsize,
                              out_itemsize=out_itemsize) <= VMEM_LIMIT_BYTES:
                # no taller than the rows, in whole strips
                return min(tm, -(-rows // _STRIP) * _STRIP), tn
    raise ValueError(f"no column block of {n} fits VMEM beside k={k}")


def _visits(sizes: jax.Array, rows: int, tm: int):
    """The (row tile, group) pairs that share rows, in row order, padded to
    their static bound ``tiles + E - 1`` by repeating the last: ``tile
    [V]``, ``group [V]``, how many are real ``[1]`` (there may be none), and
    each group's first and one-past-last row ``[E]``. Sums and lookups over
    the ``E`` groups are written as masked sums over a ``[., E]``
    comparison, which fuse into a few device operations where a cumulative
    sum, a binary search and a gather are a loop and a dozen each. Nothing
    here is negative where it is divided, so the divisions truncate
    (``lax.div``)."""
    E = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    e = jnp.arange(E, dtype=jnp.int32)
    before = e[None, :] <= e[:, None]             # [g, g']: g' <= g

    def running(x):   # inclusive cumulative sum
        return jnp.sum(jnp.where(before, x[None, :], 0), axis=1)

    end = running(sizes)
    start = end - sizes
    first = jax.lax.div(start, tm)
    count = jnp.where(sizes > 0, jax.lax.div(end - 1, tm) - first + 1, 0)
    upto = running(count)
    total = upto[E - 1:]
    v = jnp.minimum(jnp.arange(-(-rows // tm) + E - 1, dtype=jnp.int32),
                    total - 1)
    # the group of visit v: how many groups' visits all lie before it
    group = jnp.sum(upto[None, :] <= v[:, None], axis=1, dtype=jnp.int32)
    tile = v + jnp.sum(jnp.where(group[:, None] == e[None, :],
                                 (first - upto + count)[None, :], 0), axis=1)
    # there may be no visit at all: stay in range
    group = jnp.minimum(group, E - 1)
    tile = jnp.clip(tile, 0, -(-rows // tm) - 1)
    return tile, group, total, start, end


def _kernel(first_ref, tile_ref, group_ref, total_ref, start_ref, end_ref,
            x_ref, *refs):
    *w_refs, o_ref = refs
    tm = x_ref.shape[0]
    v = pl.program_id(1)
    g = group_ref[v]
    # the group's rows, counted from the tile's first
    lo = start_ref[g] - tile_ref[v] * tm
    hi = end_ref[g] - tile_ref[v] * tm

    def strip(j, carry):
        r0 = pl.multiple_of(j * _STRIP, _STRIP)
        here = pl.ds(r0, _STRIP)
        x = x_ref[here, :]
        acc = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
               for w in w_refs]
        # silu(gate) * up, of the float32 products
        y = (acc[0] if len(acc) == 1
             else acc[0] * jax.lax.logistic(acc[0]) * acc[1]
             ).astype(o_ref.dtype)
        row = r0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
        o_ref[here, :] = jnp.where((row >= lo) & (row < hi), y,
                                   o_ref[here, :])
        return carry

    # the strips of the tile that hold rows of the group; none of a
    # surplus visit
    first = jax.lax.div(jnp.maximum(lo, 0), _STRIP)
    last = jnp.where(v < total_ref[0],
                     jax.lax.div(jnp.minimum(hi, tm) + _STRIP - 1, _STRIP),
                     first)
    jax.lax.fori_loop(first, last, strip, None)


def _grouped(rows: jax.Array, stacks: Sequence[jax.Array], sizes: jax.Array,
             first_group, out_dtype) -> jax.Array:
    M, K = rows.shape
    N = stacks[0].shape[2]
    E = sizes.shape[0]
    out_dtype = jnp.dtype(out_dtype)
    for s in stacks:
        if s.shape[1:] != (K, N) or s.dtype != rows.dtype:
            raise ValueError(
                f"stack {s.shape} {s.dtype} against rows {rows.shape} "
                f"{rows.dtype} and [{K}, {N}]")
    tm, tn = gmm_tiles(M, K, N, stacks=len(stacks),
                       itemsize=rows.dtype.itemsize,
                       out_itemsize=out_dtype.itemsize)
    tile, group, total, start, end = _visits(sizes, M, tm)
    first = jnp.asarray(first_group, jnp.int32).reshape(1)

    def weights(j, v, first_ref, tile_ref, group_ref, *_):
        return (first_ref[0] + group_ref[v], 0, j)

    call = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(N // tn, tile.shape[0]),
            in_specs=[pl.BlockSpec((tm, K),
                                   lambda j, v, f, tile_ref, *_:
                                   (tile_ref[v], 0))]
            + [pl.BlockSpec((None, K, tn), weights)] * len(stacks),
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, v, f, tile_ref, *_:
                                   (tile_ref[v], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=flash_attention._interpret(),
    )
    with jax.named_scope(TRACE_NAME):
        return call(first, tile, group, total, start, end, rows, *stacks)


def grouped_matmul(rows: jax.Array, stack: jax.Array, sizes: jax.Array,
                   first_group, out_dtype) -> jax.Array:
    """``rows [M, K]`` sorted by group, ``stack [G, K, N]``, ``sizes [E]``
    (int): the ``sizes[e]`` rows of group ``e`` times
    ``stack[first_group + e]``, accumulated in float32, as ``[M, N]`` in
    ``out_dtype``. ``first_group`` may be traced. Rows past ``sum(sizes)``
    are neither read nor written: what they hold is not defined."""
    return _grouped(rows, (stack,), sizes, first_group, out_dtype)


def grouped_swiglu(rows: jax.Array, gate_stack: jax.Array,
                   up_stack: jax.Array, sizes: jax.Array, first_group,
                   out_dtype) -> jax.Array:
    """``silu(rows @ gate) * (rows @ up)`` group by group, in one pass
    over the rows: both products in float32, rounded once to
    ``out_dtype``. Arguments as ``grouped_matmul``'s."""
    return _grouped(rows, (gate_stack, up_stack), sizes, first_group,
                    out_dtype)


def unwritten(shape: Sequence[int], dtype) -> jax.Array:
    """An array of ``shape`` that no operation has filled: what it holds is
    not defined. The rows a caller will write before anybody reads them
    need no pass of zeros first (``models/moe.py``: the sorted pairs' rows,
    of which a serving step moves a fifth; at OLMoE's widths the zeros were
    2.4 to 7.7 ms a step, PERF.md). A Pallas call whose body does nothing
    and whose result stays where XLA allocated it; in a device trace it
    takes the name of the caller's innermost named scope."""
    return pl.pallas_call(
        lambda o_ref: None,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct(tuple(shape), dtype),
        interpret=flash_attention._interpret(),
    )()
