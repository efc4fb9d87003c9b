"""An indexer's scores over a prefill's square, as a Pallas TPU kernel.

DeepSeek-V3.2's lightning indexer gives every (query, key) pair of a
sequence one number, ``I[s, t] = sum_j w[s, j] ReLU(q[j, s] . k[t])``: a
few narrow heads ``j`` (dots3-note-prev: 64 of 128; Keye-VL-2.0-30B-A3B:
16 of 64), ONE key a position,
and a weight a head and query. The query then attends its ``index_topk``
keys of largest ``I``. Made by einsums the heads' products are ``[J, S, T]``
float32, 27 GB for 4 rows of 5120, where ``I`` itself is 0.4 GB: here a
grid step holds a block of queries (all heads') and a block of keys in
VMEM, multiplies head by head on the MXU in the operands' type with a
float32 accumulator, and adds ``w * ReLU(.)`` into the one ``[block_q,
block_k]`` float32 tile it writes. The blocks above the diagonal are
written as zeros and not multiplied: no query may see their keys.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import flash_attention

# What the device trace calls the kernel (``tpu_custom_call:<this>.N``).
TRACE_NAME = "index_scores"


def index_tiles(seq: int) -> Tuple[int, int]:
    """``(block_q, block_k)`` for a length: a query block holds every
    head's rows (64 heads of 128 in bf16: 4 MB at 256 rows, in two
    buffers), so it is the narrower; the key block is as wide as divides
    the length, up to 512. Pure: the length is all it reads, so 16 heads
    of 64 (Keye-VL-2.0-30B-A3B's) get the same tiles: a query block is then
    0.5 MB, a key block ``[512, 64]`` is half a lane tile wide (padded to
    128 in VMEM, the products at half the MXU's width), and a pair costs
    2048 FLOP (10.4 ps at a v5e's peak) beside the 4 bytes of its float32
    score (4.9 ps at its HBM's) where 64 heads of 128 cost 16 384: the
    write is a seventeenth of what the multiplies need there and half
    here, and what the kernel takes is mostly neither but the vector
    unit's multiply, maximum and add a head and pair (4 x 8192 in 4.4 ms a
    layer against 1.4 of need: PERF.md section 5, PR 62; wider tiles for
    few narrow heads are an open ``perf_opt``)."""
    if seq % 128:
        raise ValueError(f"seq len {seq} must divide by 128")
    block_q = 256 if seq % 256 == 0 else 128
    block_k = next(b for b in (512, 256, 128) if seq % b == 0)
    return block_q, block_k


def _kernel(q_ref, k_ref, w_ref, o_ref, *, heads: int, block_q: int,
            block_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    below = ik * block_k <= iq * block_q + block_q - 1

    @pl.when(below)
    def _score():
        k = k_ref[0]                                     # [bk, d]
        w = w_ref[0]                                     # [bq, heads] f32
        acc = jnp.zeros((block_q, block_k), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(
                q_ref[0, j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)      # [bq, bk]
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc

    @pl.when(jnp.logical_not(below))
    def _nothing():
        o_ref[0] = jnp.zeros((block_q, block_k), jnp.float32)


def index_scores_causal(q: jax.Array, k: jax.Array,
                        weights: jax.Array) -> jax.Array:
    """q ``[B, J, S, D]``, k ``[B, S, D]``, weights ``[B, S, J]`` float32 ->
    ``I [B, S, S]`` float32 (module docstring); zeros above the diagonal's
    blocks."""
    B, J, S, D = q.shape
    if k.shape != (B, S, D) or weights.shape != (B, S, J):
        raise ValueError(f"index_scores_causal: q{q.shape} k{k.shape} "
                         f"weights{weights.shape}")
    block_q, block_k = index_tiles(S)
    with jax.named_scope(TRACE_NAME):
        return pl.pallas_call(
            functools.partial(_kernel, heads=J, block_q=block_q,
                              block_k=block_k),
            grid=(B, S // block_q, S // block_k),
            in_specs=[
                pl.BlockSpec((1, J, block_q, D),
                             lambda b, iq, ik: (b, 0, iq, 0)),
                # past the diagonal the diagonal's block again: no copy
                pl.BlockSpec((1, block_k, D), lambda b, iq, ik: (
                    b, jnp.minimum(ik, (iq * block_q + block_q - 1)
                                   // block_k), 0)),
                pl.BlockSpec((1, block_q, J), lambda b, iq, ik: (b, iq, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, block_k),
                                   lambda b, iq, ik: (b, iq, ik)),
            out_shape=jax.ShapeDtypeStruct((B, S, S), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel")),
            interpret=flash_attention._interpret(),
        )(q, k, weights.astype(jnp.float32))
