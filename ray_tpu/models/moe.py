"""Sparse expert feed-forward: the routed half of ``models/llama.py``'s block.

A model with experts is a ``LlamaConfig`` whose ``num_experts`` is above 0
(OLMoE-1B-7B: 64 experts of width 1024, 8 a token, no shared expert;
LFM2-24B-A2B: 64 of width 1536, 4 a token, after its leading dense layers;
DeepSeek-V2: 160 of width 1536, 6 a token from 3 of 8 groups, beside two
shared experts, of which a chip holds one group's 20; Ling-3.0-flash: 512
of width 768, 8 a token from 4 of 8 groups under a bias, beside one shared
expert, of which a chip holds two groups' 128).
Its block is ``llama.py::_layer``: the operator half, the scan, remat, the
head and the loss are the dense model's. This module holds what only the
routed feed-forward needs: the router, the dispatch, the expert matmuls,
the load-balancing term, and the expert leaves' initialiser and logical
axes (the leading ``expert`` dim of the three stacks shards over the mesh's
``expert`` axis).

The router's variants are fields of the configuration, one function: the
scores are a softmax or a sigmoid of the router's logits
(``router_scores``); the ``K`` experts are chosen on the scores or, with
``router_bias``, on the scores plus a per-expert bias, a buffer no gradient
reaches (kept in the parameters' type like every leaf, added in float32),
while the weights stay the scores without it; the chosen
weights are renormalised or not (``norm_topk_prob``, over their sum plus
``router_norm_eps``) and scaled by ``routed_scaling_factor``. With
``router_groups`` the choice is group-limited (DeepSeek-V2's
``group_limited_greedy``): the experts lie in that many groups of
neighbours, a group's score is its best expert's
(``router_group_score`` ``"max"``) or the sum of its two best
(``"top2"``, DeepSeek-V3's ``noaux_tc``), a position keeps its best
``router_topk_groups`` groups and chooses its ``K`` among their experts
(``_best_groups``); with a bias too (``noaux_tc`` whole) the groups are
scored and the experts chosen on the scores plus the bias, and the weights
stay the scores without it. The defaults are OLMoE's router, to the bit.

Shared experts (``num_shared_experts``) are one dense SwiGLU of that many
times an expert's width, every position's, added to the routed sum (leaves
``ws_gate``, ``ws_up``, ``ws_down``).

A share of the experts (``experts_held = (first, count)``) is what one
chip of an expert-parallel deployment holds of a layer: the router stays
``num_experts`` wide and every position still chooses its ``K`` among all
of them, the three stacks hold the ``count`` experts from ``first`` on
alone, and the result is those experts' part of the routed sum; what the
absent experts would add is left out, and nothing stands in for the other
chips or their traffic. The books count both: ``pairs`` over all the
experts, ``pairs_here`` over the held ones.

Which pairs are multiplied is one rule (``expert_ffn``'s ``keep``): a
pair whose expert is held here AND whose position is wanted. Without a
share every expert is held; a caller that hands ``in_stack`` a mask and
says ``skip_unmasked`` wants the masked positions alone (a serving step's
rows' own tokens: its padding is one token repeated, four fifths of the
positions of a step in the benchmark's cells), and without that every
position is wanted. The ``T x K`` pairs are sorted as ever, the pairs not
kept past the kept ones into no group. Where some pairs are not kept, the
kept pairs' rows alone move: the dispatch gathers the first
``sum(sizes)`` rows of the sorted order, a pass of ``moved_chunk`` rows at
a time (``_kept_rows``), and the combine visits the positions that have a
kept pair, wanted positions first, and sums each one's ``K`` outputs in
float32 in ``k`` order (``_kept_sum``); the trip counts are read on the
device, no shape depends on them, and nothing branches. The rows past the
kept pairs' are never written and never read (``grouped_matmul.unwritten``
where the repo's kernels multiply, which visit no row past the last
group; zeros for ``ragged_dot``). Nothing is dropped: a kept pair's row is
the same row times the same expert whatever else is kept. Where every pair
is kept (training, a forward pass with no mask) the dispatch and the
combine are one whole gather each.

The dispatch drops nothing and has no capacity: the ``T x K`` (position,
expert) pairs are sorted by expert, the rows gathered in that order, and
the three matmuls run as grouped matmuls over the sorted rows, each
expert's weights read once. Every shape is static, the sort is over a
fixed ``T x K``, so a (batch, length) shape compiles once whatever the
routing.

The layers are scanned, and a custom call cannot read its operand through
the scan's slice as a dense matmul does: XLA copies the layer's three
``[E, hidden, width]`` slices out of the stacked ``[L, E, ...]`` arrays
first (0.8 GB a layer at OLMoE's widths, 39 ms a serving step on a v5e).
So the forward pass multiplies by the whole stack, seen as ``L x E``
groups, and nothing is copied (``_in_place``, ``_gated_in_place``). Which
kernel reads it follows from what the program can see when it is traced
(``_kernel_takes``), as ``attention(impl="auto")`` chooses: where both of
a weight's dims are on the lane width and no mesh of more than one device
is in scope, the repo's own (``ops/pallas/grouped_matmul.py``: the layer's
first group reaches its index maps, only the strips of a row tile that
hold an expert's rows are computed, and gate, up and SiLU are one pass over
the rows); everywhere
else ``jax.lax.ragged_dot``, XLA's kernel, for which only this layer's
``E`` of the ``L x E`` groups hold rows: it differentiates and partitions,
which a ``pallas_call`` does not. The backward pass is ``ragged_dot``'s on
the layer's slice either way, so that the weights' gradient is the slice's
and the scan stacks it as it stacks every other leaf's. Where the
parameters are kept in another type than the activations' the slice is
cast on its way in, which is that copy, and the matmuls take the slice.
The stack and the layer's index in it travel with the layer's leaves
(``in_stack``), so the block's signature is the dense model's.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import grouped_matmul

ROUTER_SCORES = ("softmax", "sigmoid")
EXPERT_LOGICAL_AXES = {
    "router": ("embed", "expert"),
    "we_gate": ("expert", "embed", "mlp"),
    "we_up": ("expert", "embed", "mlp"),
    "we_down": ("expert", "mlp", "embed"),
}
SHARED_LOGICAL_AXES = {
    "ws_gate": ("embed", "mlp"), "ws_up": ("embed", "mlp"),
    "ws_down": ("mlp", "embed"),
}
EXPERT_STACKS = ("we_gate", "we_up", "we_down")
# keys `in_stack` adds to a layer's leaves: not leaves themselves
_WHERE, _MASK, _SKIP = "_experts_in", "_router_mask", "_skip_unmasked"


def in_stack(lp: Dict[str, jax.Array], layers: Dict[str, jax.Array], layer,
             mask: Optional[jax.Array] = None,
             skip_unmasked: bool = False) -> Dict[str, jax.Array]:
    """The layer's leaves ``lp`` (``layers[name][layer]``) with where its
    experts lie: the three ``EXPERT_STACKS`` over all layers and the
    layer's index in them, for ``expert_ffn`` to read them in place; and
    ``mask [B, S]``, the positions the router's books count. With
    ``skip_unmasked`` the caller wants outputs at the masked positions
    alone (a serving step's rows' own tokens, as against its padding): the
    other positions' pairs are not multiplied and their routed part comes
    out zero."""
    return dict(lp, **{_WHERE: ({n: layers[n] for n in EXPERT_STACKS}, layer),
                       _MASK: mask, _SKIP: skip_unmasked})


def init_experts(cfg, key: jax.Array, num_layers: int
                 ) -> Dict[str, jax.Array]:
    """The router and the three expert stacks, stacked over ``num_layers``
    layers (truncated normal, fan-in scaled, as ``init_llama`` draws the
    dense leaves). One layer is drawn at a time: at OLMoE's widths a stack
    is ``[16, 64, 2048, 1024]``, 8.6 GB in float32 on its way to bf16, and
    a layer of it is 0.5 GB. With ``router_bias`` the per-expert bias rides
    along as zeros, as HuggingFace starts it: a buffer that training's
    balancing moves and no gradient reaches. With ``experts_held`` the
    stacks hold that many experts and the router all ``num_experts``
    columns; with ``num_shared_experts`` the shared SwiGLU's three leaves
    ride along."""
    h, m, E, L = cfg.hidden, cfg.mlp_hidden, cfg.num_experts, num_layers
    held = held_experts(cfg)[1]
    pd = cfg.param_dtype

    def stack(k, shape, fan_in):
        def one(layer_key):
            return (jax.random.truncated_normal(
                layer_key, -2, 2, shape, jnp.float32)
                * fan_in ** -0.5).astype(pd)
        return jax.lax.map(one, jax.random.split(k, L))

    ks = jax.random.split(key, 4)
    out = {
        "router": stack(ks[0], (h, E), h),
        "we_gate": stack(ks[1], (held, h, m), h),
        "we_up": stack(ks[2], (held, h, m), h),
        "we_down": stack(ks[3], (held, m, h), m),
    }
    if cfg.router_bias:
        out["router_bias"] = jnp.zeros((L, E), pd)
    if cfg.num_shared_experts:
        ms = cfg.num_shared_experts * m
        kg, ku, kd = jax.random.split(jax.random.fold_in(key, 1), 3)
        out.update(ws_gate=stack(kg, (h, ms), h), ws_up=stack(ku, (h, ms), h),
                   ws_down=stack(kd, (ms, h), ms))
    return out


def held_experts(cfg) -> Tuple[int, int]:
    """``(first, count)`` of the experts whose weights are here: the
    configuration's share, or all of them."""
    first, count = cfg.experts_held or (0, cfg.num_experts)
    if not (0 <= first and 0 < count and first + count <= cfg.num_experts):
        raise ValueError(f"experts_held {cfg.experts_held!r} lies outside "
                         f"the {cfg.num_experts} experts")
    return first, count


def _best_groups(cfg, probs: jax.Array) -> jax.Array:
    """``probs [T, E]`` with the experts outside each position's best
    ``router_topk_groups`` of ``router_groups`` groups set to 0, as
    DeepSeek-V2's ``group_limited_greedy`` masks them: a group is
    ``E / router_groups`` neighbouring experts and its score its largest
    or, under ``router_group_score`` ``"top2"``, the sum of its two largest
    (DeepSeek-V3's ``noaux_tc``; an expert that ties with its group's best
    is its second); between groups that tie, the lower index stays
    (``lax.top_k``)."""
    T, E = probs.shape
    G, keep = cfg.router_groups, cfg.router_topk_groups
    if E % G or not 0 < keep <= G:
        raise ValueError(f"{E} experts in {G} groups, {keep} kept")
    # by comparisons against each expert's group, not by reshaping the
    # expert axis into (group, member): on a TPU that reshape is a
    # relayout of every row (19 ms a step at 8 x 1536 positions, PERF.md)
    group_of = jnp.arange(E, dtype=jnp.int32) // (E // G)       # [E]
    member = group_of[None, :] == jnp.arange(G, dtype=jnp.int32)[:, None]
    grouped = jnp.where(member[None], probs[:, None, :], -jnp.inf)
    best = jnp.max(grouped, axis=-1)                            # [T, G]
    if cfg.router_group_score == "top2":
        # the first expert at its group's best leaves, and the best of
        # what is left joins it
        ids = jnp.arange(E, dtype=jnp.int32)
        first = jnp.min(jnp.where(grouped == best[..., None], ids, E),
                        axis=-1)                                # [T, G]
        best = best + jnp.max(
            jnp.where(ids == first[..., None], -jnp.inf, grouped), axis=-1)
    elif cfg.router_group_score != "max":
        raise ValueError(f"router_group_score {cfg.router_group_score!r}: "
                         "expected max|top2")
    _, kept = jax.lax.top_k(best, keep)                         # [T, keep]
    stays = jnp.any(kept[:, :, None] == group_of[None, None, :],
                    axis=1)                                     # [T, E]
    return jnp.where(stays, probs, 0.0)


def _kernel_takes(stack: jax.Array) -> bool:
    """Whether ``ops/pallas/grouped_matmul.py`` has a path for a stack, from
    what the program can see when it is traced: both of a weight's dims on
    the lane width, and no mesh of more than one device in scope (a
    ``pallas_call`` has no partitioning rule; ``ops/attention.py`` asks the
    same of the flash kernel)."""
    from ray_tpu.parallel.sharding import ambient_mesh

    mesh = ambient_mesh()
    return (grouped_matmul.takes(*stack.shape[2:])
            and (mesh is None or mesh.size == 1))


def _as_groups(stack: jax.Array) -> jax.Array:
    """``[L, E, K, N]`` seen as ``L x E`` groups: layer ``l``'s experts are
    groups ``l * E`` onwards."""
    return stack.reshape((-1,) + stack.shape[2:])


def _ragged_dot_in_stack(rows, sizes, stack, layer, out_dtype):
    """``ragged_dot(rows, stack[layer], sizes)`` by XLA's kernel, read from
    the stack where it lies: ``sizes`` sits at this layer's ``E`` of the
    ``L x E`` groups and the others are empty, which costs it nothing."""
    L, E = stack.shape[:2]
    flat = jax.lax.dynamic_update_slice(
        jnp.zeros((L * E,), sizes.dtype), sizes, (layer * E,))
    return jax.lax.ragged_dot(rows, _as_groups(stack), flat,
                              preferred_element_type=out_dtype)


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _in_place(rows, w, sizes, stack, layer, out_dtype):
    """``ragged_dot(rows, w, sizes)`` for ``w = stack[layer]``, read from
    the stack where it lies; the repo's kernel is handed the layer's first
    group, and leaves the rows past the groups' end as they were (XLA's
    kernel leaves zeros there)."""
    if _kernel_takes(stack):
        return grouped_matmul.grouped_matmul(
            rows, _as_groups(stack), sizes, layer * stack.shape[1], out_dtype)
    return _ragged_dot_in_stack(rows, sizes, stack, layer, out_dtype)


def _in_place_fwd(rows, w, sizes, stack, layer, out_dtype):
    return (_in_place(rows, w, sizes, stack, layer, out_dtype),
            (rows, w, sizes))


def _in_place_bwd(out_dtype, res, g):
    rows, w, sizes = res
    _, vjp = jax.vjp(lambda r, ww: jax.lax.ragged_dot(
        r, ww, sizes, preferred_element_type=out_dtype), rows, w)
    return vjp(g) + (None, None, None)


_in_place.defvjp(_in_place_fwd, _in_place_bwd)


@jax.custom_vjp
def _gated_in_place(rows, w_gate, w_up, sizes, gate_stack, up_stack, layer):
    """``silu(ragged_dot(rows, w_gate)) * ragged_dot(rows, w_up)`` in the
    rows' type, both read from their stacks where they lie. The kernel
    makes it in one pass over the rows and rounds once, from the float32
    products; XLA's two matmuls round each product first."""
    if _kernel_takes(gate_stack):
        return grouped_matmul.grouped_swiglu(
            rows, _as_groups(gate_stack), _as_groups(up_stack), sizes,
            layer * gate_stack.shape[1], rows.dtype)
    return (jax.nn.silu(_ragged_dot_in_stack(rows, sizes, gate_stack, layer,
                                             rows.dtype))
            * _ragged_dot_in_stack(rows, sizes, up_stack, layer, rows.dtype))


def _gated_in_place_fwd(rows, w_gate, w_up, sizes, gate_stack, up_stack,
                        layer):
    return (_gated_in_place(rows, w_gate, w_up, sizes, gate_stack, up_stack,
                            layer), (rows, w_gate, w_up, sizes))


def _gated_in_place_bwd(res, g):
    # through the layer's slices, the two products made again
    rows, w_gate, w_up, sizes = res

    def on_slices(r, wg, wu):
        return (jax.nn.silu(jax.lax.ragged_dot(
            r, wg, sizes, preferred_element_type=r.dtype))
            * jax.lax.ragged_dot(r, wu, sizes,
                                 preferred_element_type=r.dtype))
    _, vjp = jax.vjp(on_slices, rows, w_gate, w_up)
    return vjp(g) + (None, None, None, None)


_gated_in_place.defvjp(_gated_in_place_fwd, _gated_in_place_bwd)


# the sorted rows one pass moves, about: the dispatch gathers that many, the
# combine the ``K`` rows of that many over ``K`` positions
_PASS_ROWS = 8192


def moved_chunk(rows: int, pairs: int = 1) -> int:
    """How many of ``rows`` one pass of ``_kept_rows`` moves, or, of
    ``rows`` positions of ``pairs`` outputs each, one pass of ``_kept_sum``
    sums: ``_PASS_ROWS`` sorted rows either way, in whole lane tiles of
    positions. Pure: the shapes are all it reads, as
    ``grouped_matmul.gmm_tiles``. A pass is a few device operations
    whatever its size and the kept rows are covered to the pass, so the
    largest pass that wastes little: by a sweep on a v5e the dispatch is
    flat within 2 % from 1024 to 8192 rows a pass at rows of 4 KB and of
    10 KB (PERF.md, PR 41 and 42), and a traced run pays for every pass
    (PERF.md section 6, PR 42)."""
    return min(rows, max(128, _PASS_ROWS // pairs // 128 * 128))


def _rows_at(a, at):
    """``a[at]`` along the first axis for ``at [n]`` known to lie inside
    it (a permutation's image): a bare gather, where ``jnp.take`` wraps
    negative indices and fills the ones outside."""
    return jax.lax.gather(
        a, at[:, None], jax.lax.GatherDimensionNumbers(
            offset_dims=tuple(range(1, a.ndim)), collapsed_slice_dims=(0,),
            start_index_map=(0,)),
        (1,) + a.shape[1:], mode="promise_in_bounds")


def _some_rows(x, source, kept, visited_alone):
    """``x[source]`` as far as its first ``kept`` rows, covered to the pass:
    a pass gathers ``moved_chunk`` rows and lays them side by side; both
    slices clamp alike at the end. The trip count is read on the device.
    The rows no pass reaches are zeros, or with ``visited_alone`` never
    written at all (``grouped_matmul.unwritten``)."""
    shape = (source.shape[0], x.shape[1])
    rows = (grouped_matmul.unwritten(shape, x.dtype) if visited_alone
            else jnp.zeros(shape, x.dtype))
    C = moved_chunk(shape[0])

    def a_pass(i, rows):
        at = jax.lax.dynamic_slice(source, (i * C,), (C,))
        return jax.lax.dynamic_update_slice(
            rows, _rows_at(x, at), (i * C, 0))

    return jax.lax.fori_loop(0, jax.lax.div(kept + (C - 1), C), a_pass, rows)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kept_rows(x, source, kept, visited_alone):
    """``x[source]`` where only the first ``kept`` rows will be read
    (``source [N]`` int, a permutation's image; ``kept`` a traced count).
    ``visited_alone``: the repo's kernels multiply, and visit no row past
    the last group. Differentiated, it is the whole gather's transpose,
    over zeros."""
    return _some_rows(x, source, kept, visited_alone)


def _kept_rows_fwd(x, source, kept, visited_alone):
    return _some_rows(x, source, kept, False), (x, source)


def _kept_rows_bwd(visited_alone, res, g):
    x, source = res
    _, vjp = jax.vjp(lambda x: jnp.take(x, source, axis=0), x)
    return vjp(g) + (None, None)


_kept_rows.defvjp(_kept_rows_fwd, _kept_rows_bwd)


def _weighted_sum(out, back, weights, keep, dtype):
    """Each position's ``K`` expert outputs, ``out[back[t, k]]`` where
    ``keep[t, k]`` and zero elsewhere (a pair not kept: its row was never
    written), times ``weights [T, K]``, summed in float32: the whole
    combine, one gather of every pair's row."""
    T, K = weights.shape
    got = jnp.take(out, back.reshape(T * K), axis=0).reshape(T, K, -1)
    return jnp.einsum("tkh,tk->th", jnp.where(keep[:, :, None], got, 0.0),
                      weights).astype(dtype)


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _kept_sum(out, back, weights, keep, dtype):
    """``_weighted_sum`` by the positions that have a kept pair alone: they
    are visited first (a stable sort of ``[T]``), a pass gathers the ``K``
    rows of ``moved_chunk`` positions in one gather, ``k`` outermost, and
    sums them in float32 in ``k`` order. What a pass only reads (each
    pair's row and weight) is taken in visit order once, before the loop,
    and a pass slices it. A pair not kept has no row of its own (nobody
    wrote one): it reads the first sorted row at a weight of zero, which a
    kept pair's output fills whenever there is a pass at all. The sums are
    laid side by side and gathered back to the positions' order after the
    loop: a scatter inside a loop whose trip count the device reads stalls
    a v5e (PERF.md section 7; ``tests/test_scatter_in_loop_stall.py``),
    and the two sorts of ``[T]`` are its price. The positions not visited
    come out zero. Differentiated, it is ``_weighted_sum``."""
    T, K = weights.shape
    H = out.shape[1]
    wanted = jnp.any(keep, axis=1)
    visit = jnp.argsort(~wanted)                  # stable: the wanted first
    C = moved_chunk(T, K)
    w = _rows_at(jnp.where(keep, weights, 0.0), visit)            # [T, K]
    source = _rows_at(jnp.where(keep, back, 0), visit).T          # [K, T]

    def a_pass(i, sums):
        w_i = jax.lax.dynamic_slice(w, (i * C, 0), (C, K))
        at = jax.lax.dynamic_slice(source, (0, i * C), (K, C))
        got = _rows_at(out, at.reshape(K * C)).reshape(K, C, H)
        acc = w_i[:, 0, None] * got[0]
        for k in range(1, K):
            acc = acc + w_i[:, k, None] * got[k]
        return jax.lax.dynamic_update_slice(sums, acc.astype(dtype),
                                            (i * C, 0))

    sums = jax.lax.fori_loop(
        0, jax.lax.div(jnp.sum(wanted, dtype=jnp.int32) + (C - 1), C),
        a_pass, jnp.zeros((T, H), dtype))
    return _rows_at(sums, jnp.argsort(visit))


def _kept_sum_fwd(out, back, weights, keep, dtype):
    return _kept_sum(out, back, weights, keep, dtype), (out, back, weights,
                                                        keep)


def _kept_sum_bwd(dtype, res, g):
    out, back, weights, keep = res
    _, vjp = jax.vjp(lambda o, w: _weighted_sum(o, back, w, keep, dtype),
                     out, weights)
    d_out, d_weights = vjp(g)
    return d_out, None, d_weights, None


_kept_sum.defvjp(_kept_sum_fwd, _kept_sum_bwd)


def expert_ffn(cfg, h: jax.Array, lp: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``h [B, S, H]`` (after the block's second norm) -> the routed
    feed-forward's output ``[B, S, H]`` and the router's books of this
    layer: ``pairs [E]`` (how many (position, expert) pairs each expert
    took), ``prob [E]`` (the router's scores summed over positions)
    and ``positions`` (how many were counted), all float32 and all over the
    positions where ``in_stack``'s mask is true (every position without
    one). ``lp`` is the layer's leaves, as they are or from ``in_stack``.
    Under ``in_stack``'s ``skip_unmasked`` the masked-out positions' pairs
    are not multiplied and their routed part is zero (the shared experts'
    part, every position's, stays); the bare mask keeps them out of the
    books only, and they are computed like any other. With a share of the
    experts (``experts_held``) the output is the held experts' part of the
    routed sum (and the shared experts', whole), ``pairs`` stays over all
    the experts and ``pairs_here [count]`` is the held ones' own; a share
    reads the bare mask as ``skip_unmasked`` too (the legacy line below)."""
    dt = cfg.dtype
    where, mask, skip = lp.get(_WHERE), lp.get(_MASK), lp.get(_SKIP)
    B, S, H = h.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    first, count = held_experts(cfg)
    T = B * S
    x = h.reshape(T, H)
    if cfg.router_groups and "router_bias" in lp \
            and cfg.router_group_score != "top2":
        raise ValueError("the group-limited choice by a group's best "
                         "expert has no bias on it here")
    if cfg.router_scores not in ROUTER_SCORES:
        raise ValueError(f"router_scores {cfg.router_scores!r}: expected "
                         + "|".join(ROUTER_SCORES))
    with jax.named_scope("moe_router"):
        # logits, scores and the chosen weights in float32; the operands
        # are the activations and the router as every other matmul has them
        logits = jnp.einsum("th,he->te", x, lp["router"].astype(dt),
                            preferred_element_type=jnp.float32)
        probs = (jax.nn.sigmoid(logits) if cfg.router_scores == "sigmoid"
                 else jax.nn.softmax(logits, axis=-1))
        if cfg.router_groups and "router_bias" in lp:
            # noaux_tc: the groups scored and the experts chosen on the
            # scores plus the bias, the weights the scores without it
            _, chosen = jax.lax.top_k(_best_groups(
                cfg, probs + jax.lax.stop_gradient(
                    lp["router_bias"].astype(jnp.float32))), K)
            weights = jnp.take_along_axis(probs, chosen, axis=-1)
        elif cfg.router_groups:
            # among the experts of each position's best groups
            weights, chosen = jax.lax.top_k(_best_groups(cfg, probs), K)
        elif "router_bias" in lp:
            # the bias moves the choice and not the weights
            _, chosen = jax.lax.top_k(
                probs + jax.lax.stop_gradient(
                    lp["router_bias"].astype(jnp.float32)), K)
            weights = jnp.take_along_axis(probs, chosen, axis=-1)
        else:
            weights, chosen = jax.lax.top_k(probs, K)        # [T, K]
        if cfg.norm_topk_prob:
            total = jnp.sum(weights, axis=-1, keepdims=True)
            if cfg.router_norm_eps:
                total = total + cfg.router_norm_eps
            weights = weights / total
        if cfg.routed_scaling_factor != 1.0:
            weights = weights * cfg.routed_scaling_factor
    with jax.named_scope("moe_dispatch"):
        flat = chosen.reshape(T * K)
        by = flat
        # the pairs to multiply: their expert is held here AND their
        # position is wanted; None where both hold of every pair
        keep = None
        if count < E:
            keep = (chosen >= first) & (chosen < first + count)   # [T, K]
        # `skip or`: the one statement of the rule. `count < E` is the
        # legacy line: a share reads the books' bare mask as the positions
        # wanted, which tests/benchmark/test_deepseek_v2.py::
        # test_a_share_keeps_the_padding_off_and_drops_nothing pins, while
        # test_olmoe.py::test_the_routers_books_count_live_positions_only
        # pins `y_masked == y_all` for the same call on a model that holds
        # all its experts; once a `benchmark` issue drops that assertion
        # the two fold into `skip` alone (PERF.md section 7)
        if mask is not None and (skip or count < E):
            # a step's padding is one token repeated, which routes alike:
            # thousands of its rows on whichever experts it likes are work
            # for no result (four fifths of a serving step's pairs, PERF.md)
            wanted = mask.reshape(T, 1)
            keep = wanted if keep is None else keep & wanted
        if keep is not None:
            # the kept pairs first, by expert; the others past them, in no
            # group, and weightless in the combine
            weights = jnp.where(keep, weights, 0.0)
            by = jnp.where(keep, chosen - first, count).reshape(T * K)
        order = jnp.argsort(by)                   # stable: pairs by expert
        onehot = flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :]
        sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)     # [E]
        if keep is not None:  # the kept pairs', over the held experts
            sizes = jnp.sum(by[:, None] == jnp.arange(
                count, dtype=by.dtype)[None, :], axis=0, dtype=jnp.int32)
        in_place = where is not None and lp["we_gate"].dtype == dt
        if keep is None:
            rows = jnp.take(x, order // K, axis=0)           # [T*K, H]
        else:  # the kept pairs' rows, and no other
            rows = _kept_rows(x, order // K, jnp.sum(sizes),
                              in_place and _kernel_takes(where[0]["we_gate"]))
    with jax.named_scope("moe_experts"):
        if not in_place:
            # the layer's slices are cast on their way in, which is the
            # copy: multiply by them (float32 master weights in training)
            def matmul(x, name, out_dtype):
                return jax.lax.ragged_dot(x, lp[name].astype(dt), sizes,
                                          preferred_element_type=out_dtype)

            hidden = (jax.nn.silu(matmul(rows, "we_gate", dt))
                      * matmul(rows, "we_up", dt))
            out = matmul(hidden, "we_down", jnp.float32)
        else:
            stacks, layer = where
            hidden = _gated_in_place(
                rows, lp["we_gate"], lp["we_up"], sizes, stacks["we_gate"],
                stacks["we_up"], layer)
            out = _in_place(hidden, lp["we_down"], sizes, stacks["we_down"],
                            layer, jnp.float32)
    with jax.named_scope("moe_combine"):
        # back to the pairs' own order, then the weighted sum of each
        # position's K expert outputs, in float32
        back = jnp.argsort(order)
        if keep is None:
            out = jnp.take(out, back, axis=0).reshape(T, K, H)
            y = jnp.einsum("tkh,tk->th", out, weights).astype(dt)
        else:  # the positions that have a kept pair, and no other
            y = _kept_sum(out, back.reshape(T, K), weights,
                          jnp.broadcast_to(keep, (T, K)), dt)
    if "ws_gate" in lp:
        with jax.named_scope("moe_shared"):
            act = (jax.nn.silu(jnp.einsum("th,hm->tm", x,
                                          lp["ws_gate"].astype(dt)))
                   * jnp.einsum("th,hm->tm", x, lp["ws_up"].astype(dt)))
            y = y + jnp.einsum("tm,mh->th", act, lp["ws_down"].astype(dt))
    live = (jnp.ones((T,), jnp.float32) if mask is None
            else mask.reshape(T).astype(jnp.float32))
    books = {"pairs": jnp.einsum("t,te->e", jnp.repeat(live, K),
                                 onehot.astype(jnp.float32)),
             "prob": jnp.einsum("t,te->e", live, probs),
             "positions": jnp.sum(live)}
    if count < E:
        books["pairs_here"] = books["pairs"][first:first + count]
    return y.reshape(B, S, H), books


def load_balancing_loss(books: Dict[str, jax.Array], cfg) -> jax.Array:
    """The Switch load-balancing term over all layers' routers together,
    as HuggingFace's ``load_balancing_loss_func`` computes it: ``E`` times
    the sum over experts of (the share of all (layer, position) pairs'
    choices that went to the expert) x (the router's mean probability for
    it). ``books`` is ``expert_ffn``'s, stacked over layers. The gradient
    flows through the probabilities alone."""
    n = jnp.sum(books["positions"])
    share = jnp.sum(books["pairs"], axis=0) / n
    prob = jnp.sum(books["prob"], axis=0) / n
    return cfg.num_experts * jnp.sum(jax.lax.stop_gradient(share) * prob)


def router_load(books: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """For each layer, the pairs the fullest expert took and the pairs the
    mean expert took (``[L]`` float32 each): what a serving step brings to
    the host beside its token ids. With a share of the experts both are
    over the experts held, and ``all [L]``, the pairs the router made over
    every expert, comes with them."""
    if "pairs_here" not in books:
        return {"fullest": jnp.max(books["pairs"], axis=-1),
                "mean": jnp.mean(books["pairs"], axis=-1)}
    return {"fullest": jnp.max(books["pairs_here"], axis=-1),
            "mean": jnp.mean(books["pairs_here"], axis=-1),
            "all": jnp.sum(books["pairs"], axis=-1)}
