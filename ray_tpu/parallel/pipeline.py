"""Pipeline parallelism: GPipe-style microbatch schedule over a ``stage``
mesh axis.

The reference has no native pipeline parallelism — it defers to DeepSpeed
configs passed through Train (SURVEY §2.5: "PP via integrations only",
reference: python/ray/train/lightning/_lightning_utils.py:126). Here PP is a
first-class mesh axis: each device along ``stage`` holds one pipeline
stage's parameters, activations flow stage→stage over ICI with
``lax.ppermute``, and the whole schedule is a single ``lax.scan`` inside
``shard_map`` — one compiled SPMD program, no host round-trips between
microbatches.

Schedule: classic GPipe fill/drain. With S stages and M microbatches the
scan runs S+M-1 ticks; tick t has stage s working on microbatch t-s (idle
ticks compute on garbage and are masked out — on TPU a masked matmul costs
the same as control flow and keeps the program static). Bubble fraction is
(S-1)/(S+M-1); callers pick M >= 4*S to amortize.

Gradients flow through the same program via ``jax.grad`` — XLA reverses the
ppermute ring automatically, giving the backward pipeline for free.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

STAGE_AXIS = "stage"


def num_stages(mesh: Mesh, axis: str = STAGE_AXIS) -> int:
    if axis not in mesh.shape:
        raise ValueError(f"mesh {dict(mesh.shape)} has no '{axis}' axis")
    return mesh.shape[axis]


def init_stage_params(
    init_fn: Callable[[jax.Array], Any],
    n_stages: int,
    mesh: Mesh,
    *,
    axis: str = STAGE_AXIS,
    seed: int = 0,
) -> Any:
    """Initialize per-stage params stacked on a leading stage dim, sharded
    over the stage axis (each device materializes only its own stage)."""
    keys = jax.random.split(jax.random.key(seed), n_stages)

    def init_all(keys):
        return jax.vmap(init_fn)(keys)

    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, P(axis)),
        jax.eval_shape(init_all, keys))
    return jax.jit(init_all, out_shardings=shardings)(keys)


def stage_param_spec(params_stacked: Any, axis: str = STAGE_AXIS) -> Any:
    """in_specs pytree for stacked stage params: leading dim on ``axis``."""
    return jax.tree.map(lambda _: P(axis), params_stacked)


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    mesh: Mesh,
    *,
    axis: str = STAGE_AXIS,
    data_axis: Optional[Sequence[str]] = ("data",),
    num_microbatches: Optional[int] = None,
) -> jax.Array:
    """Apply S pipeline stages to ``x`` with microbatch pipelining.

    Args:
      stage_fn: ``(params_for_one_stage, h) -> h`` with unchanged shape/dtype
        (the classic homogeneous-stage contract; embed/unembed live outside
        or inside stage_fn guarded by ``lax.cond`` on the stage index).
      stage_params: pytree stacked on a leading ``n_stages`` dim (see
        :func:`init_stage_params`).
      x: ``[batch, ...]`` activations. Split into ``num_microbatches`` equal
        microbatches on the leading dim.
      data_axis: mesh axes the batch dim is additionally sharded over
        (DP x PP meshes); None/() for pure PP.

    Returns ``[batch, ...]`` output, batch-sharded like the input.
    """
    S = num_stages(mesh, axis)
    data_axes = tuple(a for a in (data_axis or ()) if a in mesh.shape)
    data_size = 1
    for a in data_axes:
        data_size *= mesh.shape[a]

    def _valid(m: int) -> bool:
        return x.shape[0] % m == 0 and (x.shape[0] // m) % data_size == 0

    if num_microbatches is None:
        # Largest M <= 4*S that divides the batch and leaves each
        # microbatch divisible across the data axes.
        M = next((m for m in range(min(4 * S, x.shape[0]), 0, -1)
                  if _valid(m)), 1)
    else:
        M = num_microbatches
    if not _valid(M):
        raise ValueError(
            f"batch {x.shape[0]} not divisible into {M} microbatches "
            f"across data axes of size {data_size}")

    batch_spec = P(data_axes if data_axes else None)
    micro_spec = P(None, *batch_spec)  # [M, mb, ...]

    perm = [(i, (i + 1) % S) for i in range(S)]

    def staged(params_stk, xs):
        # Inside shard_map each device holds one stage: squeeze the
        # (sharded, now size-1) leading dim.
        params = jax.tree.map(lambda a: jnp.squeeze(a, 0), params_stk)
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            act, outs = carry
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
            h = jnp.where(stage == 0, mb_in, act)
            y = stage_fn(params, h)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            is_out = jnp.logical_and(stage == S - 1, t >= S - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, out_idx, 0,
                                               keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(is_out, y, cur), out_idx, 0)
            act_next = jax.lax.ppermute(y, axis, perm)
            return (act_next, outs), None

        act0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            tick, (act0, outs0), jnp.arange(S + M - 1))
        # Only the last stage holds real outputs; psum replicates them
        # across the stage ring (activation-sized, rides ICI once).
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    shard = jax.shard_map(
        staged, mesh=mesh,
        in_specs=(stage_param_spec(stage_params, axis), micro_spec),
        out_specs=micro_spec,
        check_vma=False,
    )

    mb = x.shape[0] // M
    xs = x.reshape((M, mb) + x.shape[1:])
    ys = shard(stage_params, xs)
    return ys.reshape(x.shape[0:1] + ys.shape[2:])


def make_pipeline_train_step(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    loss_fn: Callable[[jax.Array, Any], jax.Array],
    tx,
    mesh: Mesh,
    stage_params: Any,
    *,
    axis: str = STAGE_AXIS,
    data_axis: Optional[Sequence[str]] = ("data",),
    num_microbatches: Optional[int] = None,
):
    """Jitted ``step((params, opt_state), (x, target)) -> ((params, opt),
    metrics)`` where the forward is the microbatch pipeline and the backward
    is its transpose (XLA reverses the ppermute ring).

    loss_fn: ``(pipeline_output, target) -> scalar``.
    """
    import optax

    def total_loss(params, x, target):
        y = pipeline_apply(stage_fn, params, x, mesh, axis=axis,
                           data_axis=data_axis,
                           num_microbatches=num_microbatches)
        return loss_fn(y, target)

    def step(carry, batch):
        params, opt_state = carry
        x, target = batch
        loss, grads = jax.value_and_grad(total_loss)(params, x, target)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), {"loss": loss}

    return jax.jit(step, donate_argnums=(0,))
