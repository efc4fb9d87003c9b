"""The grouped-matmul kernel (``ops/pallas/grouped_matmul.py``), interpreted
on the CPU at lane-grid shapes: against a per-group numpy matmul and
``jax.lax.ragged_dot``; read in place from a stack of layers; the fused
gate-up epilogue; the tile rule; and ``models/moe.py::expert_ffn`` through
the kernel against itself through ``ragged_dot``, with what each program's
jaxpr holds."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, init_llama, llama_loss, llama_next_token)
from ray_tpu.ops.pallas import grouped_matmul as gm

BUCKET_ROWS = [8 * 8 * s for s in range(128, 1153, 128)]   # the nine steps'

# 512 rows in 4 groups: one tile of 512, strips of 128
SIZES = {
    "flat": [128, 128, 128, 128],
    "fullest_at_3x_the_mean": [384, 40, 60, 28],
    "an_empty_group": [200, 0, 212, 100],
    "empty_first_and_last": [0, 300, 212, 0],
    "one_group_holds_every_row": [0, 0, 512, 0],
    "boundaries_off_the_tile_and_the_strip": [130, 127, 1, 254],
    "rows_past_the_last_group": [100, 100, 100, 84],
}


def operands(seed, m, k, n, groups, dtype):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (m, k), dtype)
    w = (jax.random.normal(ks[1], (groups, k, n), jnp.float32)
         * k ** -0.5).astype(dtype)
    u = (jax.random.normal(ks[2], (groups, k, n), jnp.float32)
         * k ** -0.5).astype(dtype)
    return x, w, u


def per_group(x, w, sizes, first=0):
    """Each group's rows times its matrix, in float64; zeros past them
    (the kernel leaves those rows as they were: compare up to ``sum(sizes)``)."""
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    out = np.zeros((x.shape[0], w.shape[2]))
    at = 0
    for e, size in enumerate(sizes):
        out[at:at + size] = x[at:at + size] @ w[first + e]
        at += size
    return out


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k,n", [(128, 256), (256, 128)])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_against_numpy_and_ragged_dot(case, k, n, out_dtype):
    sizes = SIZES[case]
    x, w, _ = operands(1, 512, k, n, len(sizes), jnp.bfloat16)
    s = jnp.asarray(sizes, jnp.int32)
    got = gm.grouped_matmul(x, w, s, 0, out_dtype)
    assert got.shape == (512, n) and got.dtype == out_dtype
    want = per_group(x, w, sizes)
    ragged = jax.lax.ragged_dot(x, w, s, preferred_element_type=out_dtype)
    step = 2 ** -7 if out_dtype == jnp.bfloat16 else 1e-5   # of values to 4
    own = sum(sizes)   # the rows past the last group get no visit
    np.testing.assert_allclose(np.asarray(got, np.float64)[:own], want[:own],
                               atol=4 * step, rtol=0)
    np.testing.assert_allclose(np.asarray(got, np.float64)[:own],
                               np.asarray(ragged, np.float64)[:own],
                               atol=4 * step, rtol=0)
    # interpreted, a row nobody wrote reads NaN
    assert np.isnan(np.asarray(got, np.float64)[-(-own // 512) * 512:]).all()


@pytest.mark.parametrize("rows,sizes", [
    (1280, [500, 150, 30, 600]),   # 512 does not divide 1280: a short tile
    (640, [100, 250, 34, 200]),    # boundaries in both tiles, off the strips
    (200, [60, 70, 30, 40]),       # fewer rows than two strips
    (8, [3, 0, 5, 0]),             # fewer than one
])
def test_a_row_count_the_tile_does_not_divide(rows, sizes):
    assert gm.gmm_tiles(rows, 128, 128)[0] == min(512, -(-rows // 128) * 128)
    x, w, _ = operands(2, rows, 128, 128, 4, jnp.float32)
    got = gm.grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32), 0,
                            jnp.float32)
    own = sum(sizes)
    np.testing.assert_allclose(np.asarray(got, np.float64)[:own],
                               per_group(x, w, sizes)[:own], atol=1e-4,
                               rtol=0)


# a share of the experts: most of the sorted rows belong to no group here
@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("sizes", [[100, 0, 60, 40], [0, 0, 0, 200],
                                   [1, 0, 0, 0], [0, 0, 0, 0],
                                   [300, 300, 300, 124]])
def test_the_rows_past_the_last_group_are_not_visited(sizes, fused):
    """The groups' own rows are ``ragged_dot``'s; no visit reaches past
    them (interpreted, what nobody wrote reads NaN: every tile past the
    last visited one), and with no row in any group there is no visit at
    all. Rows of NaN past the groups' end harm nothing."""
    rows, live = 1024, sum(sizes)
    x, w, u = operands(5, rows, 128, 256, 4, jnp.bfloat16)
    x = x.at[live:].set(jnp.nan)     # as `moe.py` leaves them: unwritten
    sz = jnp.asarray(sizes, jnp.int32)
    ragged = lambda m: jax.lax.ragged_dot(
        x, m, sz, preferred_element_type=jnp.float32)
    if fused:
        got = gm.grouped_swiglu(x, w, u, sz, 0, jnp.float32)
        want = jax.nn.silu(ragged(w)) * ragged(u)
    else:
        got = gm.grouped_matmul(x, w, sz, 0, jnp.float32)
        want = ragged(w)
    np.testing.assert_allclose(np.asarray(got)[:live],
                               np.asarray(want)[:live], atol=1e-4, rtol=0)
    tm = 512
    assert np.isnan(np.asarray(got)[-(-live // tm) * tm:]).all()
    tile, group, total, _, _ = gm._visits(sz, rows, tm)
    visits = int(total[0])
    assert visits == sum(-(-(e) // tm) - s // tm
                         for s, e in zip(np.cumsum([0] + sizes[:-1]),
                                         np.cumsum(sizes)) if e > s)
    # the static bound: a boundary inside a tile is one visit more
    assert tile.shape == group.shape == (rows // tm + 4 - 1,)
    assert np.all((0 <= np.asarray(group)) & (np.asarray(group) < 4))
    assert np.all((0 <= np.asarray(tile)) & (np.asarray(tile) < rows // tm))
    # the surplus repeat the last visit's blocks
    assert (np.asarray(tile)[max(visits, 1) - 1:]
            == np.asarray(tile)[max(visits, 1) - 1]).all()


def test_unwritten_is_an_array_nobody_filled():
    """No operation writes it (interpreted, it reads NaN); a program that
    takes it holds no fill of its shape."""
    rows = gm.unwritten((24, 128), jnp.bfloat16)
    assert rows.shape == (24, 128) and rows.dtype == jnp.bfloat16
    assert np.isnan(np.asarray(rows, np.float32)).all()
    text = str(jax.make_jaxpr(lambda: gm.unwritten((24, 128),
                                                   jnp.float32))())
    assert "pallas_call" in text and "broadcast_in_dim" not in text


@pytest.mark.parametrize("layer", [0, 2, 4])
@pytest.mark.parametrize("fused", [False, True])
def test_reads_its_layer_from_a_poisoned_stack(layer, fused):
    """``first_group`` picks the layer's experts where they lie: every
    other layer of the stack is NaN, and the layer's index is traced."""
    L, E, sizes = 5, 4, [100, 0, 156, 256]
    x, w, u = operands(3, 512, 128, 128, E, jnp.bfloat16)

    def stack(one):
        whole = jnp.full((L,) + one.shape, jnp.nan, one.dtype)
        return whole.at[layer].set(one).reshape((L * E,) + one.shape[1:])

    s = jnp.asarray(sizes, jnp.int32)
    if fused:
        got = jax.jit(lambda i: gm.grouped_swiglu(
            x, stack(w), stack(u), s, i * E, jnp.float32))(layer)
        gate, up = per_group(x, w, sizes), per_group(x, u, sizes)
        want = gate / (1 + np.exp(-gate)) * up
    else:
        got = jax.jit(lambda i: gm.grouped_matmul(
            x, stack(w), s, i * E, jnp.float32))(layer)
        want = per_group(x, w, sizes)
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=1e-4, rtol=0)


@pytest.mark.parametrize("out_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ["flat", "fullest_at_3x_the_mean",
                                  "boundaries_off_the_tile_and_the_strip"])
def test_the_fused_epilogue_is_silu_gate_times_up(case, out_dtype):
    sizes = SIZES[case]
    x, w, u = operands(4, 512, 256, 128, 4, jnp.bfloat16)
    s = jnp.asarray(sizes, jnp.int32)
    got = gm.grouped_swiglu(x, w, u, s, 0, out_dtype)
    assert got.dtype == out_dtype
    gate, up = per_group(x, w, sizes), per_group(x, u, sizes)
    want = gate / (1 + np.exp(-gate)) * up
    step = 2 ** -6 if out_dtype == jnp.bfloat16 else 1e-5   # of values to 8
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               atol=4 * step, rtol=0)
    if out_dtype == jnp.bfloat16:
        # rounded once from float32: no further from the exact value than
        # two matmuls rounded to bf16 and then multiplied
        def rd(m):
            return jax.lax.ragged_dot(x, m, s,
                                      preferred_element_type=jnp.bfloat16)
        twice = np.asarray(jax.nn.silu(rd(w)) * rd(u), np.float64)
        assert (np.abs(np.asarray(got, np.float64) - want).mean()
                <= np.abs(twice - want).mean())


def test_operands_of_another_type_or_shape_are_refused():
    x, w, _ = operands(5, 256, 128, 128, 4, jnp.bfloat16)
    s = jnp.asarray([64] * 4, jnp.int32)
    with pytest.raises(ValueError, match="stack"):
        gm.grouped_matmul(x, w.astype(jnp.float32), s, 0, jnp.float32)
    with pytest.raises(ValueError, match="stack"):
        gm.grouped_swiglu(x, w, w[:, :, :64], s, 0, jnp.float32)
    with pytest.raises(ValueError, match="128"):
        gm.gmm_tiles(256, 128, 352)
    assert gm.takes(2048, 1024) and not gm.takes(128, 352)
    assert not gm.takes(64, 128)


@pytest.mark.parametrize("rows", BUCKET_ROWS)
def test_the_tile_is_a_pure_function_inside_its_reckoning(rows):
    """OLMoE's three matmuls at each of the nine buckets' rows: the fused
    pair 2048 -> 1024 in bf16, and 1024 -> 2048 in float32."""
    for k, n, stacks, out, swept in ((2048, 1024, 2, 2, (512, 512)),
                                     (1024, 2048, 1, 4, (256, 2048))):
        tiles = gm.gmm_tiles(rows, k, n, stacks=stacks, out_itemsize=out)
        assert tiles == swept == gm.gmm_tiles(rows, k, n, stacks=stacks,
                                              out_itemsize=out)
        assert gm.gmm_vmem_bytes(*tiles, k, stacks=stacks,
                                 out_itemsize=out) <= gm.VMEM_LIMIT_BYTES


# ------------------------------------------------------------------------
# models/moe.py through the kernel
# ------------------------------------------------------------------------
def lane_grid_config(dtype):
    return LlamaConfig(vocab_size=256, hidden=128, mlp_hidden=128,
                       num_layers=2, num_heads=4, num_kv_heads=2, head_dim=32,
                       max_seq_len=64, remat=False, attn_impl="reference",
                       dtype=dtype, param_dtype=dtype, num_experts=4,
                       experts_per_token=2, router_aux_loss_coef=0.01)


@pytest.fixture
def through_ragged_dot(monkeypatch):
    """Steers ``moe.py`` to XLA's kernel, as off the lane grid. What was
    traced before is forgotten: the choice is no part of a cache's key."""
    def steer():
        monkeypatch.setattr(moe, "_kernel_takes", lambda stack: False)
        jax.clear_caches()
    yield steer
    jax.clear_caches()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_expert_ffn_through_the_kernel_and_through_ragged_dot(
        dtype, through_ragged_dot):
    cfg = lane_grid_config(dtype)
    params = init_llama(cfg, jax.random.key(0))
    layers = params["layers"]
    h = jax.random.normal(jax.random.key(1), (2, 64, cfg.hidden), dtype)

    def value(h, layers):
        lp = moe.in_stack({n: a[1] for n, a in layers.items()}, layers, 1)
        y, books = moe.expert_ffn(cfg, h, lp)
        return jnp.sum(jnp.square(y.astype(jnp.float32))), (y, books)

    def run():
        (loss, (y, books)), grads = jax.jit(jax.value_and_grad(
            value, argnums=(0, 1), has_aux=True))(h, layers)
        return "pallas_call" in str(jax.make_jaxpr(value)(h, layers)), (
            loss, y, books, grads)

    in_kernel, kernel = run()
    through_ragged_dot()
    in_kernel_steered, ragged = run()
    assert in_kernel and not in_kernel_steered
    tol = 1e-5 if dtype == jnp.float32 else 4e-2
    for a, b in zip(jax.tree.leaves(kernel), jax.tree.leaves(ragged)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=tol * max(1.0, np.abs(b).max()),
                                   rtol=0)
    # the weights' gradient is the layer's slice of the stack's
    g_gate = kernel[3][1]["we_gate"]
    assert g_gate.shape == layers["we_gate"].shape
    assert not np.asarray(g_gate[0], np.float32).any()
    assert np.asarray(g_gate[1], np.float32).any()


def step_jaxpr(cfg, batch=2, length=32):
    params = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((batch, length), jnp.int32)
    last = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return str(jax.make_jaxpr(
        lambda p, t, i: llama_next_token(p, t, i, cfg))(params, tokens, last))


def grad_jaxpr(cfg, batch=2, length=32):
    params = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((batch, length), jnp.int32)
    return str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: llama_loss(p, {"tokens": t}, cfg)))(params, tokens))


def test_a_lane_grid_sparse_step_holds_the_kernel_and_no_ragged_dot():
    step = step_jaxpr(lane_grid_config(jnp.bfloat16))
    assert "pallas_call" in step and "ragged_dot" not in step
    # float32 master weights are cast a layer at a time: the slice's path
    mixed = step_jaxpr(dataclasses.replace(
        lane_grid_config(jnp.bfloat16), param_dtype=jnp.float32))
    assert "pallas_call" not in mixed and "ragged_dot" in mixed
    # off the lane grid the stack is still read in place, by XLA's kernel
    off = step_jaxpr(dataclasses.replace(
        lane_grid_config(jnp.bfloat16), mlp_hidden=96))
    assert "pallas_call" not in off and "ragged_dot" in off
    # the backward is the slice's ragged_dot vjp
    grads = grad_jaxpr(lane_grid_config(jnp.bfloat16))
    assert "pallas_call" in grads and "ragged_dot" in grads


def test_a_mesh_of_more_than_one_device_keeps_ragged_dot():
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    cfg = lane_grid_config(jnp.bfloat16)
    with jax.set_mesh(create_mesh(MeshConfig(data=2, expert=4))):
        step = step_jaxpr(cfg)
        assert "pallas_call" not in step and "ragged_dot" in step
    with jax.set_mesh(create_mesh(MeshConfig(data=1), jax.devices()[:1])):
        assert "pallas_call" in step_jaxpr(cfg)


@pytest.mark.parametrize("config", ["mistral7b-serve-l16",
                                    "mistral7b-train-l2"])
def test_the_dense_programs_do_not_see_the_kernel(config,
                                                  through_ragged_dot):
    """The Mistral configurations' shapes, one layer: the serving step and
    the value-and-gradient hold no grouped matmul of either kind and are
    the same program whichever way ``moe.py`` is steered."""
    from benchmark.harness import loader, modelcfg

    cfg = dataclasses.replace(
        modelcfg.build_llama_config(loader.load_config(config)),
        num_layers=1)
    programs = [step_jaxpr(cfg, 8, 128), grad_jaxpr(cfg, 2, 513)]
    through_ragged_dot()
    assert programs == [step_jaxpr(cfg, 8, 128), grad_jaxpr(cfg, 2, 513)]
    for text in programs:   # their one kernel is attention's
        assert "ragged_dot" not in text and "grouped" not in text
