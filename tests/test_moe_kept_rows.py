"""Where some pairs are not kept, the routed feed-forward moves the kept
pairs' rows alone (``models/moe.py::_kept_rows`` and ``_kept_sum``): both
against the whole gather and ``_weighted_sum`` at every trip count from
none to all, with the rows in and out of order and with a share's absent
experts; the gradient is the whole form's; no program holds a scatter
inside a loop (PERF.md section 7: that stalls a v5e); ``engine_stats()``
counts the rows moved; and a call with no mask is the program it was."""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.models.llama import init_llama, llama_next_token

from tests.test_expert_padding import (
    SHAPES, drawn, ffn, generator, routed_layers)


@pytest.fixture
def small_passes(monkeypatch):
    """Passes of 8 rows and of 4 positions, so that a few dozen rows are
    many trip counts (every test here jits a function of its own, so none
    meets a program traced at other sizes)."""
    monkeypatch.setattr(moe, "moved_chunk", lambda rows, pairs=1: min(
        rows, 8 if pairs == 1 else 4))


def test_a_pass_is_a_pure_function_of_the_shapes():
    assert moe.moved_chunk(8 * 8 * 1152) == 8192    # the dispatch's pass
    assert moe.moved_chunk(100) == 100
    # the combine's: the K rows of so many positions, in whole lane tiles
    assert moe.moved_chunk(8 * 1152, 8) == 1024
    assert moe.moved_chunk(8 * 1792, 6) == 1280
    assert moe.moved_chunk(8 * 1408, 4) == 2048
    assert moe.moved_chunk(16, 2) == 16


# ------------------------------------------------------------ the dispatch
# 44 rows: the pass of 8 does not divide them, so the last pass clamps
@pytest.mark.parametrize("order", ["sorted", "shuffled"])
@pytest.mark.parametrize("visited_alone", [False, True])
def test_kept_rows_are_the_whole_gathers_at_every_trip_count(
        order, visited_alone, small_passes):
    x = jax.random.normal(jax.random.key(0), (11, 128))
    source = jnp.arange(44) // 4
    if order == "shuffled":
        source = jax.random.permutation(jax.random.key(1), source)
    whole = np.asarray(jnp.take(x, source, axis=0))
    run = jax.jit(lambda kept: moe._kept_rows(x, source, kept,
                                              visited_alone))
    for kept in range(45):
        rows = np.asarray(run(jnp.int32(kept)))
        np.testing.assert_array_equal(rows[:kept], whole[:kept])
        covered = min(-(-kept // 8) * 8, 44)
        if visited_alone:   # interpreted, an unwritten row reads NaN
            assert np.isnan(rows[covered + 8:]).all()
        else:
            assert not rows[covered + 8:].any()


def test_kept_rows_differentiate_as_the_whole_gather(small_passes):
    x = jax.random.normal(jax.random.key(0), (11, 128))
    source = jax.random.permutation(jax.random.key(1), jnp.arange(44) // 4)
    g = jax.random.normal(jax.random.key(2), (44, 128))
    kept = jnp.int32(19)
    # what nobody reads carries no gradient: the rows past the kept ones
    g = g.at[19:].set(0.0)
    got = jax.grad(lambda x: jnp.sum(
        moe._kept_rows(x, source, kept, True)[:19] * g[:19]))(x)
    want = jax.grad(lambda x: jnp.sum(jnp.take(x, source, axis=0) * g))(x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


# ------------------------------------------------------------- the combine
def combine_case(layout, wanted_count, T=22, K=3, H=128, seed=0):
    """``out``, ``back``, ``weights``, ``keep`` of ``T`` positions of ``K``
    pairs: ``wanted_count`` positions have kept pairs, the first ones
    (``prefix``: a serving step's rows), any (``scattered``), or any with
    only some of a position's pairs kept (``share``: the other experts are
    on other chips). A row no kept pair points to holds NaN."""
    ks = jax.random.split(jax.random.key(seed + wanted_count), 5)
    wanted = np.zeros(T, bool)
    wanted[:wanted_count] = True
    if layout != "prefix":
        wanted = np.asarray(jax.random.permutation(ks[0], wanted))
    keep = np.repeat(wanted[:, None], K, axis=1)
    if layout == "share":
        keep &= np.asarray(jax.random.bernoulli(ks[1], 0.4, (T, K)))
    # the kept pairs first, as the dispatch's sort leaves them
    by = np.where(keep.reshape(-1), 0, 1)
    order = np.argsort(by, kind="stable")
    back = np.argsort(order).reshape(T, K)
    out = np.array(jax.random.normal(ks[2], (T * K, H)))
    out[int(keep.sum()):] = np.nan
    weights = np.where(keep, np.asarray(jax.random.uniform(ks[3], (T, K))),
                       0.0).astype(np.float32)
    return (jnp.asarray(out), jnp.asarray(back, jnp.int32),
            jnp.asarray(weights), jnp.asarray(keep))


@pytest.mark.parametrize("layout", ["prefix", "scattered", "share"])
def test_kept_sums_are_the_whole_combines_at_every_trip_count(
        layout, small_passes):
    run = jax.jit(lambda *a: moe._kept_sum(*a, jnp.float32))
    whole = jax.jit(lambda *a: moe._weighted_sum(*a, jnp.float32))
    for wanted_count in range(23):   # 22 positions: the pass of 4 clamps
        case = combine_case(layout, wanted_count)
        got, want = np.asarray(run(*case)), np.asarray(whole(*case))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        unwanted = ~np.asarray(case[3]).any(axis=1)
        assert not got[unwanted].any()


def test_kept_sums_round_once_from_float32(small_passes):
    case = combine_case("scattered", 9)
    got = moe._kept_sum(*case, jnp.bfloat16)
    want = np.asarray(moe._weighted_sum(*case, jnp.float32))
    assert got.dtype == jnp.bfloat16
    # half a place of a bfloat16 off the float32 sum, and no more
    assert (np.abs(np.asarray(got, np.float32) - want)
            <= 2.0 ** -8 * np.abs(want) + 1e-6).all()


def test_kept_sums_differentiate_as_the_whole_combine(small_passes):
    out, back, weights, keep = combine_case("share", 13)
    g = jax.random.normal(jax.random.key(9), (22, 128))

    def loss(f):
        return lambda o, w: jnp.sum(f(o, back, w, keep, jnp.float32) * g)

    got = jax.grad(loss(moe._kept_sum), argnums=(0, 1))(out, weights)
    want = jax.grad(loss(moe._weighted_sum), argnums=(0, 1))(out, weights)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


# --------------------------------------------------- through ``expert_ffn``
@pytest.fixture(params=["kernel", "ragged_dot"])
def path(request, monkeypatch):
    if request.param == "ragged_dot":
        monkeypatch.setattr(moe, "_kernel_takes", lambda stack: False)
    return request.param


def whole_form(monkeypatch):
    """``expert_ffn`` as it was: every pair's row gathered and gathered
    back (zeros where a pair is not kept)."""
    monkeypatch.setattr(moe, "_kept_rows", lambda x, source, kept, alone:
                        jnp.take(x, source, axis=0))
    monkeypatch.setattr(moe, "_kept_sum", moe._weighted_sum)


@pytest.mark.parametrize("live", [0, 1, 8, 9, 23, 41, 48])
@pytest.mark.parametrize("shape", ["olmoe", "lfm2"])
def test_a_masked_layer_is_the_whole_forms_at_every_trip_count(
        shape, live, path, small_passes, monkeypatch):
    """2 x 24 positions of 2 pairs: passes of 8 rows and of 4 positions, the
    mask a prefix of each row as a serving step hands it."""
    cfg = SHAPES[shape]()
    layers = routed_layers(cfg, drawn(cfg))
    h = jax.random.normal(jax.random.key(3), (2, 24, cfg.hidden))
    lens = jnp.array([[min(live, 24)], [max(live - 24, 0)]])
    mask = jnp.arange(24)[None, :] < lens
    got, books = ffn(cfg, layers, h, mask, skip=True)
    whole_form(monkeypatch)
    want, books_whole = ffn(cfg, layers, h, mask, skip=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-7)
    assert not np.asarray(got)[~np.asarray(mask)].any()
    for name in books:
        np.testing.assert_array_equal(books[name], books_whole[name])


@pytest.mark.parametrize("held", [(0, 2), (2, 4), (6, 2)])
def test_a_share_with_absent_experts_is_the_whole_forms(
        held, path, small_passes, monkeypatch):
    cfg = dataclasses.replace(SHAPES["shared"](), experts_held=held)
    layers = routed_layers(cfg, drawn(cfg))
    h = jax.random.normal(jax.random.key(3), (2, 24, cfg.hidden))
    mask = jnp.arange(24)[None, :] < jnp.array([[20], [5]])
    got, books = ffn(cfg, layers, h, mask)
    whole_form(monkeypatch)
    want, _ = ffn(cfg, layers, h, mask)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-7)
    assert float(jnp.sum(books["pairs_here"])) <= 25 * 2


@pytest.mark.parametrize("shape", ["olmoe", "lfm2"])
def test_the_gradient_under_a_mask_is_the_whole_forms(
        shape, path, small_passes, monkeypatch):
    cfg = SHAPES[shape]()
    layers = routed_layers(cfg, drawn(cfg))
    h = jax.random.normal(jax.random.key(3), (2, 24, cfg.hidden))
    mask = jnp.arange(24)[None, :] < jnp.array([[20], [5]])
    names = ("router", "we_gate", "we_up", "we_down")

    def loss(h, leaves):
        lp = moe.in_stack({n: a[1] for n, a in {**layers, **leaves}.items()},
                          {**layers, **leaves}, 1, mask, skip_unmasked=True)
        y, _ = moe.expert_ffn(cfg, h, lp)
        return jnp.sum(jnp.where(mask[:, :, None], y, 0.0) ** 2)

    grad = lambda: jax.jit(jax.grad(loss, argnums=(0, 1)))(
        h, {n: layers[n] for n in names})
    got = grad()
    whole_form(monkeypatch)
    want = grad()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.isfinite(np.asarray(a)).all()
        # the largest difference seen: 2.9e-6 at a gradient of 0.11
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ----------------------------------------------- what the programs hold
def loops_of(jaxpr, found=None):
    """Every ``while`` of a jaxpr, however deep, with the primitives of its
    body and condition (their own sub-jaxprs included)."""
    found = [] if found is None else found

    def subjaxprs(eqn):
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(j, "jaxpr"):
                    yield j.jaxpr
                elif hasattr(j, "eqns"):
                    yield j

    def primitives(j):
        for eqn in j.eqns:
            yield eqn.primitive.name
            for sub in subjaxprs(eqn):
                yield from primitives(sub)

    for eqn in jaxpr.eqns:
        subs = list(subjaxprs(eqn))
        if eqn.primitive.name == "while":
            found.append(sorted({p for s in subs for p in primitives(s)}))
        for s in subs:
            loops_of(s, found)
    return found


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_no_step_program_holds_a_scatter_inside_a_loop(shape):
    cfg = SHAPES[shape](jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    step = jax.make_jaxpr(lambda p, t, i, on: llama_next_token(
        p, t, i, cfg, live=on))(
            shapes, jax.ShapeDtypeStruct((2, 32), jnp.int32),
            jax.ShapeDtypeStruct((2,), jnp.int32),
            jax.ShapeDtypeStruct((2, 32), jnp.bool_))
    loops = loops_of(step.jaxpr)
    # the kept rows' and the kept sums' of each run of routed layers
    moved = [p for p in loops if "gather" in p and "dynamic_update_slice" in p
             and "scan" not in p and "while" not in p]
    assert len(moved) == 2 * len([r for r in cfg.layer_runs()
                                  if r[0].endswith("_routed")])
    for primitives in moved:
        assert not [p for p in primitives if p.startswith("scatter")]
        assert "cond" not in primitives


def test_with_no_mask_a_layer_is_the_program_it_was():
    """``keep is None``: one whole gather each way and no loop; through
    ``ragged_dot`` the jaxpr is the parent's (``6f115a1``) to the
    character."""
    cfg = SHAPES["olmoe"](jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    layers = routed_layers(cfg, shapes)
    h = jax.ShapeDtypeStruct((2, 32, cfg.hidden), jnp.bfloat16)
    lp = {n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
          for n, a in layers.items()}
    text = str(jax.make_jaxpr(lambda h, lp: moe.expert_ffn(cfg, h, lp))(
        h, lp))
    assert "while" not in text and text.count("gather") == 2
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        "cb7331e485b12f90"


# -------------------------------------------------- the engine's counters
@pytest.mark.parametrize("shape,routed", [("olmoe", 2), ("lfm2", 3)])
def test_step_counts_the_rows_it_moved(shape, routed):
    """``expert_rows_moved``: a layer's kept pairs covered to the pass,
    over routed layers and steps, beside ``expert_rows_all``; reckoned on
    the host from the load a step brings back already."""
    gen = generator(SHAPES[shape](), 2)
    try:
        assert gen.engine_stats()["expert_rows_moved"] == 0
        gen._step("", [gen._prefill({"prompt": list(range(2, 12))}, ""),
                       None])
        s = gen.engine_stats()
        # 2 x 16 positions of 2 pairs: one pass of all 64 rows covers any
        assert moe.moved_chunk(64) == 64
        assert s["expert_rows_all"] == 2 * 16 * 2 * routed
        assert s["expert_rows_moved"] == 64 * routed
        assert s["host_bytes"] == 2 * 4 + 8 * routed
    finally:
        gen.engine.shutdown()


def test_rows_moved_are_the_kept_pairs_covered_to_the_pass(monkeypatch):
    monkeypatch.setattr(moe, "moved_chunk", lambda rows, pairs=1: min(rows, 8))
    gen = generator(SHAPES["olmoe"](), 2)
    try:
        gen._step("", [gen._prefill({"prompt": list(range(2, 12))}, ""),
                       None])
        s = gen.engine_stats()
        # 10 live positions of 2 pairs: 20 kept rows, three passes of 8
        assert s["expert_pairs_here"] == 20 * 2
        assert s["expert_rows_moved"] == 24 * 2
    finally:
        gen.engine.shutdown()
