"""A step's padding stays off the routed experts (``models/moe.py::
expert_ffn`` under ``in_stack(..., skip_unmasked=True)``, which
``models/llama.py::_hidden_and_books`` says for the mask a serving step
hands it): for an OLMoE-shaped and an LFM2-shaped tiny model, through the
repo's grouped-matmul kernel (interpreted) and through ``ragged_dot``, the
rows' own positions come out what they are with every position computed
(to a float32's last place: the kept pairs' rows are summed a pass of
positions at a time, in ``k`` order, where the whole combine is one
einsum), the padding's routed part is exactly zero, the books stand, a
mask that is all false runs, no program branches, and
``LlamaGenerator._step`` emits for unequal rows of one padded batch the
tokens each row emits alone, counting the pairs it skipped."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, _by_kind, _rms_norm, init_llama, llama_next_token)


def olmoe_shaped(dtype=jnp.float32):
    """Softmax router, every layer routed, a norm over the whole query and
    key projections; on the lane grid, so the kernel takes it."""
    return LlamaConfig(vocab_size=256, hidden=128, mlp_hidden=128,
                       num_layers=2, num_heads=4, num_kv_heads=4, head_dim=32,
                       max_seq_len=64, remat=False, attn_impl="reference",
                       dtype=dtype, param_dtype=dtype, num_experts=8,
                       experts_per_token=2, qk_norm=True)


def lfm2_shaped(dtype=jnp.float32):
    """A leading dense layer, short convolutions beside attention, a
    sigmoid router that chooses on a bias and renormalises."""
    return LlamaConfig(vocab_size=256, hidden=128, mlp_hidden=128,
                       num_layers=4, num_heads=4, num_kv_heads=2, head_dim=32,
                       max_seq_len=64, remat=False, attn_impl="reference",
                       dtype=dtype, param_dtype=dtype, num_experts=8,
                       experts_per_token=2, router_scores="sigmoid",
                       router_bias=True, norm_topk_prob=True,
                       router_norm_eps=1e-20,
                       layer_types=("conv", "conv", "full_attention", "conv"),
                       num_dense_layers=1, dense_mlp_hidden=256,
                       qk_head_norm=True, tie_embeddings=True)


def with_shared_experts(dtype=jnp.float32):
    return dataclasses.replace(olmoe_shaped(dtype), num_shared_experts=2)


SHAPES = {"olmoe": olmoe_shaped, "lfm2": lfm2_shaped,
          "shared": with_shared_experts}


def drawn(cfg, seed=0):
    """The model's parameters with the router's bias drawn too (it starts
    at zeros, where it moves no choice)."""
    params = init_llama(cfg, jax.random.key(seed))
    for leaves in _by_kind(params["layers"], cfg).values():
        if "router_bias" in leaves:
            leaves["router_bias"] = 0.1 * jax.random.normal(
                jax.random.key(seed + 1), leaves["router_bias"].shape,
                leaves["router_bias"].dtype)
    return params


def routed_layers(cfg, params):
    """The leaves of the first kind of layer that has routed experts."""
    kind = next(k for k in cfg.kind_counts() if k.endswith("_routed"))
    return _by_kind(params["layers"], cfg)[kind]


@pytest.fixture(params=["kernel", "ragged_dot"])
def path(request, monkeypatch):
    """Both grouped matmuls: the repo's kernel, and XLA's as off the lane
    grid. Steered to XLA's, what was traced before is forgotten on the way
    in and out: the choice is no part of a cache's key."""
    if request.param == "ragged_dot":
        monkeypatch.setattr(moe, "_kernel_takes", lambda stack: False)
        jax.clear_caches()
    yield request.param
    if request.param == "ragged_dot":
        jax.clear_caches()


# two rows of 24: one nearly whole, one mostly padding
MASK = jnp.arange(24)[None, :] < jnp.array([[20], [5]])


def ffn(cfg, layers, h, mask=None, skip=False, layer=1):
    lp = moe.in_stack({n: a[layer] for n, a in layers.items()}, layers,
                      layer, mask, skip_unmasked=skip)
    return jax.jit(lambda h: moe.expert_ffn(cfg, h, lp))(h)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_live_positions_are_what_they_were_and_the_padding_is_zero(
        shape, path):
    cfg = SHAPES[shape]()
    layers = routed_layers(cfg, drawn(cfg))
    x = jax.random.normal(jax.random.key(3), (2, 24, cfg.hidden))
    h = _rms_norm(x, layers["mlp_norm"][1], cfg.rms_eps)
    whole, books_whole = ffn(cfg, layers, h, MASK)
    skipped, books = ffn(cfg, layers, h, MASK, skip=True)
    in_kernel = "pallas_call" in str(jax.make_jaxpr(
        lambda h: ffn(cfg, layers, h, MASK, skip=True))(h))
    assert in_kernel == (path == "kernel")
    live = np.asarray(MASK)
    # the largest difference seen over the six cases: 1.2e-7 (one place of
    # a float32 near 1), at elements of 1e-3 a relative 1.8e-4
    np.testing.assert_allclose(np.asarray(skipped)[live],
                               np.asarray(whole)[live], rtol=1e-6, atol=2e-7)
    assert np.asarray(whole)[~live].any()
    if "ws_gate" in layers:   # the shared experts are every position's
        lp = {n: a[1] for n, a in layers.items()}
        flat = h.reshape(-1, cfg.hidden)
        shared = (jax.nn.silu(flat @ lp["ws_gate"]) * (flat @ lp["ws_up"])
                  ) @ lp["ws_down"]
        np.testing.assert_allclose(
            np.asarray(skipped)[~live],
            np.asarray(shared.reshape(2, 24, -1))[~live], atol=1e-5, rtol=0)
    else:
        assert not np.asarray(skipped)[~live].any()
    # the books count the masked positions whether their pairs are
    # multiplied or not
    assert float(books["positions"]) == 25
    for name in books:
        np.testing.assert_array_equal(books[name], books_whole[name])
    # and the mask alone, as the books have always had it, skips nothing
    # where every expert is held
    plain, _ = ffn(cfg, layers, h)
    np.testing.assert_array_equal(whole, plain)


@pytest.mark.parametrize("shape", ["olmoe", "lfm2"])
def test_a_mask_that_is_all_false_runs(shape, path):
    """What ``warm_step_programs`` passes: no row holds a request, no pair
    is kept, the grouped matmuls visit nothing."""
    cfg = SHAPES[shape](jnp.bfloat16)
    params = drawn(cfg)
    none = jnp.zeros((2, 32), bool)
    ids, hidden, load = jax.jit(lambda p, t, i, on: llama_next_token(
        p, t, i, cfg, live=on))(params, jnp.zeros((2, 32), jnp.int32),
                                jnp.zeros(2, jnp.int32), none)
    assert np.isfinite(np.asarray(hidden, np.float32)).all()
    assert ((0 <= np.asarray(ids)) & (np.asarray(ids) < 256)).all()
    assert not np.asarray(load["mean"]).any()
    h = jax.random.normal(jax.random.key(4), (2, 32, cfg.hidden),
                          jnp.bfloat16)
    y, books = ffn(cfg, routed_layers(cfg, params), h, none, skip=True)
    assert not np.asarray(y, np.float32).any()
    assert float(books["positions"]) == 0


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_step_holds_no_branch_and_one_sort_a_layer(shape, path):
    cfg = SHAPES[shape](jnp.bfloat16)
    shapes = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    last = jax.ShapeDtypeStruct((2,), jnp.int32)
    live = jax.ShapeDtypeStruct((2, 32), jnp.bool_)
    masked = str(jax.make_jaxpr(lambda p, t, i, on: llama_next_token(
        p, t, i, cfg, live=on))(shapes, tokens, last, live))
    bare = str(jax.make_jaxpr(lambda p, t, i: llama_next_token(
        p, t, i, cfg))(shapes, tokens, last))
    routed_runs = len([r for r in cfg.layer_runs()
                       if r[0].endswith("_routed")])
    for text, sorts in ((masked, 4), (bare, 2)):
        assert "cond[" not in text
        # the dispatch's sort and the combine's, in each run of layers;
        # under a mask two more of `[T]`: the wanted positions first, and
        # back (the price of no scatter in the combine's loop)
        assert text.count("name=argsort") == sorts * routed_runs
    # the kept rows and the kept sums are loops; with no mask there is none
    # in a routed layer
    assert masked.count("while[") == bare.count("while[") + 2 * routed_runs
    # a caller that passes no mask computes everything: nothing to select
    assert masked.count("select_n") > bare.count("select_n")
    if path == "kernel":
        assert "ragged_dot" not in masked and "pallas_call" in masked


@pytest.mark.parametrize("shape", ["olmoe", "lfm2"])
def test_the_next_token_of_a_padded_row_is_the_rows_own(shape, path):
    """``llama_next_token`` over a padded batch, told the rows' own
    positions, against the same rows with every position computed."""
    cfg = SHAPES[shape]()
    params = drawn(cfg)
    tokens = jax.random.randint(jax.random.key(5), (3, 32), 0, 256)
    lengths = jnp.array([32, 7, 0])
    mask = jnp.arange(32)[None, :] < lengths[:, None]
    tokens = jnp.where(mask, tokens, 0)
    last = jnp.maximum(lengths - 1, 0).astype(jnp.int32)
    step = jax.jit(lambda p, t, i, on: llama_next_token(p, t, i, cfg,
                                                        live=on))
    ids, hidden, load = step(params, tokens, last, mask)
    ids_all, hidden_all, _ = jax.jit(lambda p, t, i: llama_next_token(
        p, t, i, cfg))(params, tokens, last)
    np.testing.assert_array_equal(np.asarray(ids)[:2], np.asarray(ids_all)[:2])
    # the largest difference seen: 9.5e-7 at hidden states of up to 4
    np.testing.assert_allclose(np.asarray(hidden)[np.asarray(mask)],
                               np.asarray(hidden_all)[np.asarray(mask)],
                               rtol=1e-6, atol=2e-6)
    # every live pair is on the books, and no other
    routed = sum(n for k, n in cfg.kind_counts().items()
                 if k.endswith("_routed"))
    assert float(load["mean"].sum()) * cfg.num_experts == \
        39 * cfg.experts_per_token * routed


# ------------------------------------------------------------------------
# serve/llm.py::LlamaGenerator._step
# ------------------------------------------------------------------------
def generator(cfg, rows):
    from ray_tpu.serve.llm import LlamaGenerator

    return LlamaGenerator(config=cfg, lora_rank=2, max_batch_size=rows,
                          allowed_batch_sizes=(rows,), max_new_tokens=3,
                          seq_bucket=8, seed=2)


PROMPTS = [[9, 8, 7, 6, 5, 4, 3, 2, 1, 11, 12, 13], [3, 5, 7], [21] * 6]


@pytest.mark.parametrize("shape", ["olmoe", "lfm2"])
def test_step_emits_for_unequal_rows_what_each_row_emits_alone(shape, path):
    cfg = SHAPES[shape]()
    gen = generator(cfg, 4)
    try:
        def emitted(prompts):
            states = [gen._prefill({"prompt": p, "max_new": 3}, "")
                      for p in prompts] + [None] * (4 - len(prompts))
            out = [[] for _ in prompts]
            for _ in range(3):
                for row, result in enumerate(gen._step("", states)[
                        :len(prompts)]):
                    out[row].append(result[0])
            return out

        together = emitted(PROMPTS)   # 16 wide, then 16: 3 and 6 are padded
        alone = [emitted([p])[0] for p in PROMPTS]
        assert together == alone
        assert len({tuple(t) for t in together}) == 3
    finally:
        gen.engine.shutdown()


@pytest.mark.parametrize("shape,routed", [("olmoe", 2), ("lfm2", 3),
                                          ("dense", 0)])
def test_step_counts_the_pairs_it_skipped(shape, routed):
    """``expert_pairs_skipped``: (computed - live) x ``experts_per_token``
    x routed layers, on the host; a dense model leaves it 0, and it adds
    nothing to what a step brings to the host."""
    cfg = (dataclasses.replace(olmoe_shaped(), num_experts=0,
                               experts_per_token=0, mlp_hidden=256)
           if shape == "dense" else SHAPES[shape]())
    gen = generator(cfg, 2)
    try:
        s0 = gen.engine_stats()
        assert s0["expert_pairs_skipped"] == 0
        gen._step("", [gen._prefill({"prompt": list(range(2, 12))}, ""),
                       None])
        s1 = gen.engine_stats()
        assert s1["positions_computed"] == 2 * 16
        assert s1["positions_live"] == 10
        assert s1["expert_pairs_skipped"] == (32 - 10) * 2 * routed
        assert s1["host_bytes"] == 2 * 4 + 8 * routed
        gen._step("", [gen._prefill({"prompt": list(range(2, 18))}, ""),
                       gen._prefill({"prompt": list(range(2, 18))}, "")])
        # a step with no padding skips nothing
        assert gen.engine_stats()["expert_pairs_skipped"] == \
            s1["expert_pairs_skipped"]
    finally:
        gen.engine.shutdown()
