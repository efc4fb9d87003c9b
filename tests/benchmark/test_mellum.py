"""Mellum 2 through the one block of ``models/llama.py`` against the plain
float32 reference, tiny, on the CPU: grouped-query attention under a window
in three layers of four and under YaRN in the fourth, routed experts; the
decode through a sliding layer's ring of keys; the family module's checks
and counts; the cell's files; the readers of the metrics the cell brings.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerance is a few 1e-5 (``TIGHT``): computing in bf16, a
window ignored or one key off, YaRN left out or a weight not renormalised
move the logits by hundreds of times that (the tests of each say so). In
bf16 the program's logits lie 0.01 to 0.025 from the reference's IN THE
MEAN at these sizes (``BF16_MEAN``: eight layers of matmuls whose every
product is rounded to 8 bits of mantissa, on logits of up to 4; the
largest difference, 0.4 to 0.6, is a position whose router flipped a
near-tie in bf16, so the mean is what is held), which the next precision
down (3 bits of mantissa passed off as bf16: 0.12 to 0.14) misses by two
and a half times, and float32's tolerance by two hundred.
A router probability that ties to within the error between the k-th and
the next expert would flip an expert; the seeds below meet no such tie.
"""

import dataclasses
import json
import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import mellum as family
from benchmark.harness import lastline, loader, peaks, tokengap
from benchmark.reference import mellum as reference
from ray_tpu.models.llama import (
    LlamaConfig, LoraConfig, RopeScaling, init_decode_state, init_llama,
    init_lora, llama_decode, llama_forward, llama_logical_axes,
    llama_next_token)
from ray_tpu.ops.pallas import grouped_matmul as gm

CELL = "serve_mellum2_projctx"
CONFIG = "mellum2-12b-a2.5b-serve-l8"
TIGHT = dict(rtol=5e-5, atol=5e-5)
BF16_MEAN = 0.05
OWN = {"mellum2_window_flash_fwd_ms.serve",
       "mellum2_window_flash_fwd_roofline_pct.serve",
       "mellum2_full_flash_fwd_ms.serve",
       "mellum2_full_flash_fwd_roofline_pct.serve",
       "mellum2_window_keys_kept_pct.serve",
       "mellum2_expert_ffn_roofline_pct.serve",
       "mellum2_expert_matmul_sort_ms.serve",
       "mellum2_expert_load_imbalance.serve"}


def published():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")


def tiny_model(**over):
    """The rehearsal's sizes at two whole periods: hidden 64, 4 query heads
    on 2 key heads of 16, a window of 24, 8 experts of 32, 3 a token."""
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, num_hidden_layers=8,
             layer_types=["sliding_attention"] * 3 + ["full_attention"]
             + ["sliding_attention"] * 3 + ["full_attention"],
             mlp_layer_types=["sparse"] * 8,
             program={"attn_impl": "reference", "dtype": "float32",
                      "param_dtype": "float32"})
    m.update(over)
    return m


def randomised(params, key):
    """Norm weights off 1, so that a norm left out or misplaced shows."""
    def move(path, leaf):
        if path[-1].key.endswith("_norm"):
            return 1.0 + 0.3 * jax.random.normal(
                jax.random.fold_in(key, len(str(path))), leaf.shape,
                leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = family.build_config(m)
    params = randomised(init_llama(cfg, jax.random.key(3)),
                        jax.random.key(5))
    # four windows long: the window bites in three positions of four
    tokens = jax.random.randint(jax.random.key(4), (2, 96), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


# --------------------------------------------------------------------------
# the configuration the family builds, the tree, the count
# --------------------------------------------------------------------------
def test_the_configuration_the_family_builds(setup):
    m, cfg, _, _ = setup
    assert cfg.layer_kinds() == ("sliding_routed",) * 3 + (
        "attention_routed",) + ("sliding_routed",) * 3 + (
        "attention_routed",)
    assert cfg.kind_counts() == {"sliding_routed": 6, "attention_routed": 2}
    assert cfg.layer_runs() == (
        ("sliding_routed", 0, 3), ("attention_routed", 0, 1),
        ("sliding_routed", 3, 3), ("attention_routed", 1, 1))
    assert cfg.sliding_window == 24 and cfg.qk_head_norm
    assert not cfg.qk_norm and not cfg.tie_embeddings
    assert (cfg.num_experts, cfg.experts_per_token, cfg.norm_topk_prob) \
        == (8, 3, True)
    assert cfg.router_scores == "softmax" and not cfg.router_bias
    assert cfg.num_dense_layers == 0 and cfg.num_shared_experts == 0
    assert cfg.rope_theta == 5e5
    # YaRN by the class that latent attention has: amplitude 0.1 ln 16 + 1
    # on cos and sin, and the softmax scale left alone
    assert cfg.rope_scaling == RopeScaling(
        factor=16.0, original_max_position_embeddings=64, beta_fast=32.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0)
    assert cfg.rope_scaling.rotary_amplitude() == pytest.approx(
        1.2772588722239782, rel=1e-15)
    assert cfg.rope_scaling.softmax_amplitude() == 1.0
    # the published file: the cell's own widths, nothing toy
    real = family.build_config(loader.load_config(CONFIG))
    assert (real.hidden, real.num_heads, real.num_kv_heads, real.head_dim,
            real.mlp_hidden, real.num_experts, real.experts_per_token,
            real.sliding_window, real.vocab_size, real.num_layers) == (
        2304, 32, 4, 128, 896, 64, 8, 1024, 98304, 8)
    assert real.attn_impl == "flash" and real.max_seq_len == 131072
    assert real.dtype == real.param_dtype == jnp.bfloat16
    assert real.rope_scaling.original_max_position_embeddings == 8192


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    assert set(params["layers"]) == {"sliding_routed", "attention_routed"}
    sliding = params["layers"]["sliding_routed"]
    full = params["layers"]["attention_routed"]
    # the sliding layers' leaves are the attention layers' own
    assert set(sliding) == set(full)
    assert sliding["wq"].shape == (6, 64, 4, 16)
    assert full["wk"].shape == (2, 64, 2, 16)
    assert sliding["q_norm"].shape == (6, 16) == sliding["k_norm"].shape
    assert sliding["we_gate"].shape == (6, 8, 64, 32)
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(params)
    assert axes["layers"]["sliding_routed"]["wk"] == (
        None, "embed", "kv_heads", "head_dim")
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params() == family.num_params(m)
    # adapters reach the sliding layers' projections as the full layers'
    lcfg = LoraConfig(rank=2, targets=("wq", "wv"))
    lora = init_lora(cfg, lcfg, jax.random.key(0))
    assert lora["layers"]["sliding_routed"]["wq"]["a"].shape == (6, 64, 2)
    assert lora["layers"]["attention_routed"]["wv"]["b"].shape == (
        2, 2, 2, 16)
    assert lcfg.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(lora))


def test_counts_by_hand():
    m = loader.load_config(CONFIG)
    attention = 2304 * (4096 + 2 * 512) + 4096 * 2304
    assert attention == 21_233_664
    expert = 3 * 2304 * 896
    assert expert == 6_193_152
    layer = attention + 256 + 2304 * 64 + 64 * expert + 2 * 2304
    assert layer == 417_747_712
    assert family.part_params(m) == {
        "attention": attention + 256, "routed": 147_456 + 396_361_728,
        "dense": 3 * 2304 * 7168, "norms": 4608}
    ends = 2 * 98304 * 2304 + 2304
    assert family.num_params(m) == 8 * layer + ends == 3_794_968_832
    assert family.build_config(m).num_params() == 3_794_968_832
    uncut = dict(m, num_hidden_layers=28,
                 layer_types=(["sliding_attention"] * 3
                              + ["full_attention"]) * 7,
                 mlp_layer_types=["sparse"] * 28)
    assert family.num_params(uncut) == 28 * layer + ends == 12_149_923_072
    # the name's A2.5B: what a position meets
    assert family.num_params(uncut, active=True) == 28 * (
        layer - 56 * expert) + ends == 2_439_060_736
    assert family.layer_counts(m) == {"sliding": 6, "full": 2, "dense": 0,
                                      "routed": 8}
    # the kernels' need: a pair is a score and a weighted value over 128 at
    # 32 heads; a step of 4 whole rows of 8192
    step = {"rows": 4, "positions_live": 4 * 8192,
            "attention_keys": 4 * 8192,
            "attention_pairs": 4 * 8192 * 8193 // 2}
    inside = 4 * (8192 * 1024 - 1024 * 1023 // 2)
    assert family.window_flash_flops(m, step) == 6 * inside * 512.0 * 32
    assert family.full_flash_flops(m, step) == 2 * 512.0 * 32 * 4 * (
        8192 * 8193 // 2)
    assert family.flash_fwd_pair_flops(m, 10.0) == 2 * 10 * 512.0 * 32
    a_layer = 2.0 * 128 * (2 * 32 + 2 * 4) * 4 * 8192
    assert family.window_flash_bytes(m, step) == 6 * a_layer
    assert family.full_flash_bytes(m, step) == 2 * a_layer \
        == family.flash_fwd_row_bytes(m, 4 * 8192, 4 * 8192)
    assert family.expert_ffn_flops(m, 100) == 8 * 100 * 8 * 2.0 * expert
    assert family.expert_ffn_bytes(m) == 8 * 64 * expert * 2.0
    assert family.expert_ffn_bytes(m, 10) == 10 * expert * 2.0


# --------------------------------------------------------------------------
# YaRN, by hand
# --------------------------------------------------------------------------
def test_yarns_frequencies_and_amplitude_by_hand():
    """theta 5e5, factor 16, original 8192, head_dim 128. Dim i turns
    ``8192 / (2 pi 5e5 ** (i / 64))`` times over the original context: 32
    times at i = 64 ln(8192 / 64 pi) / ln 5e5 = 18.08 and once at i = 64
    ln(8192 / 2 pi) / ln 5e5 = 34.98, so dims 0-18 keep their frequency,
    dims 35-63 get it over 16, and dim i between gets ((35 - i) + (i - 18)
    / 16) / 17 of it."""
    full = loader.load_config(CONFIG)["rope_parameters"]["full_attention"]
    assert 64 * math.log(8192 / (64 * math.pi)) / math.log(5e5) \
        == pytest.approx(18.08, abs=0.005)
    assert 64 * math.log(8192 / (2 * math.pi)) / math.log(5e5) \
        == pytest.approx(34.98, abs=0.005)
    own = np.array([5e5 ** (-i / 64) for i in range(64)])
    blend = np.array([1.0 if i <= 18 else 1 / 16 if i >= 35 else
                      ((35 - i) + (i - 18) / 16) / 17 for i in range(64)])
    want = own * blend
    assert want[0] == 1.0 and want[63] == pytest.approx(
        5e5 ** (-63 / 64) / 16)
    assert want[26] == pytest.approx(5e5 ** (-26 / 64) * (9 + 0.5) / 17)
    ours = RopeScaling(factor=16, original_max_position_embeddings=8192,
                       mscale=1, mscale_all_dim=0)
    np.testing.assert_allclose(ours.inv_freq(128, 5e5), want, rtol=2e-6)
    np.testing.assert_allclose(reference.inverse_frequencies(full, 128),
                               want, rtol=2e-6)
    # plain rope in the sliding layers
    np.testing.assert_allclose(reference.inverse_frequencies(
        {"rope_type": "default", "rope_theta": 500000}, 128), own, rtol=2e-6)
    assert full["attention_factor"] == 1.2772588722239782 \
        == reference.amplitude(full)
    assert 0.1 * math.log(16) + 1 == pytest.approx(1.2772588722239782,
                                                   rel=1e-15)
    assert ours.rotary_amplitude() == pytest.approx(1.2772588722239782,
                                                    rel=1e-15)
    assert ours.softmax_amplitude() == 1.0


# --------------------------------------------------------------------------
# the forward against the reference
# --------------------------------------------------------------------------
def test_logits_agree_with_the_reference_in_float32(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens, cfg)
    for b in range(tokens.shape[0]):
        want = reference.logits(params, tokens[b], m)
        np.testing.assert_allclose(got[b], want, **TIGHT)
        np.testing.assert_allclose(
            got[b, -1], reference.last_logits(params, tokens[b], m), **TIGHT)


def test_logits_agree_with_the_reference_in_bf16(setup):
    """The cell's precision: bf16 activations on bf16 weights. In the mean
    it lies inside ``BF16_MEAN`` of the float32 reference on the same
    weights and two hundred times outside ``TIGHT`` (bf16 passed off as
    float32 fails); the next precision down lies outside ``BF16_MEAN``;
    float32 activations on the same bf16 weights lie inside ``TIGHT``."""
    m, cfg, params, tokens = setup
    rounded = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16,
                               param_dtype=jnp.bfloat16)
    got = llama_forward(rounded, tokens[:1], bf16)[0]
    want = reference.logits(rounded, tokens[0], m)
    off = float(jnp.abs(got - want).mean())
    assert 100 * TIGHT["atol"] < off < BF16_MEAN, off
    assert float(jnp.abs(got - want).max()) > 1000 * TIGHT["atol"]
    np.testing.assert_allclose(llama_forward(
        rounded, tokens[:1], dataclasses.replace(
            cfg, param_dtype=jnp.bfloat16))[0], want, **TIGHT)
    coarse = tokengap.to_mantissa_bits(
        jax.tree.map(lambda a: a + 0, rounded), 3)
    worse = float(jnp.abs(llama_forward(coarse, tokens[:1], bf16)[0]
                          - want).mean())
    assert worse > 2 * BF16_MEAN, worse


def test_the_kernels_path_is_the_reference_path(setup):
    """The equal-width flash forward, interpreted, inside the whole forward
    at 256 positions under the window of 24 and under none: what the chip's
    path computes. The rows are padded on the right to 200 and 77 of their
    own tokens, as a serving step pads them, the routed experts multiply
    the rows' own positions alone, and the served step's token is the
    reference's."""
    m, cfg, params, _ = setup
    tokens = jax.random.randint(jax.random.key(6), (2, 256), 2,
                                m["vocab_size"])
    lengths = (200, 77)
    live = jnp.arange(256)[None] < jnp.array(lengths)[:, None]
    tokens = jnp.where(live, tokens, 0)
    flash = dataclasses.replace(cfg, attn_impl="flash")
    got = llama_forward(params, tokens, flash)
    ids, _, load = llama_next_token(
        params, tokens, jnp.array(lengths, jnp.int32) - 1, flash, live=live)
    assert set(load) == {"fullest", "mean"}
    assert load["mean"].shape == (8,)                 # 8 routed layers
    np.testing.assert_allclose(load["mean"], 277 * 3 / 8.0)
    for b, n in enumerate(lengths):
        want = reference.logits(params, tokens[b, :n], m)
        np.testing.assert_allclose(got[b, :n], want, **TIGHT)
        assert int(ids[b]) == int(want[n - 1].argmax())


# Each control is one of the check's on the chip (tools/mellum2_probe.py);
# here, at 96 positions against a window of 24, each moves the logits by
# 0.1 to 3 where the program lies 1e-6 from the reference.
@pytest.mark.parametrize("control, why", [
    (dict(window=False),
     "the equal-width kernels knew one mask, causal, before this family"),
    (dict(window_keys=23),
     "q - k < window or <= window: whether the window counts the query's "
     "own position is a convention"),
    (dict(window_keys=25), "the same, the other way"),
    (dict(yarn=False),
     "one rope_theta and one rope served every attention layer before "
     "this family"),
    (dict(renormalise=False),
     "OLMoE, the nearest family, publishes norm_topk_prob false"),
])
def test_a_fault_fails_the_tolerance(setup, control, why):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens[:1], cfg)[0]
    assert float(jnp.abs(got - reference.logits(params, tokens[0], m)
                         ).max()) < 2e-5
    faulty = reference.logits(params, tokens[0], m, **control)
    assert float(jnp.abs(got - faulty).max()) > 100 * 2e-5, (control, why)
    if "window" in control or "window_keys" in control:
        # sound while no query has more keys than the window
        np.testing.assert_allclose(got[:23], faulty[:23], **TIGHT)


def test_the_program_without_its_operator_is_told_apart(setup):
    """The same leaves read as full attention everywhere (what a checkout
    without the sliding operator would compute if it could load them), and
    the window's layers under YaRN: both far outside the tolerance."""
    m, cfg, params, tokens = setup
    want = reference.logits(params, tokens[0], m)
    every = {k: jnp.concatenate([params["layers"]["sliding_routed"][k][:3],
                                 params["layers"]["attention_routed"][k][:1],
                                 params["layers"]["sliding_routed"][k][3:],
                                 params["layers"]["attention_routed"][k][1:]])
             for k in params["layers"]["sliding_routed"]}
    full = dataclasses.replace(cfg, layer_types=("full_attention",) * 8)
    got = llama_forward(dict(params, layers=every), tokens[:1], full)[0]
    assert float(jnp.abs(got - want).max()) > 100 * 2e-5
    np.testing.assert_allclose(
        got, reference.logits(params, tokens[0], dict(
            m, rope_parameters=dict(m["rope_parameters"], sliding_attention=m[
                "rope_parameters"]["full_attention"])), window=False),
        **TIGHT)


def test_the_served_step_and_remat_compute_the_same(setup):
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    for remat_policy in ("dots", "full"):
        other = dataclasses.replace(cfg, remat=True,
                                    remat_policy=remat_policy)
        np.testing.assert_allclose(llama_forward(params, tokens, other), want,
                                   rtol=1e-5, atol=1e-5)


def test_a_window_has_no_backward_under_the_kernel(setup):
    from ray_tpu.models.llama import llama_loss

    _, cfg, params, tokens = setup
    flash = dataclasses.replace(cfg, attn_impl="flash")
    batch = {"tokens": jnp.concatenate([tokens, tokens[:, :33]], axis=1)}
    with pytest.raises(NotImplementedError, match="has no backward"):
        jax.grad(lambda p: llama_loss(p, batch, flash))(params)
    # the reference path differentiates
    g = jax.grad(lambda p: llama_loss(p, batch, cfg))(params)
    assert float(jnp.abs(g["layers"]["sliding_routed"]["wk"]).max()) > 0


# --------------------------------------------------------------------------
# decode through the sliding layers' ring and the full layers' rows
# --------------------------------------------------------------------------
@pytest.mark.parametrize("prefill, chunk", [(10, 1), (40, 1), (30, 7)])
def test_decode_through_the_state_is_the_full_forward(setup, prefill, chunk):
    """A prompt shorter than the window of 24 and one longer, then token
    by token (and in chunks of 7) well past the window's length, so that a
    sliding layer's oldest rows are dropped again and again."""
    m, cfg, params, _ = setup
    total = prefill + (8 * chunk if chunk > 1 else 45)
    tokens = jax.random.randint(jax.random.key(8), (2, total), 0,
                                m["vocab_size"])
    want = jnp.stack([reference.logits(params, tokens[b], m)
                      for b in range(2)])
    state = init_decode_state(cfg, 2, total)
    for i, kind in enumerate(cfg.layer_kinds()):
        keys, values = state[i]
        rows = 24 if kind == "sliding_routed" else total
        assert keys.shape == values.shape == (2, rows, 2, 16)
    decode = jax.jit(lambda p, t, st, at: llama_decode(p, t, cfg, st, at))
    got, at = [], 0
    for n in [prefill] + [chunk] * ((total - prefill) // chunk):
        logits, state = decode(params, tokens[:, at:at + n], state,
                               jnp.int32(at))
        got.append(logits)
        at += n
    assert at == total
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, **TIGHT)
    assert state[0][0].shape == (2, 24, 2, 16)


# --------------------------------------------------------------------------
# the served class
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    m = tiny_model()
    gen = family.Served(**family.served_kwargs(m, dict(
        lora_rank=4, max_batch_size=2, allowed_batch_sizes=[2],
        max_new_tokens=4, seq_bucket=128), 12))
    yield m, gen
    gen.engine.shutdown()


def test_the_served_class_counts_the_windows_keys(served):
    m, gen = served
    prompt = list(range(3, 133))                     # 130 positions
    tokens = list(gen({"prompt": prompt, "max_new": 3}))
    assert len(tokens) == 3
    stats = gen.engine_stats()
    assert set(gen.STEP_COUNTERS) <= set(stats)
    assert stats["layer_kinds"] == {"sliding_routed": 6,
                                    "attention_routed": 2}
    assert stats["positions_computed"] == 3 * 2 * 256
    lengths = (130, 131, 132)
    assert stats["window_keys_kept"] == 6 * sum(
        n * 24 - 24 * 23 // 2 for n in lengths)
    assert stats["window_keys_seen"] == 6 * sum(
        n * (n + 1) // 2 for n in lengths)
    assert stats["index_keys_seen"] == 0 == stats["index_keys_kept"]
    assert stats["flash_blocks_run"] == 0        # no latent operator
    assert stats["expert_pairs_all"] == sum(lengths) * 3 * 8
    # the tokens are the reference's own first choices
    rows = reference.logits(gen._params, jnp.asarray(prompt + tokens[:-1]), m)
    assert tokens == np.asarray(rows[129:132].argmax(-1)).tolist()
    from ray_tpu.serve.llm import LlamaGenerator
    assert "window_keys_seen" in LlamaGenerator.STEP_COUNTERS
    assert "window_keys_seen" in LlamaGenerator.engine_stats.__doc__
    # an adapter reaches the sliding layers too
    adapted = list(gen({"prompt": prompt, "max_new": 2, "adapter": "a1"}))
    assert len(adapted) == 2


# --------------------------------------------------------------------------
# the family module
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(attention_bias=True), "attention_bias True"),
    (dict(use_sliding_window=False), "use_sliding_window False"),
    (dict(max_window_layers=4), "max_window_layers 4"),
    (dict(rope_scaling=None), r"does not understand \['rope_scaling'\]"),
    (dict(sliding_window=0), "at least 1"),
    (dict(layer_types=["full_attention"] * 7), "layer_types names 7"),
    (dict(layer_types=["full_attention"] * 7 + ["conv"]), r"\['conv'\]"),
    (dict(mlp_layer_types=["sparse"] * 7 + ["dense"]), "the leading ones"),
    (dict(num_experts_per_tok=65), "1..num_experts"),
    (dict(num_key_value_heads=5), "whole groups"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    with pytest.raises(ValueError, match=match):
        family.check(dict(loader.load_config(CONFIG), **change))


@pytest.mark.parametrize("entry, change, match", [
    ("sliding_attention", dict(rope_theta=10000), "plain rope at the full"),
    ("sliding_attention", dict(rope_type="yarn"), "plain rope at the full"),
    ("full_attention", dict(rope_type="default"), "expected rope_type yarn"),
    ("full_attention", dict(attention_factor=1.0), "0.1 ln"),
    ("full_attention", dict(mscale=0.7), "expected rope_type yarn"),
])
def test_the_family_refuses_another_rope(entry, change, match):
    m = loader.load_config(CONFIG)
    ropes = dict(m["rope_parameters"])
    ropes[entry] = dict(ropes[entry], **change)
    with pytest.raises(ValueError, match=match):
        family.check(dict(m, rope_parameters=ropes))


def test_a_file_that_lacks_a_key_is_refused():
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "sliding_window"}
    with pytest.raises(ValueError, match=r"lacks \['sliding_window'\]"):
        family.check(lacking)


def test_a_checkout_without_the_operator_is_refused_at_once(monkeypatch):
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert family._config_fields() == fields
    assert (set(family.MODEL_KEYS.values()) | set(family.BUILT)
            | set(family.MODELING)) <= fields
    monkeypatch.setattr(family.LlamaGenerator, "STEP_COUNTERS",
                        ("host_bytes", "window_keys_kept"))
    with pytest.raises(ValueError, match="no sliding_attention operator"):
        family.check(loader.load_config(CONFIG))


def test_the_parent_fails_on_the_cell_within_seconds(repo_root, tmp_path):
    """This PR's benchmark files over a program that lacks the operator:
    ``run.py`` exits at once and says so (the driver tries each new cell on
    the parent first, and a parent that hangs there refuses the PR)."""
    import shutil
    import time

    root = tmp_path / "parent"
    for sub in ("benchmark", "ray_tpu"):
        shutil.copytree(os.path.join(repo_root, sub), root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(repo_root, "BENCHMARK.json"), root)
    llm = root / "ray_tpu" / "serve" / "llm.py"
    llm.write_text(llm.read_text().replace('"window_keys_seen", ', ""))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    t = time.time()
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "5", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=60)
    assert time.time() - t < 30
    assert proc.returncode not in (0, 3)
    assert "no sliding_attention operator" in proc.stderr


def test_the_configuration_keeps_every_published_number():
    m = loader.load_config(CONFIG)
    row = published()
    assert m["source"] == row["source_url"]
    assert m["reduced"] == ["num_hidden_layers", "layer_types",
                            "mlp_layer_types"]
    cut = {"num_hidden_layers": 8,
           "layer_types": row["config"]["layer_types"][:8],
           "mlp_layer_types": row["config"]["mlp_layer_types"][:8]}
    assert m["changed_from_source"] == {
        k: {"source": row["config"][k], "here": here}
        for k, here in cut.items()}
    for key, value in row["config"].items():
        assert m[key] == cut.get(key, value), key
    assert set(m) - set(row["config"]) == {
        "name", "source", "family", "reduced", "changed_from_source",
        "assumed", "program", "deployment", "notes"}
    # two whole periods, in the published ratio
    assert m["layer_types"] == (["sliding_attention"] * 3
                                + ["full_attention"]) * 2
    # no width, head count, expert count, top_k, window, rope number or
    # vocabulary row is cut
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "num_experts", "num_experts_per_tok",
                "sliding_window", "vocab_size", "rope_parameters",
                "max_position_embeddings"):
        assert key not in m["reduced"] and m[key] == row["config"][key]
    assert m["program"] == {"attn_impl": "flash", "dtype": "bfloat16",
                            "param_dtype": "bfloat16"}
    said = " ".join(m["assumed"])
    for item in ("qk_head_norm", "q - k < sliding_window", "rotate-half",
                 "max_window_layers", "multi-token-prediction",
                 "norm_topk_prob", "random from --seed"):
        assert item in said, item
    assert "layers 0-7" in m["deployment"]
    assert "3 794 968 832" in " ".join(m["notes"])


def test_the_family_module_imports_no_jax(repo_root):
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.families import mellum as f; "
            "from benchmark.harness import loader; "
            "m = loader.load_config(%r); f.check(m); "
            "print(f.num_params(m)); "
            "assert 'jax' not in sys.modules, 'jax was imported'"
            % (repo_root, CONFIG))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "3794968832"


# --------------------------------------------------------------------------
# gmm_tiles at this model's shapes and the other expert cells', as they are
# --------------------------------------------------------------------------
@pytest.mark.parametrize("config, hidden, width, pair, down", [
    # 896 is seven lanes and seven is prime: column blocks of 128, seven
    # passes over the rows (ROADMAP has the perf_opt item)
    (CONFIG, 2304, 896, (512, 128), (256, 2304)),
    ("olmoe-1b-7b-serve", 2048, 1024, (512, 512), (256, 2048)),
    ("lfm2-24b-a2b-serve-l9", 2048, 1536, (128, 768), (512, 1024)),
    ("deepseek-v2-serve-ep8-l8", 5120, 1536, (128, 256), (256, 1280)),
    ("dots3-note-prev-serve-ep8-l5", 5120, 1536, (128, 256), (256, 1280)),
    ("ling-3.0-flash-serve-ep4-l8", 2560, 768, (512, 384), (256, 2560)),
])
def test_the_grouped_matmuls_tiles_as_they_are_today(config, hidden, width,
                                                     pair, down):
    m = loader.load_config(config)
    assert m["hidden_size"] == hidden
    assert m.get("moe_intermediate_size", m["intermediate_size"]) == width
    rows = 4 * 8192 * 8
    # the fused gate and up projections in bf16, the down in float32
    assert gm.gmm_tiles(rows, hidden, width, stacks=2, out_itemsize=2) == pair
    assert gm.gmm_tiles(rows, width, hidden, out_itemsize=4) == down
    for tiles, k, stacks, out in ((pair, hidden, 2, 2), (down, width, 1, 4)):
        assert gm.gmm_vmem_bytes(*tiles, k, stacks=stacks,
                                 out_itemsize=out) <= gm.VMEM_LIMIT_BYTES


# --------------------------------------------------------------------------
# the cell's files
# --------------------------------------------------------------------------
def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell(CELL)
    dots3 = loader.load_cell("serve_dots3_longdoc")
    # the engine is serve_dots3_longdoc's but for the bucket and the answers
    assert {k: v for k, v in cell["engine"].items()
            if k not in ("seq_bucket", "max_new_tokens")} == {
        k: v for k, v in dots3["engine"].items()
        if k not in ("seq_bucket", "max_new_tokens")}
    assert cell["engine"]["max_batch_size"] == 4
    assert cell["engine"]["allowed_batch_sizes"] == [4]
    assert cell["engine"]["seq_bucket"] == 1024 \
        == cell["model"]["sliding_window"]
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 5120, "sigma": 0.3, "min": 3072,
                                 "max": 8160}
    assert mix["output_len"] == {"median": 12, "sigma": 0.5, "min": 4,
                                 "max": 24}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 24
    # five buckets; a context never passes 8192, and every prompt is at
    # least three windows long
    assert serve_driver.seq_buckets(cell) == [4096, 5120, 6144, 7168, 8192]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 8192
    assert mix["prompt_len"]["min"] >= 3 * cell["model"]["sliding_window"]
    assert list(cell["check"]["limits"]) == ["gap_mean"]
    assert all(0 < limit < 1 for limit in cell["check"]["limits"].values())
    assert mix["rate_per_s"] / mix["knee"]["rate_per_s"] == \
        pytest.approx(0.6, abs=0.02)
    assert "order_seed" in mix and "found_by" in mix["knee"]
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    assert OWN <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    assert not OWN & {m["name"] for m in loader.metrics_for_cell(dots3)}
    listed = loader.manifest_cell(manifest, CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        CONFIG, "project_context_completions", 1)
    # the cell's own entries, each found by its name: where they lie in
    # their lists and what else the manifest holds is not this test's
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == cell["model"]["source"]
    own = [m for m in manifest["per_layer"] if m["name"] in OWN]
    assert {m["name"] for m in own} == OWN and len(own) == len(OWN)
    for metric in own:
        assert metric["moves"] == "serve_gap_p95_ms"
        assert metric["workloads"] == [CELL]
    # every metric that lists the serving cells lists this one
    serving = [m for g in ("end_to_end", "per_layer") for m in manifest[g]
               if "serve_chat_steady" in m.get("workloads", ())]
    assert serving and all(CELL in m["workloads"] for m in serving)
    # flash_tiles gives 1024 x 1024 at all five buckets, so a sliding
    # layer walks two key blocks a query block
    from ray_tpu.ops.pallas import flash_attention as fa
    for seq in serve_driver.seq_buckets(cell):
        assert fa.flash_tiles(seq, seq, head_dim=128) == (1024, 1024)
        assert fa._window_key_blocks(seq, 1024, 1024, 1024) == 2


def view_of(ops, records, stats):
    cell = loader.load_cell(CELL)
    return {"cell": cell, "peaks": peaks.peak("TPU v5 lite"),
            "trace": {"ops": ops, "step_records": records, "steps": 4},
            "obs": {"engine_stats_end": stats}}


def whole_rows(rows, length):
    """The record of a step that re-ran ``rows`` whole rows of ``length``."""
    return {"rows": rows, "positions_live": rows * length,
            "attention_keys": rows * length,
            "attention_pairs": rows * length * (length + 1) // 2,
            "experts_met": None}


def test_the_readers_tell_the_kernels_apart():
    cell = loader.load_cell(CELL)
    m = cell["model"]
    ops = [("tpu_custom_call:flash_fwd_sliding.3", 0.400, 12),
           ("tpu_custom_call:checkpoint.7", 0.300, 4),
           ("tpu_custom_call:ragged-dot-none-pallas.2", 0.500, 16),
           ("sort.4", 0.020, 8), ("fusion.11", 0.250, 40)]
    records = [whole_rows(4, 8192)] * 2
    stats = {"window_keys_kept": 30, "window_keys_seen": 120,
             "expert_pairs_fullest": 150.0, "expert_pairs_mean": 100.0}
    view = view_of(ops, records, stats)
    got = {}
    for metric in loader.metrics_for_cell(cell):
        if metric["name"] in OWN:
            got[metric["name"]] = loader.load_reader(metric)(view, metric)
    assert set(got) == OWN
    assert got["mellum2_window_flash_fwd_ms.serve"] == pytest.approx(100.0)
    assert got["mellum2_full_flash_fwd_ms.serve"] == pytest.approx(75.0)
    assert got["mellum2_expert_matmul_sort_ms.serve"] == pytest.approx(130.0)
    assert got["mellum2_window_keys_kept_pct.serve"] == 25.0
    assert got["mellum2_expert_load_imbalance.serve"] == 1.5
    pk = view["peaks"]
    step = records[0]
    assert got["mellum2_window_flash_fwd_roofline_pct.serve"] == \
        pytest.approx(100 * 2 * family.window_flash_flops(m, step)
                      / pk["bf16_flops_per_s"] / 0.400)
    assert got["mellum2_full_flash_fwd_roofline_pct.serve"] == \
        pytest.approx(100 * 2 * family.full_flash_flops(m, step)
                      / pk["bf16_flops_per_s"] / 0.300)
    assert got["mellum2_expert_ffn_roofline_pct.serve"] == \
        pytest.approx(100 * 2 * family.expert_ffn_flops(m, 4 * 8192)
                      / pk["bf16_flops_per_s"] / 0.500)
    assert all(0 < got[k] < 100 for k in OWN if "roofline" in k)
    # the FLOPs bind both kernels at these lengths
    assert family.window_flash_flops(m, step) / pk["bf16_flops_per_s"] > \
        family.window_flash_bytes(m, step) / pk["hbm_bytes_per_s"]
    # a program without the span or the counter: nothing, and no raise
    bare = view_of([("fusion.1", 0.1, 2)], records, {})
    for metric in loader.metrics_for_cell(cell):
        if metric["name"] in OWN:
            assert loader.load_reader(metric)(bare, metric) is None


def test_the_cells_step_holds_the_kernels_under_their_scopes():
    from tests.benchmark.test_deepseek_v2 import program_text

    text = program_text(CELL, "step4096")
    # the sliding layers' forward is its own function (under its own
    # scope: tests/test_flash_fewer_keys.py), the full layers' the old one
    assert "name=flash_attention\n" in text or "name=flash_attention " \
        in text or "name=flash_attention]" in text
    assert text.count("name=flash_attention_window") == 2   # two runs
    assert "ragged_dot" not in text
    # reference attention's scores would be [8, 32, 4096, 4096]
    assert "8,32,4096,4096" not in text
    # the keys reach the kernel at their 4 heads, never repeated to 32
    assert "bf16[8,4,4096,128]" in text


def test_the_probe_rehearses(capsys, tmp_path):
    from benchmark.tools import mellum2_probe

    out = tmp_path / "probe.jsonl"
    rc = mellum2_probe.check_probe.main([
        "--workload", CELL, "--seeds", "1", "--control-seeds", "1",
        "--requests", "2", "--rehearsal", "--out", str(out)])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    controls = {"window_ignored", "window_one_key_short", "yarn_left_out",
                "topk_not_renormalised", "mantissa_3_bits"}
    assert controls | {"program", "tokens_shifted", "tokens_stale"} \
        <= set(line)
    # float32 on the CPU: the program's tokens are the reference's own
    assert line["program"]["gap_mean"] == 0.0 and line["program"]["correct"]
    for name in ("tokens_shifted", "tokens_stale", "window_ignored",
                 "yarn_left_out", "topk_not_renormalised",
                 "mantissa_3_bits"):
        assert not line[name]["correct"], name


# --------------------------------------------------------------------------
# the window's convention, which served tokens cannot tell: the kernel's own
# check (tools/mellum2_window_check.py)
# --------------------------------------------------------------------------
def window_check(capsys, *argv):
    from benchmark.tools import mellum2_window_check

    rc = mellum2_window_check.main(["--rehearsal", *argv])
    return rc, [json.loads(ln) for ln in
                capsys.readouterr().out.strip().splitlines()]


def test_the_window_check_rehearses(capsys, tmp_path):
    out = tmp_path / "lines" / "window.jsonl"
    rc, lines = window_check(capsys, "--seeds", "1", "--out", str(out))
    assert rc == 0 and len(lines) == 1
    assert lines == [json.loads(ln) for ln in out.read_text().splitlines()]
    line = lines[0]
    assert line["sound_ok"] and line["faults_told"] and line["rehearsal"]
    assert (line["rows"], line["length"], line["heads"], line["kv_heads"],
            line["window"]) == (4, 256, 4, 2, 24)
    assert line["sound"] < line["tolerance"] < line["off_over"] < min(
        line["one_key_short"], line["one_key_long"], line["window_ignored"])


def test_the_window_check_tells_a_kernel_one_key_off(capsys, monkeypatch):
    """The fault planted in the kernel itself: `<=` where `<` belongs."""
    from ray_tpu.ops.pallas import flash_attention as fa

    sound = fa.flash_attention_window
    monkeypatch.setattr(fa, "flash_attention_window",
                        lambda q, k, v, window: sound(q, k, v, window + 1))
    rc, (line,) = window_check(capsys, "--seeds", "1")
    assert rc == 1 and not line["sound_ok"]
    assert line["sound"] > line["off_over"]


def test_the_window_check_measures_on_a_chip_alone():
    from benchmark.tools import mellum2_window_check

    with pytest.raises(SystemExit, match="no chip"):
        mellum2_window_check.main(["--seeds", "1"])


# --------------------------------------------------------------------------
# run.py --rehearsal of the cell, in a process of its own
# --------------------------------------------------------------------------
def test_the_cell_rehearses(repo_root, manifest):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONASYNCIODEBUG")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "5", "--trace", "1", "--rehearsal"],
        cwd=repo_root, env=env, capture_output=True, text=True, timeout=170)
    assert proc.returncode == 3, proc.stderr[-3000:]
    head = "[bench REHEARSAL] would-be last line: "
    found = [ln for ln in proc.stdout.splitlines() if ln.startswith(head)]
    assert len(found) == 1
    line = json.loads(found[0][len(head):])
    lastline.validate(line, manifest, CELL, True)
    assert line["attempted"] == 10 and line["failed"] == 0
    assert "NOT CORRECT" not in proc.stdout
    assert OWN <= set(line["metrics"])
