"""Laguna XS.2 through the one block of ``models/llama.py`` against the
plain float32 reference, tiny, on the CPU: grouped-query attention at a
head count a layer kind (8 heads on 2 key/value heads under a window in the
sliding layers, 6 on 2 under YaRN over half a head in the full ones, so
groups of 4 and of 3), a sigmoid gate a head, a leading dense layer, routed
experts under a sigmoid router beside a shared expert; the decode through a
sliding layer's ring and a full layer's rows; the family module's checks
and counts; the cell's files; the readers of the metrics the cell brings.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerance is a few 1e-5 (``TIGHT``): computing in bf16, or
any of the ten faults that ``reference/laguna.py`` can plant, moves the
logits by hundreds of times that (the tests of each say so). In bf16 the
program's logits lie some 0.01 to 0.03 from the reference's IN THE MEAN at
these sizes (``BF16_MEAN``: five layers of matmuls whose every product is
rounded to 8 bits of mantissa; the largest difference is a position whose
router flipped a near-tie in bf16, so the mean is what is held), which the
next precision down (3 bits of mantissa passed off as bf16) misses by
twice and more, and float32's tolerance by two hundred. The YaRN of the
rehearsal's sizes is reckoned over an original context of 32 and the
sequences here are 96 and more, so every test runs past it.
"""

import dataclasses
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import laguna as family
from benchmark.harness import lastline, loader, peaks, tokengap
from benchmark.reference import laguna as reference
from ray_tpu.models.llama import (
    LlamaConfig, LoraConfig, RopeScaling, _yarn_rope, init_decode_state,
    init_llama, init_lora, llama_decode, llama_forward, llama_logical_axes,
    llama_next_token)

CELL = "serve_laguna_agentturns"
CONFIG = "laguna-xs.2-serve-l5"
TIGHT = dict(rtol=5e-5, atol=5e-5)
BF16_MEAN = 0.05
OWN = {"laguna_window_flash_fwd_ms.serve",
       "laguna_window_flash_fwd_roofline_pct.serve",
       "laguna_full_flash_fwd_ms.serve",
       "laguna_full_flash_fwd_roofline_pct.serve",
       "laguna_window_keys_kept_pct.serve",
       "laguna_expert_ffn_roofline_pct.serve",
       "laguna_expert_matmul_sort_ms.serve",
       "laguna_expert_load_imbalance.serve"}
PATTERN = ["full_attention"] + ["sliding_attention"] * 3 + ["full_attention"]
KINDS = ("attention_dense",) + ("sliding_routed",) * 3 + (
    "attention_routed",)


def published():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Laguna-XS.2")


def tiny_model(**over):
    """The rehearsal's sizes: hidden 64, 6 (full) and 8 (sliding) query
    heads on 2 key/value heads of 16, a window of 24, 16 experts of 32, 4 a
    token and a shared one, YaRN over 8 of 16 dims past an original 32."""
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, program={"attn_impl": "reference", "dtype": "float32",
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
    # four windows and three original contexts long
    tokens = jax.random.randint(jax.random.key(4), (2, 96), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


# --------------------------------------------------------------------------
# the configuration the family builds, the tree, the count
# --------------------------------------------------------------------------
def test_the_configuration_the_family_builds(setup):
    m, cfg, _, _ = setup
    assert cfg.layer_kinds() == KINDS
    assert cfg.kind_counts() == {"attention_dense": 1, "sliding_routed": 3,
                                 "attention_routed": 1}
    assert (cfg.num_heads, cfg.swa_num_heads, cfg.num_kv_heads) == (6, 8, 2)
    assert cfg.attention_heads("attention") == 6
    assert cfg.attention_heads("sliding") == 8
    assert cfg.head_gate and not cfg.qk_norm and not cfg.qk_head_norm
    assert (cfg.rope_theta, cfg.swa_rope_theta) == (5e5, 1e4)
    assert cfg.partial_rotary_factor == 0.5 and cfg.rotary_dim() == 8
    assert (cfg.num_experts, cfg.experts_per_token, cfg.norm_topk_prob,
            cfg.router_scores, cfg.routed_scaling_factor,
            cfg.num_shared_experts, cfg.num_dense_layers) == (
        16, 4, True, "sigmoid", 2.5, 1, 1)
    assert cfg.rope_scaling == RopeScaling(
        factor=64.0, original_max_position_embeddings=32, beta_fast=64.0,
        beta_slow=1.0, mscale=1.0, mscale_all_dim=0.0)
    assert cfg.rope_scaling.rotary_amplitude() == pytest.approx(
        1.4158883083359672, rel=1e-15)
    assert cfg.rope_scaling.softmax_amplitude() == 1.0
    # the published file: the cell's own widths, nothing toy
    real = family.build_config(loader.load_config(CONFIG))
    assert (real.hidden, real.num_heads, real.swa_num_heads,
            real.num_kv_heads, real.head_dim, real.mlp_hidden,
            real.dense_mlp_hidden, real.num_experts, real.experts_per_token,
            real.sliding_window, real.vocab_size, real.num_layers) == (
        2048, 48, 64, 8, 128, 512, 8192, 256, 8, 512, 100352, 5)
    assert real.layer_kinds() == KINDS and real.rotary_dim() == 64
    assert real.attn_impl == "flash" and real.max_seq_len == 262144
    assert real.dtype == real.param_dtype == jnp.bfloat16
    assert real.rope_scaling.original_max_position_embeddings == 4096


def test_the_defaults_leave_every_other_model_as_it_was():
    """``swa_num_heads`` 0 is ``num_heads``, ``swa_rope_theta`` 0 is
    ``rope_theta`` (in the latent window operator too), the whole head
    turns and no gate is drawn."""
    cfg = LlamaConfig(num_heads=4, rope_theta=7e4, sliding_window=8)
    assert cfg.attention_heads("sliding") == 4 == cfg.attention_heads()
    assert cfg.rotary_dim() == cfg.head_dim
    assert cfg.latent_widths("window").theta == 7e4
    assert dataclasses.replace(cfg, swa_rope_theta=5e4).latent_widths(
        "window").theta == 5e4
    assert not cfg.head_gate


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    assert set(params["layers"]) == set(KINDS)
    sliding = params["layers"]["sliding_routed"]
    full = params["layers"]["attention_routed"]
    first = params["layers"]["attention_dense"]
    # one shape a kind: the sliding layers' 8 heads, the full layers' 6
    assert sliding["wq"].shape == (3, 64, 8, 16)
    assert sliding["wo"].shape == (3, 8, 16, 64)
    assert sliding["w_head_gate"].shape == (3, 64, 8)
    assert full["wq"].shape == (1, 64, 6, 16) == first["wq"].shape
    assert full["w_head_gate"].shape == (1, 64, 6)
    assert full["wk"].shape == sliding["wk"][:1].shape == (1, 64, 2, 16)
    assert sliding["we_gate"].shape == (3, 16, 64, 32)
    assert sliding["ws_gate"].shape == (3, 64, 32)
    assert first["w_gate"].shape == (1, 64, 96) and "router" not in first
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)) \
        == jax.tree.structure(params)
    assert axes["layers"]["sliding_routed"]["w_head_gate"] == (
        None, "embed", "heads")
    n = sum(x.size for x in jax.tree.leaves(params))
    assert n == cfg.num_params() == family.num_params(m)
    # adapters take each kind's own head count
    lcfg = LoraConfig(rank=2, targets=("wq", "wv"))
    lora = init_lora(cfg, lcfg, jax.random.key(0))
    assert lora["layers"]["sliding_routed"]["wq"]["b"].shape == (3, 2, 8, 16)
    assert lora["layers"]["attention_routed"]["wq"]["b"].shape == (
        1, 2, 6, 16)
    assert lcfg.num_params(cfg) == sum(
        x.size for x in jax.tree.leaves(lora))


def test_counts_by_hand():
    """ISSUE 60's table, and the kernels' need at a step of four whole rows
    of 6144 and at a row shorter than the window."""
    m = loader.load_config(CONFIG)
    sliding = 2048 * 8192 + 2 * 2048 * 1024 + 8192 * 2048 + 2048 * 64
    full = 2048 * 6144 + 2 * 2048 * 1024 + 6144 * 2048 + 2048 * 48
    assert (sliding, full) == (37_879_808, 29_458_432)
    expert = 3 * 2048 * 512
    routed = 2048 * 256 + 256 * expert + expert
    assert (expert, 256 * expert) == (3_145_728, 805_306_368)
    assert family.part_params(m) == {
        "sliding": sliding, "full": full, "routed": routed,
        "dense": 3 * 2048 * 8192, "norms": 4096}
    assert full + 3 * 2048 * 8192 + 4096 == 79_794_176
    assert sliding + routed + 4096 == 846_860_288
    assert full + routed + 4096 == 838_438_912
    ends = 2 * 100352 * 2048 + 2048
    assert family.num_params(m) == 3_869_857_792 == (
        79_794_176 + 3 * 846_860_288 + 838_438_912 + ends)
    assert family.build_config(m).num_params() == 3_869_857_792
    row = published()["config"]
    uncut = dict(m, **{k: row[k] for k in m["reduced"]})
    assert family.num_params(uncut) == 33_442_596_864 == (
        79_794_176 + 30 * 846_860_288 + 9 * 838_438_912 + ends)
    # what a position meets: the name's A3B
    assert family.num_params(uncut, active=True) == 33_442_596_864 \
        - 39 * 248 * expert == 3_017_115_648
    assert family.layer_counts(m) == {"sliding": 3, "full": 2, "dense": 1,
                                      "routed": 4}
    assert family.kind_heads(m) == {"sliding": 64, "full": 48}
    # a pair is a score and a weighted value over 128 a head: 512 FLOP
    step = {"rows": 4, "positions_live": 4 * 6144,
            "attention_keys": 4 * 6144,
            "attention_pairs": 4 * 6144 * 6145 // 2}
    inside = 4 * (6144 * 512 - 512 * 511 // 2)
    assert family.window_flash_flops(m, step) == 3 * inside * 512.0 * 64
    assert family.full_flash_flops(m, step) == 2 * 512.0 * 48 * 4 * (
        6144 * 6145 // 2)
    assert family.flash_fwd_pair_flops(m, 10.0) == 2 * 10 * 512.0 * 48
    assert family.window_flash_bytes(m, step) == \
        3 * 2.0 * 128 * (2 * 64 + 2 * 8) * 4 * 6144
    assert family.full_flash_bytes(m, step) == \
        2 * 2.0 * 128 * (2 * 48 + 2 * 8) * 4 * 6144 \
        == family.flash_fwd_row_bytes(m, 4 * 6144, 4 * 6144)
    # a row of 400, under the window, keeps 400 x 401 / 2 pairs; the
    # record does not say its length and the count errs low, never over
    short = {"rows": 1, "positions_live": 400, "attention_keys": 400,
             "attention_pairs": 400 * 401 // 2}
    assert 0 < family.window_flash_flops(m, short) <= \
        3 * 512.0 * 64 * 400 * 401 // 2
    assert family.expert_ffn_flops(m, 100) == 4 * 100 * 8 * 2.0 * expert
    assert family.expert_ffn_bytes(m) == 4 * 256 * expert * 2.0
    assert family.expert_ffn_bytes(m, 10) == 10 * expert * 2.0
    # the two sides of the experts' roofline cross near 7 700 positions
    pk = peaks.peak("TPU v5 lite")
    cross = (family.expert_ffn_bytes(m) / pk["hbm_bytes_per_s"]
             * pk["bf16_flops_per_s"] / family.expert_ffn_flops(m, 1))
    assert 7600 < cross < 7800


# --------------------------------------------------------------------------
# the partial rotary form, against HuggingFace's formula written out
# --------------------------------------------------------------------------
def test_yarn_over_half_a_head_by_hand():
    """theta 5e5, factor 64, original 4096, over the FIRST 64 of 128 dims
    (32 frequencies). Dim i of them turns ``4096 / (2 pi 5e5 ** (i / 32))``
    times over the original context: 64 times at i = 32 ln(4096 / 128 pi) /
    ln 5e5 = 5.66 and once at i = 32 ln(4096 / 2 pi) / ln 5e5 = 15.80, so
    dims 0-5 keep their frequency, dims 16-31 get it over 64, and dim i
    between gets ((16 - i) + (i - 5) / 64) / 11 of it. Reckoned over the
    whole head of 128 the dims would be 11.3 and 31.6: another model."""
    full = loader.load_config(CONFIG)["rope_parameters"]["full_attention"]
    assert 32 * math.log(4096 / (128 * math.pi)) / math.log(5e5) \
        == pytest.approx(5.66, abs=0.005)
    assert 32 * math.log(4096 / (2 * math.pi)) / math.log(5e5) \
        == pytest.approx(15.80, abs=0.005)
    own = np.array([5e5 ** (-i / 32) for i in range(32)])
    blend = np.array([1.0 if i <= 5 else 1 / 64 if i >= 16 else
                      ((16 - i) + (i - 5) / 64) / 11 for i in range(32)])
    want = own * blend
    ours = RopeScaling(factor=64, original_max_position_embeddings=4096,
                       beta_fast=64, beta_slow=1, mscale=1, mscale_all_dim=0)
    np.testing.assert_allclose(ours.inv_freq(64, 5e5), want, rtol=2e-6)
    np.testing.assert_allclose(reference.inverse_frequencies(full, 64),
                               want, rtol=2e-6)
    assert not np.allclose(ours.inv_freq(128, 5e5)[:32], want, rtol=1e-3)
    assert 0.1 * math.log(64) + 1 == pytest.approx(
        full["attention_factor"], rel=1e-15)
    # HuggingFace's apply_rotary_pos_emb under a partial rotary factor:
    # q_rot, q_pass = q[..., :64], q[..., 64:]; q_rot * cos + rotate_half(
    # q_rot) * sin, cos and sin over cat(freqs, freqs) times the attention
    # factor; cat(q_embed, q_pass)
    x = jax.random.normal(jax.random.key(0), (1, 7, 3, 128), jnp.float32)
    positions = jnp.array([[0, 1, 5, 100, 4095, 4096, 20000]])
    # float32 frequencies, as both sides hold them; one rounded otherwise
    # in its last bit moves an angle by 1e-7 of itself, a thousandth at
    # position 20 000, so the far positions are held to 3e-3 and the near
    # ones to 2e-5
    w32 = jnp.asarray(want, jnp.float32)
    angles = (positions[0].astype(jnp.float32)[:, None]
              * jnp.concatenate([w32, w32])[None, :])
    cos = (jnp.cos(angles) * full["attention_factor"])[None, :, None, :]
    sin = (jnp.sin(angles) * full["attention_factor"])[None, :, None, :]
    rot, passed = x[..., :64], x[..., 64:]
    rotate_half = jnp.concatenate([-rot[..., 32:], rot[..., :32]], -1)
    hf = jnp.concatenate([rot * cos + rotate_half * sin, passed], -1)
    ours_x = _yarn_rope(x, positions, 5e5, ours, 64)
    theirs_x = reference.rotate(x[0], positions[0], w32,
                                full["attention_factor"])
    for got in (ours_x[0], theirs_x):
        np.testing.assert_allclose(got[:4], hf[0, :4], rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(got, hf[0], rtol=0, atol=3e-3)
    assert float(jnp.abs(hf[0, 4:] - x[0, 4:]).max()) > 1.0  # they do turn
    # the passed half is the input's to the bit, the amplitude not on it
    assert (np.asarray(_yarn_rope(x, positions, 5e5, ours, 64)[..., 64:])
            == np.asarray(passed)).all()
    # told the whole head, the call is the one it was
    np.testing.assert_array_equal(_yarn_rope(x, positions, 5e5, ours, 128),
                                  _yarn_rope(x, positions, 5e5, ours))


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
    weights and a hundred times outside ``TIGHT`` (bf16 passed off as
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


@pytest.mark.parametrize("window", [24, 150])
def test_the_kernels_path_is_the_reference_path(setup, window, monkeypatch):
    """The equal-width flash forward, interpreted, inside the whole forward
    at 384 positions in tiles of 128 x 128: at groups of 3 and of 4 in one
    model, under a window shorter than a block (24: the walk visits two
    key blocks a query block and most of both is outside) and one that
    straddles blocks (150), and under none. The rows are padded on the
    right to 300 and 77 of their own tokens, as a serving step pads them,
    the kernels are told those lengths, the routed experts multiply the
    rows' own positions alone, and the served step's token is the
    reference's."""
    from ray_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "flash_tiles", lambda *a, **k: (128, 128))
    m = tiny_model(sliding_window=window)
    _, _, params, _ = setup
    cfg = family.build_config(m)
    tokens = jax.random.randint(jax.random.key(6), (2, 384), 2,
                                m["vocab_size"])
    lengths = (300, 77)
    live = jnp.arange(384)[None] < jnp.array(lengths)[:, None]
    tokens = jnp.where(live, tokens, 0)
    flash = dataclasses.replace(cfg, attn_impl="flash")
    ids, _, load = llama_next_token(
        params, tokens, jnp.array(lengths, jnp.int32) - 1, flash, live=live)
    assert load["mean"].shape == (4,)                 # 4 routed layers
    np.testing.assert_allclose(load["mean"], 377 * 4 / 16.0)
    got = llama_forward(params, tokens, flash)
    for b, n in enumerate(lengths):
        want = reference.logits(params, tokens[b, :n], m)
        np.testing.assert_allclose(got[b, :n], want, **TIGHT)
        assert int(ids[b]) == int(want[n - 1].argmax())


# Each control is one of the check's on the chip (tools/laguna_probe.py);
# here, at 96 positions against a window of 24 and an original context of
# 32, each moves the logits by 0.01 to 3 where the program lies 1e-6 from
# the reference. bf16 against float32 is the test above.
@pytest.mark.parametrize("control, why", [
    (dict(gate=False),
     "the grouped-query operator had no gate before this family"),
    (dict(partial=False),
     "every attention layer turned its whole head before this family"),
    (dict(sliding_theta=5e5),
     "one rope_theta served both layer kinds before this family"),
    (dict(yarn=False), "plain rope in place of YaRN"),
    (dict(window=False), "the window ignored"),
    (dict(window_keys=23),
     "q - k < window or <= window: whether the window counts the query's "
     "own position is a convention"),
    (dict(window_keys=25), "the same, the other way"),
    (dict(full_group=4),
     "one head count served both layer kinds: a full layer's six heads "
     "grouped as a sliding layer's eight are"),
    (dict(scaling=1.0), "moe_routed_scaling_factor left out"),
    (dict(shared=False), "the shared expert left out"),
    (dict(scores="softmax"), "OLMoE's and Mellum's router"),
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


def test_the_program_with_one_head_count_is_told_apart(setup):
    """What a program with one head count and one rope would compute if it
    could load the leaves: the sliding layers at the full layers' six heads
    (their first six of eight), far outside the tolerance."""
    m, cfg, params, tokens = setup
    want = reference.logits(params, tokens[0], m)
    cut = dict(params["layers"]["sliding_routed"])
    cut.update(wq=cut["wq"][:, :, :6], wo=cut["wo"][:, :6],
               w_head_gate=cut["w_head_gate"][:, :, :6])
    one = dataclasses.replace(cfg, swa_num_heads=0)
    got = llama_forward(dict(params, layers=dict(
        params["layers"], sliding_routed=cut)), tokens[:1], one)[0]
    assert float(jnp.abs(got - want).max()) > 100 * 2e-5


def test_the_served_step_and_remat_compute_the_same(setup):
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    for remat_policy in ("dots", "full"):
        other = dataclasses.replace(cfg, remat=True,
                                    remat_policy=remat_policy)
        np.testing.assert_allclose(llama_forward(params, tokens, other), want,
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# decode through the sliding layers' ring and the full layers' rows
# --------------------------------------------------------------------------
@pytest.mark.parametrize("prefill, chunk", [(10, 1), (40, 1), (30, 7)])
def test_decode_through_the_state_is_the_full_forward(setup, prefill, chunk):
    """A prompt shorter than the window of 24 and one longer (and longer
    than YaRN's original 32), then token by token (and in chunks of 7) well
    past the window's length, so that a sliding layer's oldest rows are
    dropped again and again; the gate and the two ropes on the cached path
    too. Logits, not tokens."""
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
        # key/value heads do not differ by kind
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
    assert state[1][0].shape == (2, 24, 2, 16)


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


def test_the_served_class_counts_by_each_kinds_own_heads(served):
    from ray_tpu.ops.pallas import flash_attention as fa

    m, gen = served
    prompt = list(range(3, 133))                     # 130 positions
    tokens = list(gen({"prompt": prompt, "max_new": 3}))
    assert len(tokens) == 3
    stats = gen.engine_stats()
    assert stats["layer_kinds"] == {"attention_dense": 1,
                                    "sliding_routed": 3,
                                    "attention_routed": 1}
    assert stats["positions_computed"] == 3 * 2 * 256
    lengths = (130, 131, 132)
    assert stats["window_keys_kept"] == 3 * sum(
        n * 24 - 24 * 23 // 2 for n in lengths)
    assert stats["window_keys_seen"] == 3 * sum(
        n * (n + 1) // 2 for n in lengths)
    # 2 full layers at 6 heads over the causal walk, 3 sliding at 8 over
    # the window's: each kind's own count and walk
    tiles = fa.flash_tiles(256, 256, head_dim=16)
    run = live = 0
    for n in lengths:
        for layers, heads, window in ((2, 6, None), (3, 8, 24)):
            r, own = fa.causal_blocks(256, np.array([n, 0]), tiles, window)
            run += layers * heads * r
            live += layers * heads * own
    assert stats["attn_blocks_run"] == run > 0
    assert stats["attn_blocks_live"] == live > 0
    assert stats["attn_blocks_skipped"] == run - live
    assert stats["expert_pairs_all"] == sum(lengths) * 4 * 4
    # the tokens are the reference's own first choices
    rows = reference.logits(gen._params, jnp.asarray(prompt + tokens[:-1]), m)
    assert tokens == np.asarray(rows[129:132].argmax(-1)).tolist()
    # an adapter reaches both kinds' projections at their own head counts
    adapted = list(gen({"prompt": prompt, "max_new": 2, "adapter": "a1"}))
    assert len(adapted) == 2


# --------------------------------------------------------------------------
# the family module
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(attention_bias=True), "attention_bias True"),
    (dict(moe_apply_router_weight_on_input=True), "router_weight_on_input"),
    (dict(gating="per-element"), "head-wise gate"),
    (dict(rope_scaling=None), r"does not understand \['rope_scaling'\]"),
    (dict(sliding_window=0), "at least 1"),
    (dict(layer_types=["full_attention"] * 4), "layer_types names 4"),
    (dict(layer_types=PATTERN[:4] + ["conv"]), r"\['conv'\]"),
    (dict(mlp_layer_types=["sparse"] * 4 + ["dense"]), "the leading ones"),
    (dict(num_attention_heads_per_layer=[48, 64, 64, 56, 48]),
     "one head count a layer type"),
    (dict(num_attention_heads=64), "the full layers' count"),
    (dict(num_experts_per_tok=257), "1..num_experts"),
    (dict(num_key_value_heads=5), "whole groups"),
    (dict(shared_expert_intermediate_size=700), "whole number of experts"),
    (dict(partial_rotary_factor=0.25), "is the full layers'"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    with pytest.raises(ValueError, match=match):
        family.check(dict(loader.load_config(CONFIG), **change))


@pytest.mark.parametrize("entry, change, match", [
    ("sliding_attention", dict(partial_rotary_factor=0.5), "whole head"),
    ("sliding_attention", dict(rope_type="yarn"), "whole head"),
    ("full_attention", dict(rope_type="default"), "expected rope_type yarn"),
    ("full_attention", dict(attention_factor=1.0), "0.1 ln"),
    ("full_attention", dict(mscale=0.7), "expected rope_type yarn"),
    ("full_attention", dict(original_max_position_embeddings=8192),
     "given twice"),
])
def test_the_family_refuses_another_rope(entry, change, match):
    m = loader.load_config(CONFIG)
    ropes = dict(m["rope_parameters"])
    ropes[entry] = dict(ropes[entry], **change)
    with pytest.raises(ValueError, match=match):
        family.check(dict(m, rope_parameters=ropes))


def test_a_file_that_lacks_a_key_is_refused():
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "num_attention_heads_per_layer"}
    with pytest.raises(ValueError,
                       match=r"lacks \['num_attention_heads_per_layer'\]"):
        family.check(lacking)


def test_the_parent_fails_on_the_cell_within_seconds(repo_root, tmp_path):
    """This PR's benchmark files over a program whose configuration has no
    partial rotary factor: ``run.py`` exits at once and says so (the driver
    tries each new cell on the parent first, and a parent that hangs there
    refuses the PR)."""
    import shutil
    import time

    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert family._config_fields() == fields
    assert (set(family.MODEL_KEYS.values()) | set(family.BUILT)
            | set(family.MODELING)) <= fields
    root = tmp_path / "parent"
    for sub in ("benchmark", "ray_tpu"):
        shutil.copytree(os.path.join(repo_root, sub), root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(repo_root, "BENCHMARK.json"), root)
    llama = root / "ray_tpu" / "models" / "llama.py"
    llama.write_text(llama.read_text().replace(
        "    partial_rotary_factor: float = 1.0\n", ""))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    t = time.time()
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "5", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=60)
    assert time.time() - t < 30
    assert proc.returncode not in (0, 3)
    assert "LlamaConfig has no ['partial_rotary_factor']" in proc.stderr


def test_the_configuration_keeps_every_published_number():
    m = loader.load_config(CONFIG)
    row = published()
    assert m["source"] == row["source_url"]
    assert m["reduced"] == ["num_hidden_layers", "layer_types",
                            "mlp_layer_types",
                            "num_attention_heads_per_layer"]
    cut = {"num_hidden_layers": 5,
           **{k: row["config"][k][:5] for k in m["reduced"][1:]}}
    assert m["changed_from_source"] == {
        k: {"source": row["config"][k], "here": here}
        for k, here in cut.items()}
    for key, value in row["config"].items():
        assert m[key] == cut.get(key, value), key
    assert set(m) - set(row["config"]) == {
        "name", "source", "family", "reduced", "changed_from_source",
        "assumed", "program", "deployment", "notes"}
    # the leading dense layer, then one whole period
    assert m["layer_types"] == PATTERN
    assert m["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert m["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    # no width, head count, expert count, top_k, window, rope number or
    # vocabulary row is cut
    for key in ("hidden_size", "head_dim", "moe_intermediate_size",
                "shared_expert_intermediate_size", "intermediate_size",
                "num_attention_heads", "num_key_value_heads", "num_experts",
                "num_experts_per_tok", "sliding_window", "vocab_size",
                "rope_parameters", "partial_rotary_factor",
                "moe_routed_scaling_factor", "max_position_embeddings"):
        assert key not in m["reduced"] and m[key] == row["config"][key]
    assert m["program"] == {"attn_impl": "flash", "dtype": "bfloat16",
                            "param_dtype": "bfloat16"}
    said = " ".join(m["assumed"])
    for item in ("gating true", "33 442 596 864", "34 066 827 264",
                 "sigmoid", "norm_topk_prob", "No RMSNorm over the heads",
                 "no gate on the shared expert", "silu",
                 "multi-token-prediction", "rotate-half",
                 "q - k < sliding_window", "apply_rotary_pos_emb",
                 "random from --seed"):
        assert item in said, item
    assert "eight-stage pipeline" in m["deployment"]
    assert "layers 0-4" in m["deployment"]
    assert "3 869 857 792" in " ".join(m["notes"])


def test_the_family_module_imports_no_jax(repo_root):
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.families import laguna as f; "
            "from benchmark.harness import loader; "
            "m = loader.load_config(%r); f.check(m); "
            "print(f.num_params(m)); "
            "assert 'jax' not in sys.modules, 'jax was imported'"
            % (repo_root, CONFIG))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "3869857792"


# --------------------------------------------------------------------------
# the cell's files
# --------------------------------------------------------------------------
def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver
    from ray_tpu.ops.pallas import flash_attention as fa
    from ray_tpu.ops.pallas import grouped_matmul as gm

    cell = loader.load_cell(CELL)
    mellum = loader.load_cell("serve_mellum2_projctx")
    # the engine is serve_mellum2_projctx's but for the answers' length
    assert {k: v for k, v in cell["engine"].items()
            if k != "max_new_tokens"} == {
        k: v for k, v in mellum["engine"].items() if k != "max_new_tokens"}
    assert cell["engine"]["max_batch_size"] == 4
    assert cell["engine"]["allowed_batch_sizes"] == [4]
    assert cell["engine"]["seq_bucket"] == 1024
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 2048, "sigma": 0.7, "min": 384,
                                 "max": 6080}
    assert mix["output_len"] == {"median": 16, "sigma": 0.5, "min": 8,
                                 "max": 48}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 48
    # six programs; a context never passes 6144; prompts under the window
    # exist
    assert serve_driver.seq_buckets(cell) == [1024, 2048, 3072, 4096, 5120,
                                              6144]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 6144
    assert mix["prompt_len"]["min"] < cell["model"]["sliding_window"]
    assert list(cell["check"]["limits"]) == ["gap_capped_mean"]
    assert all(0 < limit < 1 for limit in cell["check"]["limits"].values())
    assert mix["rate_per_s"] / mix["knee"]["rate_per_s"] == \
        pytest.approx(0.6, abs=0.02)
    assert "order_seed" in mix and "found_by" in mix["knee"]
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    assert OWN <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    assert not OWN & {m["name"] for m in loader.metrics_for_cell(mellum)}
    listed = loader.manifest_cell(manifest, CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        CONFIG, "agent_turns_mixed_depth", 1)
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == cell["model"]["reduced"]
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
    # flash_tiles gives 1024 x 1024 at all six buckets, so the window of
    # 512 is narrower than the tile and a sliding layer walks two key
    # blocks a query block (one at the first bucket)
    for seq in serve_driver.seq_buckets(cell):
        assert fa.flash_tiles(seq, seq, head_dim=128) == (1024, 1024)
        assert fa._window_key_blocks(seq, 1024, 1024, 512) == min(
            2, seq // 1024)
    # the grouped matmuls' tiles at the smallest and the largest step
    for rows in (4 * 1024 * 8, 4 * 6144 * 8):
        assert gm.gmm_tiles(rows, 2048, 512, stacks=2,
                            out_itemsize=2) == (512, 512)
        assert gm.gmm_tiles(rows, 512, 2048, out_itemsize=4) == (512, 2048)


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
    records = [whole_rows(4, 6144), whole_rows(1, 700)]
    stats = {"window_keys_kept": 30, "window_keys_seen": 120,
             "expert_pairs_fullest": 150.0, "expert_pairs_mean": 100.0}
    view = view_of(ops, records, stats)
    got = {}
    for metric in loader.metrics_for_cell(cell):
        if metric["name"] in OWN:
            got[metric["name"]] = loader.load_reader(metric)(view, metric)
    assert set(got) == OWN
    assert got["laguna_window_flash_fwd_ms.serve"] == pytest.approx(100.0)
    assert got["laguna_full_flash_fwd_ms.serve"] == pytest.approx(75.0)
    assert got["laguna_expert_matmul_sort_ms.serve"] == pytest.approx(130.0)
    assert got["laguna_window_keys_kept_pct.serve"] == 25.0
    assert got["laguna_expert_load_imbalance.serve"] == 1.5
    pk = view["peaks"]
    flops, hbm = pk["bf16_flops_per_s"], pk["hbm_bytes_per_s"]
    assert got["laguna_window_flash_fwd_roofline_pct.serve"] == \
        pytest.approx(100 * sum(family.window_flash_flops(m, s)
                                for s in records) / flops / 0.400)
    assert got["laguna_full_flash_fwd_roofline_pct.serve"] == \
        pytest.approx(100 * sum(family.full_flash_flops(m, s)
                                for s in records) / flops / 0.300)
    # the experts: the big step by its FLOPs, the small one by the
    # weights' stream: both sides bind in one cell
    assert got["laguna_expert_ffn_roofline_pct.serve"] == pytest.approx(
        100 * (family.expert_ffn_flops(m, 4 * 6144) / flops
               + family.expert_ffn_bytes(m) / hbm) / 0.500)
    assert family.expert_ffn_flops(m, 700) / flops < \
        family.expert_ffn_bytes(m) / hbm < \
        family.expert_ffn_flops(m, 4 * 6144) / flops
    assert all(0 < got[k] < 100 for k in OWN if "roofline" in k)
    # a program without the span or the counter: nothing, and no raise
    bare = view_of([("fusion.1", 0.1, 2)], records, {})
    for metric in loader.metrics_for_cell(cell):
        if metric["name"] in OWN:
            assert loader.load_reader(metric)(bare, metric) is None


def test_the_cells_step_holds_the_kernels_under_their_scopes():
    from tests.benchmark.test_deepseek_v2 import program_text

    text = program_text(CELL, "step2048")
    assert text.count("name=flash_attention_window") == 1   # one run of 3
    assert "ragged_dot" not in text
    # reference attention's scores would be [8, 64, 2048, 2048]
    assert "8,64,2048,2048" not in text and "8,48,2048,2048" not in text
    # the queries reach the kernels at each kind's heads, the keys at
    # their 8 heads, never repeated
    assert "bf16[8,64,2048,128]" in text and "bf16[8,48,2048,128]" in text
    assert "bf16[8,8,2048,128]" in text


def test_the_probe_rehearses(capsys, tmp_path):
    from benchmark.tools import laguna_probe

    out = tmp_path / "probe.jsonl"
    rc = laguna_probe.check_probe.main([
        "--workload", CELL, "--seeds", "1", "--control-seeds", "1",
        "--requests", "2", "--rehearsal", "--out", str(out)])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    controls = set(laguna_probe.check_probe.CONTROLS["laguna"]) | {
        "mantissa_3_bits"}
    assert len(controls) == 11
    assert controls | {"program", "tokens_shifted", "tokens_stale"} \
        <= set(line)
    # float32 on the CPU: the program's tokens are the reference's own
    assert line["program"]["gap_capped_mean"] == 0.0
    assert line["program"]["correct"]
    told = [name for name in controls if not line[name]["correct"]]
    # tokens do not tell every control at these sizes (one key of the
    # window, a theta): the logits do, in test_a_fault_fails_the_tolerance
    assert {"tokens_shifted", "tokens_stale"} & set(line) and len(told) >= 6
    assert not line["tokens_shifted"]["correct"]
    assert not line["tokens_stale"]["correct"]


# --------------------------------------------------------------------------
# the window's convention and the groups of six, at the kernels' outputs
# (tools/laguna_window_check.py)
# --------------------------------------------------------------------------
def window_check(capsys, *argv):
    from benchmark.tools import laguna_window_check

    rc = laguna_window_check.main(["--rehearsal", *argv])
    return rc, [json.loads(ln) for ln in
                capsys.readouterr().out.strip().splitlines()]


def test_the_window_check_rehearses(capsys, tmp_path):
    out = tmp_path / "lines" / "window.jsonl"
    rc, lines = window_check(capsys, "--seeds", "1", "--out", str(out))
    assert rc == 0 and len(lines) == 1
    assert lines == [json.loads(ln) for ln in out.read_text().splitlines()]
    line = lines[0]
    assert line["sound_ok"] and line["faults_told"] and line["rehearsal"]
    assert (line["rows"], line["length"], line["heads"], line["full_heads"],
            line["kv_heads"], line["window"]) == (4, 256, 8, 6, 2, 24)
    assert max(line["sound"], line["sound_told"], line["full"],
               line["full_told"]) < line["tolerance"] < line["off_over"] \
        < min(line["one_key_short"], line["one_key_long"],
              line["window_ignored"], line["group_of_8_for_6"])


def test_the_window_check_tells_a_kernel_one_key_off(capsys, monkeypatch):
    """The fault planted in the kernel itself: `<=` where `<` belongs."""
    from ray_tpu.ops.pallas import flash_attention as fa

    sound = fa.flash_attention_window
    monkeypatch.setattr(
        fa, "flash_attention_window",
        lambda q, k, v, window, lengths=None: sound(q, k, v, window + 1,
                                                    lengths))
    rc, (line,) = window_check(capsys, "--seeds", "1")
    assert rc == 1 and not line["sound_ok"]
    assert line["sound"] > line["off_over"]


def test_the_window_check_measures_on_a_chip_alone():
    from benchmark.tools import laguna_window_check

    with pytest.raises(SystemExit, match="no chip"):
        laguna_window_check.main(["--seeds", "1"])


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
