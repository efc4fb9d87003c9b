"""DeepSeek-V2's decoder through the one block of ``models/llama.py``
against the plain float32 reference, tiny, on the CPU: latent attention in
its prefill form (two widths, a rotary key the heads share) and in its
decode form (the absorbed products over the latent rows), the group-limited
choice, the shared experts, and one chip's share of the routed experts; the
family module's checks and counts; the cell's files and the reader it
brings; and what the old models keep.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerances are a few 1e-5: computing in bf16, the group limit
ignored, YaRN's ``m^2`` left out of the softmax scale or the shared experts
left out move the results by hundreds to thousands of times that (the test
beside the logits' shows it). A score that ties to within that error at the
boundary of the chosen experts or groups would flip an expert; the seeds
below meet no such tie.
"""

import dataclasses
import hashlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import deepseek_v2 as family
from benchmark.harness import lastline, loader, peaks
from benchmark.reference import deepseek_v2 as reference
from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, LoraConfig, RopeScaling, _latent_attention, _rms_norm,
    init_decode_state, init_llama, init_lora, latent_softmax_scale,
    llama_decode, llama_forward, llama_logical_axes, llama_loss,
    llama_next_token)
from ray_tpu.ops.attention import attention, reference_attention
from ray_tpu.ops.pallas import flash_attention as fa

CELL = "serve_dsv2_docqa"
CONFIG = "deepseek-v2-serve-ep8-l8"
TIGHT = dict(rtol=5e-5, atol=5e-5)
# config.json of deepseek-ai/DeepSeek-V2, as the catalog beside the
# model-configs guide reads it (row DeepSeek-V2, `config`)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 12288, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1, "n_group": 8,
    "n_routed_experts": 160, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_group": 3, "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}


def tiny_model(**over):
    """The rehearsal's sizes (16 experts in 4 groups of which 2 stay, 3 a
    token, group 0 held), computed in float32 by the reference path."""
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, program={"attn_impl": "reference", "dtype": "float32",
                         "param_dtype": "float32"})
    m.update(over)
    return m


def held(m, first, count=None):
    """The same model holding another share of its experts."""
    count = m["n_routed_experts"] if count is None else count
    return dict(m, n_routed_experts=count,
                expert_share=dict(m["expert_share"], first=first))


def randomised(params, key):
    """Norm weights off 1, so that a norm left out or misplaced shows."""
    def off_one(path, a):
        if not path[-1].key.endswith("_norm"):
            return a
        return 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, sum(map(ord, str(path)))), a.shape)
    return jax.tree_util.tree_map_with_path(off_one, params)


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = family.build_config(m)
    params = randomised(init_llama(cfg, jax.random.key(3)), jax.random.key(5))
    tokens = jax.random.randint(jax.random.key(4), (2, 48), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


# --------------------------------------------------------------------------
# the configuration, the tree and its count
# --------------------------------------------------------------------------
def test_the_configuration_the_family_builds(setup):
    m, cfg, _, _ = setup
    assert cfg.layer_kinds() == ("latent_dense",) + ("latent_routed",) * 3
    assert cfg.layer_runs() == (("latent_dense", 0, 1),
                                ("latent_routed", 0, 3))
    assert (cfg.num_experts, cfg.experts_held) == (16, (0, 4))
    assert (cfg.router_groups, cfg.router_topk_groups) == (4, 2)
    assert cfg.num_shared_experts == 2 and cfg.routed_scaling_factor == 16
    assert cfg.rope_scaling == RopeScaling(
        factor=40, original_max_position_embeddings=64, beta_fast=32,
        beta_slow=1, mscale=0.707, mscale_all_dim=0.707)
    # every expert held: no share
    whole = family.build_config(held(m, 0, 16))
    assert whole.experts_held is None and whole.num_experts == 16
    with pytest.raises(ValueError, match="latent_attention"):
        dataclasses.replace(cfg, layer_types=("mla",) * 4).layer_kinds()


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert set(params["layers"]) == {"latent_dense", "latent_routed"}
    routed = params["layers"]["latent_routed"]
    assert routed["router"].shape == (3, 64, 16)          # all 16 experts
    assert routed["we_gate"].shape == (3, 4, 64, 32)      # the 4 held
    assert routed["ws_gate"].shape == (3, 64, 2 * 32)     # two shared
    assert routed["wq_b"].shape == (3, 24, 4, 16 + 8)
    assert routed["wkv_a"].shape == (3, 64, 16 + 8)
    assert routed["wkv_b"].shape == (3, 16, 4, 16 + 16)
    assert routed["wo"].shape == (3, 4, 16, 64)
    assert "wq" not in routed and "w_gate" not in routed
    assert axes["layers"]["latent_routed"]["wq_b"] == (
        None, None, "heads", "head_dim")
    assert axes["layers"]["latent_routed"]["ws_down"] == (
        None, "mlp", "embed")
    total = sum(a.size for a in jax.tree.leaves(params))
    assert total == cfg.num_params() == family.num_params(m)


def test_yarn_and_the_softmax_scale_at_the_published_numbers():
    rs = PUBLISHED["rope_scaling"]
    scaling = RopeScaling(**{k: v for k, v in rs.items() if k != "type"})
    got = scaling.inv_freq(64, 10000.0)
    want = reference.yarn_inv_freq(64, 10000.0, rs)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # the fastest dims keep their frequency, the slowest are divided by 40
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(got[-8:], plain[-8:] / 40, rtol=1e-6)
    assert np.all(np.diff(got) < 0)
    assert scaling.rotary_amplitude() == 1.0
    m = 0.1 * 0.707 * np.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    cfg = family.build_config(loader.load_config(CONFIG))
    assert latent_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)
    assert latent_softmax_scale(cfg) == pytest.approx(
        reference.softmax_scale(loader.load_config(CONFIG)))


# --------------------------------------------------------------------------
# program against reference
# --------------------------------------------------------------------------
def test_logits_agree_with_the_reference(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens, cfg)
    for b in range(tokens.shape[0]):
        np.testing.assert_allclose(
            got[b], reference.logits(params, tokens[b], m), **TIGHT)


def test_what_the_tolerance_tells(setup):
    """bf16 compute, and each of the check's controls, against 5e-5."""
    m, cfg, params, tokens = setup
    want = reference.logits(params, tokens[0], m)
    off = lambda got: float(jnp.abs(got - want).max())  # noqa: E731
    assert off(llama_forward(params, tokens[:1], cfg)[0]) < 5e-5
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    assert off(llama_forward(params, tokens[:1], bf16)[0]) > 100 * 5e-5
    plain_scale = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5
    for control in (dict(group_limited=False), dict(shared=False),
                    dict(scale=plain_scale)):
        assert off(reference.logits(params, tokens[0], m, **control)) \
            > 100 * 5e-5, control
    # a rotary weight left in the order the source stores it
    routed = dict(params["layers"]["latent_routed"])
    routed["wkv_a"] = routed["wkv_a"].at[..., -8:].set(
        routed["wkv_a"][..., -8:][..., ::-1])
    wrong = dict(params, layers=dict(params["layers"], latent_routed=routed))
    assert off(llama_forward(wrong, tokens[:1], cfg)[0]) > 100 * 5e-5


def test_the_served_step_and_remat_compute_the_same(setup):
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    for remat_policy in ("dots", "full", "mixed:2"):
        other = dataclasses.replace(cfg, remat=True,
                                    remat_policy=remat_policy)
        np.testing.assert_allclose(llama_forward(params, tokens, other), want,
                                   rtol=1e-5, atol=1e-5)
    last = jnp.array([47, 20], jnp.int32)
    live = jnp.arange(48)[None, :] <= last[:, None]
    ids, hidden, load = llama_next_token(params, tokens, last, cfg, live=live)
    assert ids.tolist() == [int(want[0, 47].argmax()),
                            int(want[1, 20].argmax())]
    # a share's load: the fullest and the mean of the 4 held experts, and
    # the pairs over all 16, a routed layer
    assert set(load) == {"fullest", "mean", "all"}
    assert load["all"].tolist() == [3.0 * (48 + 21)] * 3
    assert np.all(np.asarray(load["mean"]) * 4 <= np.asarray(load["all"]))
    assert np.all(np.asarray(load["fullest"]) >= np.asarray(load["mean"]))


def test_the_routed_layer_with_a_share_agrees_with_the_reference(setup):
    m, cfg, params, _ = setup
    layers = params["layers"]["latent_routed"]
    x = jax.random.normal(jax.random.key(9), (2, 24, 64))
    for j in range(3):
        lp = {k: v[j] for k, v in layers.items()}
        h = _rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        got, books = moe.expert_ffn(cfg, h, lp)
        for b in range(2):
            np.testing.assert_allclose(
                got[b], reference.moe_ffn(x[b], layers, j, m), **TIGHT)
        # read in place from the stack, as the scanned forward reads it
        in_place, _ = moe.expert_ffn(cfg, h, moe.in_stack(lp, layers, j))
        np.testing.assert_allclose(in_place, got, rtol=1e-6, atol=1e-6)
        assert float(books["pairs"].sum()) == 2 * 24 * 3
        np.testing.assert_array_equal(books["pairs_here"],
                                      books["pairs"][:4])
        assert 0 < float(books["pairs_here"].sum()) < 2 * 24 * 3


def test_a_share_keeps_the_padding_off_and_drops_nothing(setup):
    """One way whatever the routing: a step's padding is kept off the held
    experts, and a router that sends every pair here loses none."""
    m, cfg, params, _ = setup
    layers = params["layers"]["latent_routed"]
    x = jax.random.normal(jax.random.key(12), (2, 24, 64))
    lp = {k: v[0] for k, v in layers.items()}
    h = _rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    # a step's padding: the rows' own positions are what they were, the
    # padded ones get the shared experts alone, and no held expert's rows
    mask = jnp.arange(24)[None, :] < jnp.array([[20], [5]])
    masked, books_m = moe.expert_ffn(
        cfg, h, moe.in_stack(lp, layers, 0, mask))
    plain, _ = moe.expert_ffn(cfg, h, lp)
    np.testing.assert_allclose(masked[0, :20], plain[0, :20], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(masked[1, :5], plain[1, :5], rtol=1e-6,
                               atol=1e-6)
    r = h[1, 5:]
    np.testing.assert_allclose(
        masked[1, 5:], reference.shared_part(r, layers, 0), **TIGHT)
    assert float(books_m["pairs"].sum()) == 25 * 3
    # a router that loves the held group, on inputs with a common
    # component: every position's 3 pairs are held, 144 of 144
    x = x + 3.0
    h = _rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    loves = dict(lp, router=lp["router"].at[:, :4].add(1.0))
    got, books = moe.expert_ffn(cfg, h, loves)
    assert float(books["pairs_here"].sum()) == 144
    stack = dict(layers, router=layers["router"].at[0].set(loves["router"]))
    for b in range(2):
        np.testing.assert_allclose(
            got[b], reference.moe_ffn(x[b], stack, 0, m), **TIGHT)
    # and the share's jaxpr holds one way: no branch on the routing
    text = str(jax.make_jaxpr(lambda h: moe.expert_ffn(cfg, h, lp)[0])(h))
    assert "cond[" not in text


def test_the_groups_shares_add_up_to_the_uncut_layer(setup):
    """The guide's one test of the share: the 4 groups' partial results,
    the shared experts counted once, are the uncut reference's layer."""
    m, cfg, params, _ = setup
    whole_m = held(m, 0, 16)
    whole_cfg = family.build_config(whole_m)
    whole = randomised(init_llama(whole_cfg, jax.random.key(7)),
                       jax.random.key(8))["layers"]["latent_routed"]
    x = jax.random.normal(jax.random.key(10), (1, 40, 64))
    want = reference.moe_ffn(x[0], whole, 1, whole_m)
    lp = {k: v[1] for k, v in whole.items()}
    h = _rms_norm(x, lp["mlp_norm"], whole_cfg.rms_eps)
    np.testing.assert_allclose(moe.expert_ffn(whole_cfg, h, lp)[0][0], want,
                               **TIGHT)
    shared = reference.shared_part(h[0], whole, 1)
    total, pairs = shared, 0.0
    for g in range(4):   # each chip of the deployment: its group, all else
        chip_cfg = dataclasses.replace(whole_cfg, experts_held=(4 * g, 4))
        chip = dict(lp, **{k: lp[k][4 * g:4 * g + 4]
                           for k in moe.EXPERT_STACKS})
        y, books = moe.expert_ffn(chip_cfg, h, chip)
        # what a chip computes less what every chip computes alike
        total = total + (y[0] - shared)
        pairs += float(books["pairs_here"].sum())
        # and the reference's own share is the program's
        stack = {k: (v[:, 4 * g:4 * g + 4] if k in moe.EXPERT_STACKS else v)
                 for k, v in whole.items()}
        np.testing.assert_allclose(
            y[0], reference.moe_ffn(x[0], stack, 1, held(m, 4 * g)), **TIGHT)
    np.testing.assert_allclose(total, want, **TIGHT)
    assert pairs == 40 * 3        # every pair lands on exactly one chip


def test_the_grouped_choice_is_a_top_k_under_a_mask():
    cfg = LlamaConfig.tiny()
    cfg = dataclasses.replace(cfg, num_experts=12, experts_per_token=3,
                              router_groups=4, router_topk_groups=2)
    rng = np.random.default_rng(0)
    probs = rng.random((64, 12)).astype(np.float32)
    # ties: between groups' best scores, and between experts of one group
    probs[0] = 0.1
    probs[1, [0, 3, 6]] = 0.9
    probs[2, :6] = [0.5, 0.5, 0.5, 0.5, 0.4, 0.4]
    kept = np.asarray(moe._best_groups(cfg, jnp.asarray(probs)))
    for t in range(64):
        best = probs[t].reshape(4, 3).max(-1)
        # the 2 best groups, the lower index between equals
        groups = sorted(sorted(range(4), key=lambda g: (-best[g], g))[:2])
        mask = np.repeat(np.isin(np.arange(4), groups), 3)
        np.testing.assert_array_equal(kept[t], np.where(mask, probs[t], 0.0))
        _, chosen = jax.lax.top_k(jnp.asarray(kept[t]), 3)
        want = sorted(np.flatnonzero(mask),
                      key=lambda e: (-probs[t, e], e))[:3]
        assert chosen.tolist() == [int(e) for e in want]
    assert np.flatnonzero(kept[0]).tolist() == [0, 1, 2, 3, 4, 5]
    assert np.flatnonzero(kept[1]).tolist() == [0, 1, 2, 3, 4, 5]
    # the reference's router makes the same choice
    r = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 12)), jnp.float32)
    scores, _, experts = reference.route(
        r, router, top_k=3, groups=4, kept_groups=2, renormalise=False,
        scaling=1.0)
    _, chosen = jax.lax.top_k(moe._best_groups(cfg, scores), 3)
    np.testing.assert_array_equal(chosen, experts)
    with pytest.raises(ValueError, match="12 experts in 5 groups"):
        moe._best_groups(dataclasses.replace(cfg, router_groups=5), scores)


def test_decode_through_the_latent_state_is_the_full_forward(setup):
    m, cfg, params, tokens = setup
    state = init_decode_state(cfg, 2, 64)
    assert [s.shape for s in state] == [(2, 64, 16 + 8)] * 4
    logits, state = llama_decode(params, tokens[:, :20], cfg, state,
                                 jnp.int32(0))
    parts = [logits]
    for t in range(20, 32):       # a token at a time
        logits, state = llama_decode(params, tokens[:, t:t + 1], cfg, state,
                                     jnp.int32(t))
        parts.append(logits)
    got = jnp.concatenate(parts, axis=1)
    for b in range(2):
        np.testing.assert_allclose(
            got[b], reference.logits(params, tokens[b, :32], m), **TIGHT)
    # the state is the normed latent row and the rotated shared key, and
    # nothing past what was written
    assert not np.asarray(state[1][:, 32:]).any()
    assert np.asarray(state[1][:, :32]).all()


def test_the_absorbed_form_is_the_decompressed_one(setup):
    _, cfg, params, _ = setup
    lp = {k: v[1] for k, v in params["layers"]["latent_routed"].items()}
    u = jax.random.normal(jax.random.key(11), (2, 16, 64))
    positions = jnp.broadcast_to(jnp.arange(16), (2, 16))
    want, none = _latent_attention(cfg, u, lp, positions)
    assert none is None
    state = jnp.zeros((2, 16, 24))
    got, state = _latent_attention(cfg, u, lp, positions, state, jnp.int32(0))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_loss_and_its_gradients_against_the_references(setup):
    m, cfg, params, tokens = setup
    batch = {"tokens": tokens[:1, :33]}
    value, grads = jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.loss(p, tokens[0, :32], tokens[0, 1:33], m)
    )(params)
    assert float(value) == pytest.approx(float(want), abs=2e-5)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    wanted = dict(jax.tree_util.tree_flatten_with_path(want_grads)[0])
    for path, g in flat:
        np.testing.assert_allclose(g, wanted[path], rtol=2e-4, atol=2e-5,
                                   err_msg=str(path))
        assert float(jnp.abs(g).max()) > 0, path
    for remat_policy in ("dots", "full"):
        other = dataclasses.replace(cfg, remat=True, loss_chunk=16,
                                    remat_policy=remat_policy)
        assert float(llama_loss(params, batch, other)) == pytest.approx(
            float(value), abs=2e-5), remat_policy


def test_lora_names_what_a_latent_layer_lacks(setup):
    _, cfg, _, _ = setup
    with pytest.raises(ValueError, match=r"LoRA targets \['wq', 'wv'\]: no "
                                         "layer of this model has them"):
        init_lora(cfg, LoraConfig(rank=2, targets=("wq", "wv")),
                  jax.random.key(2))
    lora = init_lora(cfg, LoraConfig(rank=2, targets=("w_gate",)),
                     jax.random.key(2))
    assert set(lora["layers"]) == {"latent_dense"}


# --------------------------------------------------------------------------
# the kernel at two widths, interpreted; the dispatcher
# --------------------------------------------------------------------------
@pytest.mark.parametrize("seq, heads", [(128, 4), (384, 2), (1280, 1)])
def test_the_kernel_at_192_and_128_against_the_reference(seq, heads):
    """Heads of 128 + 64 against values of 128, the rotary key one row a
    position; 1280 is two blocks of 640 a side."""
    ks = jax.random.split(jax.random.key(seq), 5)
    q = jax.random.normal(ks[0], (2, seq, heads, 128))
    q_rope = jax.random.normal(ks[1], (2, seq, heads, 64))
    k = jax.random.normal(ks[2], (2, seq, heads, 128))
    k_rope = jax.random.normal(ks[3], (2, seq, 64))
    v = jax.random.normal(ks[4], (2, seq, heads, 128))
    scale = 192 ** -0.5 * 1.2608 ** 2
    want = reference_attention(q, k, v, q_rope=q_rope, k_rope=k_rope,
                               scale=scale)
    # the reference's two widths are the plain one on concatenated heads
    whole_k = jnp.concatenate(
        [k, jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)], -1)
    whole_q = jnp.concatenate([q, q_rope], -1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", whole_q, whole_k) * scale
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(want, jnp.einsum("bhqk,bkhd->bqhd", probs, v),
                               rtol=2e-5, atol=2e-5)
    got = attention(q, k, v, impl="flash", q_rope=q_rope, k_rope=k_rope,
                    scale=scale)
    assert got.shape == (2, seq, heads, 128)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    if seq == 1280:
        assert fa.flash_tiles(seq, seq, head_dim=256, value_dim=128) == (
            640, 640)


def test_the_two_width_kernel_reads_the_shared_key_once_a_position():
    q = jax.ShapeDtypeStruct((2, 4, 256, 128), jnp.bfloat16)
    q_rope = jax.ShapeDtypeStruct((2, 4, 256, 64), jnp.bfloat16)
    k_rope = jax.ShapeDtypeStruct((2, 256, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda *a: fa._flash_fwd_shared_rope(
        *a, scale=0.1, causal=True))(q, q_rope, q, k_rope, q)
    call = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(call) == 1
    # the operands as they lie in HBM: no head on the rotary key
    assert [tuple(v.aval.shape) for v in call[0].invars] == [
        (2, 4, 256, 128), (2, 4, 256, 64), (2, 4, 256, 128), (2, 256, 64),
        (2, 4, 256, 128)]
    # the scope the trace names it by
    assert str(call[0].source_info.name_stack) == fa.SHARED_ROPE_TRACE_NAME
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda a: fa.flash_attention_shared_rope(
            a, jnp.zeros((1, 128, 1, 64)), jnp.zeros((1, 128, 1, 128)),
            jnp.zeros((1, 128, 64)), jnp.zeros((1, 128, 1, 128)), 0.1,
            True).sum())(jnp.zeros((1, 128, 1, 128)))


def test_which_widths_the_kernels_take_and_what_their_tiles_hold():
    assert [d for d in (32, 64, 96, 128, 192, 256)
            if fa.takes_head_dim(d)] == [64, 128, 256]
    assert fa.takes_head_dim(192, 128, shared_dim=64)
    assert fa.takes_head_dim(256, 128, shared_dim=128)
    assert not fa.takes_head_dim(192, 128)            # nothing shared
    assert not fa.takes_head_dim(192, 192, shared_dim=64)
    assert not fa.takes_head_dim(160, 128, shared_dim=64)
    assert not fa.takes_head_dim(192, 128, shared_dim=32)
    assert not fa.takes_head_dim(128, 64)
    # equal widths reckon what they always have
    for bq, bk in ((128, 128), (1024, 1024), (384, 1152)):
        assert fa.tile_vmem_bytes(bq, bk) == fa.tile_vmem_bytes(
            bq, bk, value_dim=128)
        assert fa.tile_vmem_bytes(bq, bk, head_dim=256, value_dim=128) \
            < fa.tile_vmem_bytes(bq, bk, head_dim=256)
    with pytest.raises(ValueError, match="one width"):
        fa.tile_vmem_bytes(128, 128, head_dim=256, value_dim=128,
                           backward=True)
    # the cell's seven buckets: one block to 1024, then the largest divisor
    assert [fa.flash_tiles(s, s, head_dim=256, value_dim=128)[0]
            for s in range(256, 1793, 256)] == [256, 512, 768, 1024, 640,
                                                768, 896]


def test_auto_raises_where_the_references_scores_cannot_fit(monkeypatch):
    dispatcher = sys.modules["ray_tpu.ops.attention"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatcher, "_device_memory_bytes",
                        lambda: 16 * 2 ** 30)
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    # a width no kernel takes, 8 x 128 heads at 1792: 13 GB of scores fit
    # and are warned about; at 2304 they are 21.7 GB
    small = (shape(8, 1792, 128, 96),) * 3
    with pytest.warns(UserWarning, match="reference path"):
        jax.eval_shape(lambda q, k, v: attention(q, k, v), *small)
    large = (shape(8, 2304, 128, 96),) * 3
    with pytest.raises(ValueError, match="float32 scores are 21743271936"):
        jax.eval_shape(lambda q, k, v: attention(q, k, v), *large)
    # the two widths on the grid go to the kernel, whatever the memory
    seen = []
    monkeypatch.setattr(
        dispatcher, "_flash_per_shard",
        lambda q, k, v, causal, q_rope, k_rope, scale: seen.append(
            (q.shape, k_rope.shape, scale)) or v)
    attention(jnp.zeros((1, 256, 2, 128)), jnp.zeros((1, 256, 2, 128)),
              jnp.zeros((1, 256, 2, 128)), q_rope=jnp.zeros((1, 256, 2, 64)),
              k_rope=jnp.zeros((1, 256, 64)), scale=0.11)
    assert seen == [((1, 256, 2, 128), (1, 256, 64), 0.11)]


# --------------------------------------------------------------------------
# the served class
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    engine = {"lora_rank": 2, "max_batch_size": 2, "allowed_batch_sizes": [2],
              "max_new_tokens": 4, "seq_bucket": 16}
    gen = family.Served(**family.served_kwargs(tiny_model(), engine,
                                               3000000019))
    yield gen
    gen.engine.shutdown()


def test_the_served_class_keeps_the_shares_books(served):
    m = tiny_model()
    stats = served.engine_stats()
    assert stats["layer_kinds"] == {"latent_dense": 1, "latent_routed": 3}
    assert stats["expert_pairs_all"] == stats["expert_pairs_here"] == 0
    states = [served._prefill({"prompt": list(range(3, 14)), "max_new": 4},
                              ""), None]
    served._step("", states)
    after = served.engine_stats()
    # 11 live positions x 3 experts over 16 experts, in each of 3 layers
    assert after["expert_pairs_all"] == 3 * 11 * 3
    assert 0 < after["expert_pairs_here"] < after["expert_pairs_all"]
    assert after["expert_pairs_mean"] * 4 == pytest.approx(
        after["expert_pairs_here"])
    # 2 ids, and fullest, mean and all of each routed layer
    assert after["host_bytes"] - stats["host_bytes"] == 2 * 4 + 3 * 3 * 4
    metric = {m["name"]: m for m in loader.load_metric_files()}[
        "dsv2_routed_pairs_here_pct.serve"]
    read = loader.load_reader(metric)
    assert read({"obs": {"engine_stats_end": after}}, metric) == \
        pytest.approx(100 * after["expert_pairs_here"] / 99)
    # a program that keeps no such books gives nothing, and does not raise
    assert read({"obs": {"engine_stats_end": {}}}, metric) is None
    assert read({"obs": {"engine_stats_end": stats}}, metric) is None
    # what the check compares: the mean over the prompt's positions
    prompt = list(range(5, 37))
    want = reference.last_logits(served._params, jnp.asarray(prompt), m)
    np.testing.assert_allclose(served.last_position_logits(prompt), want,
                               **TIGHT)
    np.testing.assert_allclose(
        want, reference.logits(served._params, jnp.asarray(prompt),
                               m).mean(0), rtol=1e-6, atol=1e-6)
    # no adapter: the base model alone
    assert served._adapter("") is None


# --------------------------------------------------------------------------
# the family module: what it refuses, what it counts
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(sliding_window=4096), r"does not understand \['sliding_window'\]"),
    (dict(rope_scaling=dict(PUBLISHED["rope_scaling"], type="linear")),
     "rope_scaling"),
    (dict(rope_scaling={"type": "yarn", "factor": 40}), "rope_scaling"),
    (dict(rope_scaling=None), "rope_scaling"),
    (dict(n_routed_experts=30), "no whole number of the 8 groups of 20"),
    (dict(expert_share={"first": 10, "of": 160}), "no whole number"),
    (dict(expert_share={"first": 160, "of": 160}), "no whole number"),
    (dict(expert_share={"of": 160}), "expected first and of"),
    (dict(topk_method="noaux_tc"), "topk_method 'noaux_tc'"),
    (dict(scoring_func="sigmoid"), "scoring_func 'sigmoid'"),
    (dict(attention_bias=True), "attention_bias True"),
    (dict(num_key_value_heads=8), "num_key_value_heads"),
    (dict(q_lora_rank=None), "q_lora_rank None"),
    (dict(num_experts_per_tok=0), "num_experts_per_tok"),
    (dict(topk_group=9), "topk_group 9"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    m = dict(loader.load_config(CONFIG), **change)
    with pytest.raises(ValueError, match=match):
        family.check(m)
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "kv_lora_rank"}
    with pytest.raises(ValueError, match="lacks"):
        family.check(lacking)


def test_a_checkout_without_the_fields_is_refused_at_once(monkeypatch):
    # without jax, so that the harness process fails at once where a
    # replica that cannot build its configuration is retried for minutes
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert family._config_fields() == fields
    assert set(family.MODEL_KEYS.values()) | set(family.BUILT) <= fields
    monkeypatch.setattr(family, "_config_fields", lambda: fields - {
        "kv_lora_rank", "experts_held", "router_groups"})
    with pytest.raises(ValueError, match=r"LlamaConfig has no \['experts_held"
                                         r"', 'kv_lora_rank', 'router_gro"):
        family.check(loader.load_config(CONFIG))
    monkeypatch.undo()
    monkeypatch.setattr(family.LlamaGenerator, "STEP_COUNTERS",
                        ("host_bytes", "expert_pairs_mean"))
    with pytest.raises(ValueError, match="keeps no books of a share"):
        family.check(loader.load_config(CONFIG))


def test_the_parent_fails_on_the_cell_within_seconds(repo_root, tmp_path):
    """This PR's benchmark files over a program that lacks its fields:
    ``run.py`` exits at once and names them (the driver tries each new cell
    on the parent first, and a parent that hangs there refuses the PR)."""
    import shutil

    root = tmp_path / "parent"
    for sub in ("benchmark", "ray_tpu"):
        shutil.copytree(os.path.join(repo_root, sub), root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(repo_root, "BENCHMARK.json"), root)
    llama = root / "ray_tpu" / "models" / "llama.py"
    text = llama.read_text()
    for field in ("kv_lora_rank", "experts_held"):
        text = re.sub(rf"\n    {field}: [^\n]*", "", text)
    llama.write_text(text)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "5", "--trace", "0", "--rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=60)
    assert proc.returncode not in (0, 3)
    assert "LlamaConfig has no ['experts_held', 'kv_lora_rank']" \
        in proc.stderr


def test_the_configuration_is_the_published_one_cut_to_a_chips_share():
    m = loader.load_config(CONFIG)
    assert m["source"] == ("https://huggingface.co/deepseek-ai/DeepSeek-V2/"
                           "blob/main/config.json")
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in m["reduced"]:
            assert m["changed_from_source"][key]["source"] == value
            assert m["changed_from_source"][key]["here"] == m[key]
        else:
            assert m[key] == value, key
    assert set(m["changed_from_source"]) == set(m["reduced"])
    # the floors: 4 layers after the dense one, 8 experts, an eighth of
    # the vocabulary; and the share is one whole routing group
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] == 7
    assert m["n_routed_experts"] == 160 // 8 == 20
    assert m["expert_share"] == {"first": 0, "of": 160}
    assert m["vocab_size"] * 8 == 102400
    assert "8 v5e chips" in m["deployment"]
    with open(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}[CONFIG]
    assert listed["reduced"] == m["reduced"]
    assert listed["source"] == m["source"]
    cfg = family.build_config(m)
    assert (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (128, 128, 64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token) == (
        160, (0, 20), 6)
    assert cfg.attn_impl == "flash" and cfg.dtype == jnp.bfloat16
    out = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(out)) == family.num_params(m)
    assert {a.dtype for a in jax.tree.leaves(out)} == {jnp.dtype("bfloat16")}
    # no float32 draw of a whole kind's projection on its way to bf16
    jaxpr = jax.make_jaxpr(lambda k: init_llama(cfg, k))(jax.random.key(0))
    big = [v.aval for eqn in jaxpr.eqns for v in eqn.outvars
           if v.aval.size > 2 ** 30]
    assert big and all(a.dtype == jnp.bfloat16 for a in big)


def test_counts_by_hand():
    m = loader.load_config(CONFIG)
    latent = (5120 * 1536 + 1536 + 1536 * 128 * 192 + 5120 * 576 + 512
              + 512 * 128 * 256 + 128 * 128 * 5120)
    expert = 3 * 5120 * 1536
    assert (latent, expert) == (149_227_520, 23_592_960)
    routed = 20 * expert + 2 * expert + 5120 * 160
    dense = 3 * 5120 * 12288
    total = (8 * (latent + 2 * 5120) + dense + 7 * routed
             + 2 * 12800 * 5120 + 5120)
    assert total == 5_152_773_120 == family.num_params(m)
    assert family.build_config(m).num_params() == total
    # the whole model, by the same functions: 236B
    whole = dict(m, num_hidden_layers=60, n_routed_experts=160,
                 vocab_size=102400)
    assert round(family.num_params(whole) / 1e9, 1) == 235.7
    # a position's pairs here: 6 x 20/160 of three 5120 x 1536 matmuls
    assert family.expert_ffn_flops(m, 8) == \
        7 * 8 * 6 * 0.125 * 3 * 2 * 5120 * 1536
    assert family.expert_ffn_bytes(m) == 7 * 20 * expert * 2 == 6_606_028_800
    need = lambda n: (family.expert_ffn_flops(m, n) / 197e12,  # noqa: E731
                      family.expert_ffn_bytes(m) / 819e9)
    assert need(8 * 801)[0] < need(8 * 801)[1] < need(8 * 802)[0]
    # the latent flash forward of the 8 layers, the causal half
    assert family.flash_fwd_flops(m, 8, 1792) == \
        8 * 8 * 128 * 2 * (192 + 128) * 1792 * 1792 / 2
    assert family.flash_fwd_bytes(m, 8, 1792) == \
        8 * 2 * 8 * 1792 * (128 * (192 + 128 + 128 + 128) + 64)
    flops = lambda s: family.flash_fwd_flops(m, 8, s) / 197e12  # noqa: E731
    bytes_ = lambda s: family.flash_fwd_bytes(m, 8, s) / 819e9  # noqa: E731
    assert flops(768) < bytes_(768) and flops(1024) > bytes_(1024)
    assert family.attention_kernel_flops(m, 8, 256) == \
        3.5 * family.flash_fwd_flops(m, 8, 256)


def test_the_family_module_imports_no_jax(repo_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.harness import loader\n"
        "from benchmark.families import deepseek_v2\n"
        "cell = loader.load_cell('serve_dsv2_docqa')\n"
        "assert deepseek_v2.num_params(cell['model']) > 5.1e9\n"
        "for m in loader.metrics_for_cell(cell): loader.load_reader(m)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % repo_root)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------------
# the cell's files and its metrics
# --------------------------------------------------------------------------
OWN = {"dsv2_mla_flash_fwd_ms.serve", "dsv2_mla_flash_fwd_roofline_pct.serve",
       "dsv2_expert_ffn_roofline_pct.serve",
       "dsv2_expert_matmul_sort_ms.serve", "dsv2_expert_load_imbalance.serve",
       "dsv2_routed_pairs_here_pct.serve"}


def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell(CELL)
    lfm2 = loader.load_cell("serve_lfm2_rag")
    # the engine is the other serving cells' but for the bucket and the
    # longest answer
    differ = ("max_new_tokens", "seq_bucket")
    assert {k: v for k, v in cell["engine"].items() if k not in differ} == {
        k: v for k, v in lfm2["engine"].items() if k not in differ}
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 640, "sigma": 0.6, "min": 192,
                                 "max": 1536}
    assert mix["output_len"] == {"median": 12, "sigma": 0.5, "min": 4,
                                 "max": 32}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 32
    assert cell["engine"]["seq_bucket"] == 256
    assert serve_driver.seq_buckets(cell) == list(range(256, 1793, 256))
    assert cell["check"]["prompt_len"] in serve_driver.seq_buckets(cell)
    assert mix["rate_per_s"] / mix["knee"]["rate_per_s"] == \
        pytest.approx(0.6, abs=0.02)
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    assert OWN <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    assert not OWN & {m["name"] for m in loader.metrics_for_cell(lfm2)}
    listed = loader.manifest_cell(manifest, CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        CONFIG, "doc_qa_short_answers", 1)
    for metric in manifest["per_layer"]:
        if metric["name"] in OWN:
            assert metric["moves"] == "serve_gap_p95_ms"
            assert metric["workloads"] == [CELL]


def view_of(ops, spans, stats):
    cell = loader.load_cell(CELL)
    return {"cell": cell, "peaks": peaks.peak("TPU v5 lite"),
            "trace": {"ops": ops, "host_spans": spans, "steps": 4},
            "obs": {"engine_stats_end": stats}}


def test_the_readers_tell_the_latent_kernel_from_the_other_kernels():
    metrics = {m["name"]: m for m in loader.load_metric_files()}
    m = loader.load_config(CONFIG)
    ops = [("tpu_custom_call:ragged-dot-none-pallas.16", 0.100, 28),
           ("tpu_custom_call:ragged-dot-none-pallas.17", 0.060, 28),
           ("tpu_custom_call:flash_fwd_shared_rope.10", 0.040, 4),
           ("tpu_custom_call:flash_fwd_shared_rope.11", 0.200, 28),
           # the equal-width forward's name is not this kernel's
           ("tpu_custom_call:checkpoint.8", 0.050, 4),
           ("sort.3", 0.010, 84), ("fusion.120", 0.300, 48)]
    spans = {"model_step": [0.9, 4], "len_512": [0.2, 3],
             "len_1792": [0.4, 1], "live_3000": [0.15, 2],
             "live_2100": [0.05, 1], "live_10752": [0.4, 1]}
    view = view_of(ops, spans, {"expert_pairs_fullest": 30.0,
                                "expert_pairs_mean": 20.0,
                                "expert_pairs_here": 400.0,
                                "expert_pairs_all": 3000.0})

    def value(name):
        return loader.load_reader(metrics[name])(view, metrics[name])

    assert value("dsv2_mla_flash_fwd_ms.serve") == pytest.approx(
        1e3 * 0.240 / 4)
    assert value("dsv2_expert_matmul_sort_ms.serve") == pytest.approx(
        1e3 * 0.170 / 4)
    # 512: the bytes of q, k, v, o bind; 1792: the FLOPs
    short = family.flash_fwd_bytes(m, 8, 512) / 819e9
    long = family.flash_fwd_flops(m, 8, 1792) / 197e12
    assert value("dsv2_mla_flash_fwd_roofline_pct.serve") == pytest.approx(
        100.0 * (3 * short + long) / 0.240)
    # over each step's own live positions (the `live_<n>` spans): the
    # bytes bind in the three short steps, the FLOPs in the step of 10 752
    need = (3 * family.expert_ffn_bytes(m) / 819e9
            + family.expert_ffn_flops(m, 10752) / 197e12)
    assert value("dsv2_expert_ffn_roofline_pct.serve") == pytest.approx(
        100.0 * need / 0.160)
    # a program that names no live positions gives nothing, and no raise
    ffn = metrics["dsv2_expert_ffn_roofline_pct.serve"]
    bare = {k: v for k, v in spans.items() if not k.startswith("live_")}
    assert loader.load_reader(ffn)(view_of(ops, bare, {}), ffn) is None
    assert value("dsv2_expert_load_imbalance.serve") == 1.5
    assert value("dsv2_routed_pairs_here_pct.serve") == pytest.approx(
        100 * 400 / 3000)
    # lfm2's flash metric does not read this kernel
    d64 = metrics["flash_fwd_d64_ms.serve"]
    assert loader.load_reader(d64)(view, d64) == pytest.approx(
        1e3 * 0.050 / 4)
    # a program without the kernel or the spans: None, and no raise
    metric = metrics["dsv2_mla_flash_fwd_roofline_pct.serve"]
    read = loader.load_reader(metric)
    assert read(view_of(ops[:2] + ops[4:], spans, {}), metric) is None
    assert read(view_of(ops, {"model_step": [0.9, 4]}, {}), metric) is None


# --------------------------------------------------------------------------
# what the old models keep: their programs are the parent's, as strings
# --------------------------------------------------------------------------
# sha256 of the jaxpr's text (addresses blanked), at the parent dd27b79;
# regenerate with the function below where a PR means to change a program
PARENTS_PROGRAMS = {
    "train_l2_seq4k.init": "d02563bf9b97ea28",
    "train_l2_seq4k.grad": "cc40eeae09e6ec41",
    "serve_chat_steady.init": "3ff45d688c80d7f8",
    "serve_chat_steady.step256": "47bff74954adc074",
    "serve_chat_steady.step384": "f2df55b80038d939",
    "serve_olmoe_chat.init": "126fada9fb96dc80",
    "serve_olmoe_chat.step256": "62cc9022fd93103d",
    "serve_olmoe_chat.step384": "cd1a561be9f92c9b",
    "serve_lfm2_rag.init": "5766fc6f6af74d3d",
    "serve_lfm2_rag.step256": "b753f8009fe61f85",
    "serve_lfm2_rag.step384": "b913a039c197cd82",
}


def program_text(cell_name: str, which: str) -> str:
    """The jaxpr of one of a cell's programs at the real sizes, as text:
    ``init``, ``step<length>`` (8 rows) or ``grad`` (2 x 4096)."""
    cell = loader.load_cell(cell_name)
    engine = dict(cell.get("engine") or {}, lora_rank=4, max_batch_size=8,
                  allowed_batch_sizes=[8], max_new_tokens=8, seq_bucket=128)
    cfg = loader.load_family(cell["model"]).served_kwargs(
        cell["model"], engine, 1)["config"]
    shapes = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    if which == "init":
        jaxpr = jax.make_jaxpr(lambda k: init_llama(cfg, k))(
            jax.random.key(0))
    elif which == "grad":
        batch = {"tokens": jax.ShapeDtypeStruct((2, 4097), jnp.int32)}
        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            lambda p, b: llama_loss(p, b, cfg)))(shapes, batch)
    else:
        length = int(which[len("step"):])
        tokens = jax.ShapeDtypeStruct((8, length), jnp.int32)
        last = jax.ShapeDtypeStruct((8,), jnp.int32)
        live = jax.ShapeDtypeStruct((8, length), jnp.bool_)
        if cfg.num_experts:
            jaxpr = jax.make_jaxpr(lambda p, t, i, on: llama_next_token(
                p, t, i, cfg, live=on))(shapes, tokens, last, live)
        else:
            jaxpr = jax.make_jaxpr(lambda p, t, i: llama_next_token(
                p, t, i, cfg))(shapes, tokens, last)
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


@pytest.mark.parametrize("program", sorted(PARENTS_PROGRAMS))
def test_an_old_models_program_is_the_parents(program):
    cell_name, which = program.split(".")
    text = program_text(cell_name, which)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENTS_PROGRAMS[program]
    assert "flash_fwd_shared_rope" not in text and "top_k" not in text \
        or cell_name != "serve_chat_steady"


def test_the_new_cells_step_holds_both_kernels_and_no_score_tensor():
    text = program_text(CELL, "step1792")
    # a latent forward a run of like layers; two grouped matmuls, over
    # every pair's row (the absent ones' unvisited), and no branch
    assert text.count("name=flash_attention_shared_rope") == 2
    assert text.count("pallas_call[") == 4 and "ragged_dot" not in text
    assert "bf16[86016,5120]" in text and "bf16[21504,5120]" not in text
    # reference attention's scores would be [8, 128, 1792, 1792]
    assert "8,128,1792,1792" not in text
    # the rotary key reaches the kernel as [8, 1792, 64], never a head's
    assert "bf16[8,1792,64]" in text
    assert "bf16[8,128,1792,64]" in text                 # q_pe alone


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
    assert line["metrics"]["dsv2_expert_load_imbalance.serve"]["value"] >= 1.0
    assert 0 < line["metrics"]["dsv2_routed_pairs_here_pct.serve"][
        "value"] < 100
