"""dots3-note-prev's language model through the one block of
``models/llama.py`` against the plain float32 reference, tiny, on the CPU:
latent attention at two geometries, a window and an indexer's choice that
are both SHORTER than the lengths tested (17 and 24 keys against 48 to 128
positions), the head-wise gate, the rescale of the latents, the sigmoid
router with its bias and one chip's share of the routed experts; the decode
through the two operators' states; the family module's checks and counts;
the cell's files and the readers it brings.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerances are a few 1e-5: each control (the choice ignored,
the window ignored, the gate left out, the rescale left out) moves the
logits by tens of thousands of times that, as the test beside the logits'
shows. An index score that ties to within that error at a query's 24th
would flip a key; the seeds below meet no such tie.
"""

import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import dots3_note as family
from benchmark.harness import lastline, loader, peaks
from benchmark.reference import dots3_note as reference
from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, _chosen_keys, _latent_attention, _rms_norm,
    init_decode_state, init_llama, latent_softmax_scale, llama_decode,
    llama_forward, llama_logical_axes, llama_next_token)

CELL = "serve_dots3_longdoc"
CONFIG = "dots3-note-prev-serve-ep8-l5"
TIGHT = dict(rtol=5e-5, atol=5e-5)
FULL, SLIDING = "full_attention", "sliding_attention"
# config.json of dots-studio/dots3-note-prev, as the catalog beside the
# model-configs guide reads it (row dots3-note-prev, `config`)
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512,
    "layer_types": [FULL] + [FULL, SLIDING, SLIDING, SLIDING] * 11 + [FULL],
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128,
    "q_lora_rank": 1024, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid",
    "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
    "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
    "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
    "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064}


def tiny_model(**over):
    """The rehearsal's sizes (a window of 17, an indexer of 8 heads that
    keeps 24 keys, 16 experts of which 4 are held, 3 a token), computed in
    float32 by the reference path."""
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
    """Norm weights off 1 and the two biases off 0, so that a norm or a
    bias left out or misplaced shows."""
    def moved(path, a):
        name = path[-1].key
        k = jax.random.fold_in(key, sum(map(ord, str(path))))
        if name.endswith("_norm"):
            return 1.0 + 0.3 * jax.random.normal(k, a.shape)
        if name in ("wi_k_bias", "router_bias"):
            return 0.1 * jax.random.normal(k, a.shape)
        return a
    return jax.tree_util.tree_map_with_path(moved, params)


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = family.build_config(m)
    params = randomised(init_llama(cfg, jax.random.key(13)), jax.random.key(5))
    tokens = jax.random.randint(jax.random.key(4), (2, 48), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


# --------------------------------------------------------------------------
# the configuration, the tree and its count
# --------------------------------------------------------------------------
def test_the_configuration_the_family_builds(setup):
    m, cfg, _, _ = setup
    assert cfg.layer_kinds() == ("indexed_dense", "indexed_routed",
                                 "window_routed", "window_routed",
                                 "window_routed")
    assert cfg.layer_runs() == (("indexed_dense", 0, 1),
                                ("indexed_routed", 0, 1),
                                ("window_routed", 0, 3))
    assert (cfg.num_experts, cfg.experts_held) == (16, (0, 4))
    assert (cfg.router_scores, cfg.router_bias, cfg.norm_topk_prob) == (
        "sigmoid", True, True)
    assert cfg.router_groups == 0 and cfg.num_shared_experts == 1
    assert cfg.head_gate and cfg.latent_rescale
    full, window = cfg.latent_widths("indexed"), cfg.latent_widths("window")
    assert full == (4, 24, 16, 16, 8, 16, 8e7, 0, 24)
    assert window == (2, 24, 32, 24, 8, 16, 5e4, 17, 0)
    # the plain latent operator is the full geometry with neither
    assert cfg.latent_widths() == full._replace(topk=0)
    assert latent_softmax_scale(cfg) == 24 ** -0.5
    assert latent_softmax_scale(cfg, window) == 32 ** -0.5
    whole = family.build_config(held(m, 0, 16))
    assert whole.experts_held is None and whole.num_experts == 16
    with pytest.raises(ValueError, match="window_latent_attention"):
        dataclasses.replace(cfg, layer_types=("swa",) * 5).layer_kinds()


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert set(params["layers"]) == {"indexed_dense", "indexed_routed",
                                     "window_routed"}
    full = params["layers"]["indexed_routed"]
    window = params["layers"]["window_routed"]
    assert full["wq_b"].shape == (1, 24, 4, 16 + 8)
    assert full["wkv_a"].shape == (1, 64, 16 + 8)
    assert full["wo"].shape == (1, 4, 16, 64)
    assert full["w_head_gate"].shape == (1, 64, 4)
    assert full["wi_q"].shape == (1, 24, 8, 16)
    assert full["wi_k"].shape == (1, 64, 16)
    assert full["wi_k_norm"].shape == full["wi_k_bias"].shape == (1, 16)
    assert full["wi_w"].shape == (1, 64, 8)
    # the second geometry: its own heads, ranks and widths, and no indexer
    assert window["wq_b"].shape == (3, 24, 2, 24 + 8)
    assert window["wkv_a"].shape == (3, 64, 32 + 8)
    assert window["wkv_b"].shape == (3, 32, 2, 24 + 16)
    assert window["wo"].shape == (3, 2, 16, 64)
    assert window["w_head_gate"].shape == (3, 64, 2)
    assert not [k for k in window if k.startswith("wi_")]
    assert window["router"].shape == (3, 64, 16)          # all 16 experts
    assert window["router_bias"].shape == (3, 16)
    assert window["we_gate"].shape == (3, 4, 64, 32)      # the 4 held
    assert window["ws_gate"].shape == (3, 64, 32)         # one shared
    assert "router" not in params["layers"]["indexed_dense"]
    assert axes["layers"]["window_routed"]["w_head_gate"] == (
        None, "embed", "heads")
    total = sum(a.size for a in jax.tree.leaves(params))
    assert total == cfg.num_params() == family.num_params(m)


# --------------------------------------------------------------------------
# program against reference
# --------------------------------------------------------------------------
def test_logits_agree_with_the_reference(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens, cfg)
    for b in range(tokens.shape[0]):
        np.testing.assert_allclose(
            got[b], reference.logits(params, tokens[b], m), **TIGHT)


def test_bf16_compute_is_told_from_float32(setup):
    m, cfg, params, tokens = setup
    want = reference.logits(params, tokens[0], m)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    off = float(jnp.abs(llama_forward(params, tokens[:1], bf16)[0]
                        - want).max())
    assert off > 100 * 5e-5


# each control is the check's on the chip (tools/dots3_probe.py); here, at
# 48 positions against a window of 17 and a choice of 24, each moves the
# logits by 1 to 3 where the program lies 4e-6 from the reference
@pytest.mark.parametrize("control", ["selection", "window", "gate",
                                     "rescale"])
def test_a_mechanism_left_out_fails_the_tolerance(setup, control):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens[:1], cfg)[0]
    assert float(jnp.abs(got - reference.logits(params, tokens[0], m)
                         ).max()) < 5e-5
    without = reference.logits(params, tokens[0], m, **{control: False})
    assert float(jnp.abs(got - without).max()) > 1000 * 5e-5, control


def test_the_indexers_bias_and_rotation_reach_the_choice(setup):
    """The LayerNorm's bias and the rotation of the index key move the
    choice, so a program that dropped either would show."""
    m, cfg, params, tokens = setup
    want = reference.logits(params, tokens[0], m)
    for leaf, change in (("wi_k_bias", lambda a: a * 0),
                         ("wi_k_norm", lambda a: a * 0 + 1)):
        layers = {kind: ({**lv, leaf: change(lv[leaf])} if leaf in lv else lv)
                  for kind, lv in params["layers"].items()}
        other = llama_forward(dict(params, layers=layers), tokens[:1], cfg)[0]
        assert float(jnp.abs(other - want).max()) > 100 * 5e-5, leaf
    moved = llama_forward(params, tokens[:1], cfg,
                          positions=jnp.arange(48)[None] * 3)[0]
    assert float(jnp.abs(moved - want).max()) > 100 * 5e-5


def test_the_served_step_and_remat_compute_the_same(setup):
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    for remat_policy in ("dots", "full", "mixed:2"):
        other = dataclasses.replace(cfg, remat=True,
                                    remat_policy=remat_policy)
        np.testing.assert_allclose(llama_forward(params, tokens, other), want,
                                   rtol=1e-5, atol=1e-5)
    last = jnp.array([47, 30], jnp.int32)
    live = jnp.arange(48)[None, :] <= last[:, None]
    ids, _, load = llama_next_token(params, tokens, last, cfg, live=live)
    assert ids.tolist() == [int(want[0, 47].argmax()),
                            int(want[1, 30].argmax())]
    # a share's load a routed layer, and what each indexed layer's choice
    # kept over the live queries: min(t + 1, 24) of a query's keys
    assert set(load) == {"fullest", "mean", "all", "index_kept"}
    assert load["all"].tolist() == [3.0 * (48 + 31)] * 4
    kept = sum(min(t + 1, 24) for n in (48, 31) for t in range(n))
    assert load["index_kept"].tolist() == [kept, kept]
    assert load["index_kept"].dtype == jnp.int32


def test_the_flash_path_is_the_reference_path(setup):
    """The two kernels under a window and a choice, interpreted, inside
    the whole forward at 128 positions: what the chip's path computes."""
    m, cfg, params, _ = setup
    tokens = jax.random.randint(jax.random.key(6), (2, 128), 0,
                                m["vocab_size"])
    flash = dataclasses.replace(cfg, attn_impl="flash")
    np.testing.assert_allclose(llama_forward(params, tokens, flash),
                               llama_forward(params, tokens, cfg),
                               rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# decode through the two operators' states
# --------------------------------------------------------------------------
@pytest.mark.parametrize("prefill, chunk", [(30, 1), (1, 1), (20, 7)])
def test_decode_through_the_states_is_the_full_forward(setup, prefill, chunk):
    """A prefill of ``prefill`` positions and then ``chunk`` at a time:
    48 positions pass the window of 17 and the choice of 24, so a window
    layer's state drops rows and an indexed layer's choice leaves keys
    out."""
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    state = init_decode_state(cfg, 2, 64)
    assert [s.shape for s in state] == [
        (2, 64, 16 + 8 + 16)] * 2 + [(2, 17, 32 + 8)] * 3
    got, at = [], 0
    for n in [prefill] + [chunk] * 48:
        n = min(n, 48 - at)
        if not n:
            break
        logits, state = llama_decode(params, tokens[:, at:at + n], cfg,
                                     state, jnp.int32(at))
        got.append(logits)
        at += n
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want,
                               rtol=2e-5, atol=2e-5)
    # a window layer keeps its last 17 rows, the newest last
    assert state[2].shape == (2, 17, 40)


def test_the_absorbed_form_is_the_decompressed_one(setup):
    _, cfg, params, _ = setup
    u = jax.random.normal(jax.random.key(11), (2, 40, 64))
    positions = jnp.broadcast_to(jnp.arange(40), (2, 40))
    for kind, operator in (("indexed_routed", "indexed"),
                           ("window_routed", "window")):
        lp = {k: v[0] for k, v in params["layers"][kind].items()}
        want, none = _latent_attention(cfg, u, lp, positions,
                                       operator=operator)
        assert none is None
        rows = init_decode_state(cfg, 2, 40)[1 if operator == "indexed"
                                             else 2]
        got, state = _latent_attention(cfg, u, lp, positions, rows,
                                       jnp.int32(0), operator=operator)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert state.shape == rows.shape


# --------------------------------------------------------------------------
# the choice of keys
# --------------------------------------------------------------------------
@pytest.mark.parametrize("k", [1, 7, 24, 200])
def test_the_choice_is_a_top_k_over_the_keys_seen(k):
    """``_chosen_keys`` against ``lax.top_k`` over the causal keys, where
    no two scores tie; ties at the k-th all stay."""
    scores = jax.random.normal(jax.random.key(k), (2, 96, 96))
    scores = scores.at[0, 5].set(-scores[0, 5] ** 2)       # all negative
    at = jnp.arange(96)
    seen = at[:, None] >= at[None, :]
    got = _chosen_keys(scores, seen, k)
    np.testing.assert_array_equal(got.sum(-1), jnp.broadcast_to(
        jnp.minimum(at + 1, k), (2, 96)))
    for b in range(2):
        want = reference.chosen_keys(
            jnp.where(seen, scores[b], -jnp.inf), k)
        np.testing.assert_array_equal(got[b], want)
    tied = jnp.zeros((1, 8, 8)).at[0, :, 0].set(1.0)
    kept = _chosen_keys(tied, jnp.ones((8, 8), bool), 3)
    assert bool(kept.all())       # seven scores tie at the third: all stay


# --------------------------------------------------------------------------
# the share
# --------------------------------------------------------------------------
def test_the_routed_layer_with_a_share_agrees_with_the_reference(setup):
    m, cfg, params, _ = setup
    layers = params["layers"]["window_routed"]
    x = jax.random.normal(jax.random.key(9), (2, 24, 64))
    for j in range(3):
        lp = {k: v[j] for k, v in layers.items()}
        h = _rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
        got, books = moe.expert_ffn(cfg, h, lp)
        for b in range(2):
            np.testing.assert_allclose(
                got[b], reference.moe_ffn(x[b], layers, j, m), **TIGHT)
        assert float(books["pairs"].sum()) == 2 * 24 * 3
        assert 0 < float(books["pairs_here"].sum()) < 2 * 24 * 3
    # the bias moves the choice: without it other experts are chosen
    lp = {k: v[0] for k, v in layers.items()}
    h = _rms_norm(x, lp["mlp_norm"], cfg.rms_eps)
    _, with_bias = moe.expert_ffn(cfg, h, lp)
    _, without = moe.expert_ffn(cfg, h, dict(
        lp, router_bias=lp["router_bias"] * 0))
    assert not np.array_equal(with_bias["pairs"], without["pairs"])


def test_the_shares_add_up_to_the_uncut_layer(setup):
    """The guide's one test of the share: the 4 shares' routed parts, the
    shared expert counted once, are the uncut reference's layer."""
    m, cfg, params, _ = setup
    whole_m = held(m, 0, 16)
    whole_cfg = family.build_config(whole_m)
    whole = randomised(init_llama(whole_cfg, jax.random.key(7)),
                       jax.random.key(8))["layers"]["window_routed"]
    x = jax.random.normal(jax.random.key(10), (1, 40, 64))
    want = reference.moe_ffn(x[0], whole, 1, whole_m)
    lp = {k: v[1] for k, v in whole.items()}
    h = _rms_norm(x, lp["mlp_norm"], whole_cfg.rms_eps)
    np.testing.assert_allclose(moe.expert_ffn(whole_cfg, h, lp)[0][0], want,
                               **TIGHT)
    shared = reference.shared_part(h[0], whole, 1)
    total, pairs = shared, 0.0
    for g in range(4):   # each chip of the deployment: its 4, all else
        chip_cfg = dataclasses.replace(whole_cfg, experts_held=(4 * g, 4))
        chip = dict(lp, **{k: lp[k][4 * g:4 * g + 4]
                           for k in moe.EXPERT_STACKS})
        y, books = moe.expert_ffn(chip_cfg, h, chip)
        total = total + (y[0] - shared)
        pairs += float(books["pairs_here"].sum())
        stack = {k: (v[:, 4 * g:4 * g + 4] if k in moe.EXPERT_STACKS else v)
                 for k, v in whole.items()}
        np.testing.assert_allclose(
            y[0], reference.moe_ffn(x[0], stack, 1, held(m, 4 * g)), **TIGHT)
    np.testing.assert_allclose(total, want, **TIGHT)
    assert pairs == 40 * 3        # every pair lands on exactly one chip


# --------------------------------------------------------------------------
# the served class and its counters
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    m = tiny_model()
    gen = family.Served(**family.served_kwargs(m, dict(
        lora_rank=4, max_batch_size=2, allowed_batch_sizes=[2],
        max_new_tokens=4, seq_bucket=64), 12))
    yield m, gen
    gen.engine.shutdown()


def test_the_served_class_counts_what_the_choice_and_the_window_kept(served):
    m, gen = served
    assert float(jnp.abs(gen._params["layers"]["window_routed"][
        "router_bias"]).max()) > 0          # drawn from the seed
    prompt = list(range(3, 43))
    tokens = list(gen({"prompt": prompt, "max_new": 3}))
    assert len(tokens) == 3
    stats = gen.engine_stats()
    assert set(gen.STEP_COUNTERS) <= set(stats)
    rows = (40, 41, 42)           # each step re-runs the prefix
    seen = sum(n * (n + 1) // 2 for n in rows)
    assert stats["index_keys_seen"] == 2 * seen
    # (under seed 11 one query's 24th score ties with its 25th at 0.0, all
    # 8 heads' products negative for both keys, and both stay: 3 more)
    assert stats["index_keys_kept"] == 2 * sum(
        min(t + 1, 24) for n in rows for t in range(n))
    assert stats["window_keys_kept"] == 3 * sum(
        min(t + 1, 17) for n in rows for t in range(n))
    assert stats["layer_kinds"] == {"indexed_dense": 1, "indexed_routed": 1,
                                    "window_routed": 3}
    # the tokens are the reference's own first choices
    rows_ = reference.logits(gen._params, jnp.asarray(prompt + tokens[:-1]),
                             m)
    assert tokens == np.asarray(rows_[39:42].argmax(-1)).tolist()


def test_the_routers_bias_is_dealt_alike_to_the_shares():
    """Every layer holds the same 256 values in an order of its own, and
    each of the 8 shares gets one value of every run of 8 neighbours."""
    leaf = {"layers": {"k": {"router_bias": jnp.zeros((3, 256)),
                             "router": jnp.ones((3, 4, 256))}}}
    out = family.with_expert_bias(leaf, 0.02, 5, 8)["layers"]["k"]
    np.testing.assert_array_equal(out["router"], 1.0)
    bias = np.asarray(out["router_bias"])
    quantiles = np.sort(bias[0])
    assert quantiles[0] == pytest.approx(-0.02 * 2.886, rel=1e-3)
    np.testing.assert_allclose(quantiles, -quantiles[::-1], atol=1e-7)
    for layer in bias:
        np.testing.assert_array_equal(np.sort(layer), quantiles)
        shares = np.sort(layer.reshape(8, 32), axis=1)
        # a share's n-th smallest is one of the n-th run of 8 neighbours
        for n in range(32):
            assert set(shares[:, n]) == set(quantiles[8 * n:8 * n + 8])
    assert not np.array_equal(bias[0], bias[1])
    other = family.with_expert_bias(leaf, 0.02, 6, 8)["layers"]["k"]
    assert not np.array_equal(other["router_bias"][0], bias[0])


# --------------------------------------------------------------------------
# the family module
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(sliding_window=4096), r"does not understand \['sliding_window'\]"),
    (dict(n_group=8), r"does not understand \['n_group'\]"),
    (dict(topk_method="group_limited_greedy"), "topk_method 'group_limited"),
    (dict(scoring_func="softmax"), "scoring_func 'softmax'"),
    (dict(attention_gate_type="elementwise"), "attention_gate_type 'elemen"),
    (dict(swa_attention_gate_type=None), "swa_attention_gate_type None"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(attention_bias=True), "attention_bias True"),
    (dict(swa_num_key_value_heads=8), "swa_num_key_value_heads"),
    (dict(q_lora_rank=None), "q_lora_rank None"),
    (dict(layer_types=[FULL] * 4), "layer_types names 4 layers"),
    (dict(layer_types=[FULL, "conv", SLIDING, SLIDING, SLIDING]),
     r"layer_types \['conv'\]"),
    (dict(index_topk=0), "index_topk"),
    (dict(index_head_dim=32), "index_head_dim cannot be narrower"),
    (dict(n_routed_experts=300), "expert_share: 300 experts"),
    (dict(n_routed_experts=48), "no whole share"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    m = dict(loader.load_config(CONFIG), **change)
    with pytest.raises(ValueError, match=match):
        family.check(m)


def test_a_file_that_lacks_a_key_is_refused():
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "swa_kv_lora_rank"}
    with pytest.raises(ValueError, match=r"lacks \['swa_kv_lora_rank'\]"):
        family.check(lacking)


def test_a_checkout_without_the_fields_is_refused_at_once(monkeypatch):
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert family._config_fields() == fields
    assert (set(family.MODEL_KEYS.values()) | set(family.BUILT)
            | set(family.MODELING)) <= fields
    monkeypatch.setattr(family, "_config_fields", lambda: fields - {
        "sliding_window", "index_topk", "head_gate"})
    with pytest.raises(ValueError, match=r"LlamaConfig has no \['head_gate'"
                                         r", 'index_topk', 'sliding_window"):
        family.check(loader.load_config(CONFIG))
    monkeypatch.undo()
    monkeypatch.setattr(family.LlamaGenerator, "STEP_COUNTERS",
                        ("host_bytes", "expert_pairs_all"))
    with pytest.raises(ValueError, match="counts no keys that an indexer"):
        family.check(loader.load_config(CONFIG))


def test_the_parent_fails_on_the_cell_within_seconds(repo_root, tmp_path):
    """This PR's benchmark files over a program that lacks its fields:
    ``run.py`` exits at once and names them (the driver tries each new cell
    on the parent first, and a parent that hangs there refuses the PR)."""
    import shutil
    import time

    root = tmp_path / "parent"
    for sub in ("benchmark", "ray_tpu"):
        shutil.copytree(os.path.join(repo_root, sub), root / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(repo_root, "BENCHMARK.json"), root)
    llama = root / "ray_tpu" / "models" / "llama.py"
    text = llama.read_text()
    for field in ("sliding_window", "index_topk", "head_gate",
                  "latent_rescale"):
        text = re.sub(rf"\n    {field}: [^\n]*", "", text)
    llama.write_text(text)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    t = time.time()
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "5", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=60)
    assert time.time() - t < 30
    assert proc.returncode not in (0, 3)
    assert ("LlamaConfig has no ['head_gate', 'index_topk', "
            "'latent_rescale', 'sliding_window']") in proc.stderr


def test_the_configuration_is_the_published_one_cut_to_a_chips_share():
    m = loader.load_config(CONFIG)
    assert m["source"] == ("https://huggingface.co/dots-studio/"
                           "dots3-note-prev/blob/main/config.json")
    assert m["reduced"] == ["num_hidden_layers", "n_routed_experts",
                            "vocab_size", "layer_types"]
    for key, value in PUBLISHED.items():
        if key in m["reduced"]:
            assert m["changed_from_source"][key]["source"] == value
            assert m["changed_from_source"][key]["here"] == m[key]
        else:
            assert m[key] == value, key
    assert set(m["changed_from_source"]) == set(m["reduced"])
    # the floors: the dense layer and one whole period after it (four
    # layers), 8 experts, an eighth of the vocabulary
    assert m["layer_types"] == PUBLISHED["layer_types"][:5] == [
        FULL, FULL, SLIDING, SLIDING, SLIDING]
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] == 4
    assert m["n_routed_experts"] == 256 // 8 == 32
    assert m["expert_share"] == {"first": 0, "of": 256}
    assert m["vocab_size"] * 8 == 152064
    assert "8 v5e chips" in m["deployment"]
    assumed = " ".join(m["assumed"])
    for said in ("apply_mla_qkv_lora_rescale", "head-wise gate",
                 "sliding_window_size 513 counts the query's own",
                 "lightning indexer", "LayerNorm", "noaux_tc",
                 "expert_bias_init_std", "vision and audio towers"):
        assert said in assumed, said
    with open(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}[CONFIG]
    assert listed["reduced"] == m["reduced"]
    assert listed["source"] == m["source"]
    cfg = family.build_config(m)
    assert cfg.latent_widths("indexed") == (128, 1024, 512, 128, 64, 128,
                                            8e7, 0, 2048)
    assert cfg.latent_widths("window") == (64, 1024, 1024, 192, 64, 128,
                                           5e4, 513, 0)
    assert (cfg.index_heads, cfg.index_head_dim) == (64, 128)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token) == (
        256, (0, 32), 8)
    assert cfg.attn_impl == "flash" and cfg.dtype == jnp.bfloat16
    out = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(out)) == family.num_params(m)
    assert {a.dtype for a in jax.tree.leaves(out)} == {jnp.dtype("bfloat16")}


def test_counts_by_hand():
    m = loader.load_config(CONFIG)
    indexer = 1024 * 64 * 128 + 5120 * 128 + 2 * 128 + 5120 * 64
    full = (5120 * 1024 + 1024 + 1024 * 128 * 192 + 5120 * 576 + 512
            + 512 * 128 * 256 + 128 * 128 * 5120 + 5120 * 128 + indexer)
    sliding = (5120 * 1024 + 1024 + 1024 * 64 * 256 + 5120 * 1088 + 1024
               + 1024 * 64 * 320 + 64 * 128 * 5120 + 5120 * 64)
    expert = 3 * 5120 * 1536
    assert (full, sliding, expert, indexer) == (
        144_049_920, 90_834_944, 23_592_960, 9_371_904)
    routed = 32 * expert + expert + 5120 * 256 + 256
    dense = 3 * 5120 * 13824
    total = (2 * full + 3 * sliding + 5 * 2 * 5120 + dense + 4 * routed
             + 2 * 19008 * 5120 + 5120)
    assert total == 4_087_154_176 == family.num_params(m)
    assert family.build_config(m).num_params() == total
    assert round(total * 2 / 1e9, 2) == 8.17
    # the whole language model, by the same functions: 279.6B of the
    # published 288B, whose rest is the towers and the MTP module
    whole = dict(m, num_hidden_layers=46, n_routed_experts=256,
                 vocab_size=152064, layer_types=PUBLISHED["layer_types"])
    assert round(family.num_params(whole) / 1e9, 1) == 279.6
    # a position's pairs here: 8 x 32/256 of three 5120 x 1536 matmuls
    assert family.expert_ffn_flops(m, 8) == \
        4 * 8 * 8 * 0.125 * 3 * 2 * 5120 * 1536
    assert family.expert_ffn_bytes(m) == 4 * 32 * expert * 2 == 6_039_797_760
    need = lambda n: (family.expert_ffn_flops(m, n) / 197e12,  # noqa: E731
                      family.expert_ffn_bytes(m) / 819e9)
    assert need(7697)[0] < need(7697)[1] < need(7698)[0]
    # what a step keeps, from its record: 4 whole rows of 5120
    step = {"rows": 4, "positions_live": 4 * 5120,
            "attention_keys": 4 * 5120,
            "attention_pairs": 4 * 5120 * 5121 // 2}
    assert family.kept_pairs(step, 2048) == 4 * sum(
        min(t, 2048) for t in range(1, 5121))
    assert family.kept_pairs(step, 513) == 4 * sum(
        min(t, 513) for t in range(1, 5121))
    assert family.kept_pairs(step, 2048) / step["attention_pairs"] == \
        pytest.approx(0.64, abs=0.005)
    # a row shorter than the most a query keeps: the count errs low
    short = {"rows": 1, "positions_live": 1000}
    assert 0 <= family.kept_pairs(short, 2048) < 1000 * 1001 / 2
    assert family.kept_pairs({"rows": 4, "positions_live": 4}, 513) == 0
    assert family.sparse_flash_flops(m, step) == \
        2 * 128 * 2 * (192 + 128) * family.kept_pairs(step, 2048)
    assert family.window_flash_flops(m, step) == \
        3 * 64 * 2 * (256 + 128) * family.kept_pairs(step, 513)
    assert family.sparse_flash_bytes(m, step) == \
        2 * 2 * 4 * 5120 * (128 * (192 + 128 + 128 + 128) + 64)
    assert family.window_flash_bytes(m, step) == \
        3 * 2 * 4 * 5120 * (64 * (256 + 128 + 192 + 128) + 64)
    assert family.index_scores_flops(m, step) == \
        2 * step["attention_pairs"] * 2 * 64 * 128
    assert family.index_scores_bytes(m, step) == 2 * (
        4 * 5120 * (2 * 64 * 128 + 4 * 64) + 4 * 5120 * 2 * 128
        + step["attention_pairs"] * 4)
    # at whole rows of 5120 the FLOPs bind in all three (the window's by
    # a tenth); a step of many short contexts is bound by its bytes
    for need in ("window_flash", "sparse_flash", "index_scores"):
        flops = getattr(family, need + "_flops")(m, step) / 197e12
        bytes_ = getattr(family, need + "_bytes")(m, step) / 819e9
        assert flops > bytes_, need
    assert family.window_flash_flops(m, step) / 197e12 < 1.2 * (
        family.window_flash_bytes(m, step) / 819e9)


def test_the_family_module_imports_no_jax(repo_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.harness import loader\n"
        "from benchmark.families import dots3_note\n"
        "cell = loader.load_cell('serve_dots3_longdoc')\n"
        "assert dots3_note.num_params(cell['model']) > 4e9\n"
        "for m in loader.metrics_for_cell(cell): loader.load_reader(m)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % repo_root)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------------
# the cell's files and its metrics
# --------------------------------------------------------------------------
OWN = {"dots3_sparse_flash_fwd_ms.serve",
       "dots3_sparse_flash_fwd_roofline_pct.serve",
       "dots3_window_flash_fwd_ms.serve",
       "dots3_window_flash_fwd_roofline_pct.serve",
       "dots3_indexer_ms.serve", "dots3_indexer_roofline_pct.serve",
       "dots3_index_keys_kept_pct.serve",
       "dots3_expert_ffn_roofline_pct.serve",
       "dots3_expert_matmul_sort_ms.serve",
       "dots3_expert_load_imbalance.serve",
       "dots3_routed_pairs_here_pct.serve"}


def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell(CELL)
    dsv2 = loader.load_cell("serve_dsv2_docqa")
    # the engine is the other serving cells' but for the batch, the bucket
    # and the longest answer
    differ = ("max_batch_size", "allowed_batch_sizes", "max_new_tokens",
              "seq_bucket")
    assert {k: v for k, v in cell["engine"].items() if k not in differ} == {
        k: v for k, v in dsv2["engine"].items() if k not in differ}
    assert cell["engine"]["max_batch_size"] == 4
    assert cell["engine"]["allowed_batch_sizes"] == [4]
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 3200, "sigma": 0.35, "min": 2304,
                                 "max": 4864}
    assert mix["output_len"] == {"median": 8, "sigma": 0.5, "min": 4,
                                 "max": 16}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 16
    assert cell["engine"]["seq_bucket"] == 512
    # six buckets, and every prompt longer than the indexer's 2048 keys
    assert serve_driver.seq_buckets(cell) == list(range(2560, 5121, 512))
    assert mix["prompt_len"]["min"] > cell["model"]["index_topk"]
    # the plain mean: two tokens in five are off the reference's best
    # here, and the cap would take from the controls' readings alone
    assert list(cell["check"]["limits"]) == ["gap_mean"]
    assert all(0 < limit < 1 for limit in cell["check"]["limits"].values())
    assert mix["rate_per_s"] / mix["knee"]["rate_per_s"] == \
        pytest.approx(0.6, abs=0.02)
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    assert OWN <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    assert not OWN & {m["name"] for m in loader.metrics_for_cell(dsv2)}
    listed = loader.manifest_cell(manifest, CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        CONFIG, "long_doc_short_answers", 1)
    for metric in manifest["per_layer"]:
        if metric["name"] in OWN:
            assert metric["moves"] == "serve_gap_p95_ms"
            assert metric["workloads"] == [CELL]
    assert len(manifest["configs"]) == len(manifest["workloads"]) == 6
    assert not [w for w in manifest["workloads"] if w["chips"] != 1]


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


def test_the_readers_tell_the_three_kernels_apart():
    metrics = {m["name"]: m for m in loader.load_metric_files()}
    m = loader.load_config(CONFIG)
    ops = [("tpu_custom_call:ragged-dot-none-pallas.16", 0.200, 16),
           ("tpu_custom_call:flash_fwd_selected.10", 0.800, 8),
           ("tpu_custom_call:flash_fwd_window.11", 0.120, 12),
           ("tpu_custom_call:index_scores.12", 0.300, 8),
           # another model's forward is none of the three
           ("tpu_custom_call:flash_fwd_shared_rope.9", 0.050, 4),
           ("sort.3", 0.010, 84), ("fusion.120", 0.300, 48),
           ("while.4", 0.090, 8)]
    records = [whole_rows(4, 3000)] * 3 + [whole_rows(3, 5120)]
    view = view_of(ops, records, {
        "expert_pairs_fullest": 30.0, "expert_pairs_mean": 20.0,
        "expert_pairs_here": 400.0, "expert_pairs_all": 3000.0,
        "index_keys_kept": 700, "index_keys_seen": 1000})

    def value(name):
        return loader.load_reader(metrics[name])(view, metrics[name])

    assert value("dots3_sparse_flash_fwd_ms.serve") == pytest.approx(200.0)
    assert value("dots3_window_flash_fwd_ms.serve") == pytest.approx(30.0)
    assert value("dots3_indexer_ms.serve") == pytest.approx(75.0)
    assert value("dots3_expert_matmul_sort_ms.serve") == pytest.approx(52.5)
    for name, need, measured in (
            ("dots3_sparse_flash_fwd_roofline_pct.serve", "sparse_flash",
             0.800),
            ("dots3_window_flash_fwd_roofline_pct.serve", "window_flash",
             0.120),
            ("dots3_indexer_roofline_pct.serve", "index_scores", 0.300)):
        want = sum(max(
            getattr(family, need + "_flops")(m, r) / 197e12,
            getattr(family, need + "_bytes")(m, r) / 819e9) for r in records)
        assert value(name) == pytest.approx(100.0 * want / measured), name
        # no such kernel in the trace, or no traced step: None, no raise
        metric = metrics[name]
        read = loader.load_reader(metric)
        assert read(view_of(ops[:1] + ops[4:], records, {}), metric) is None
        assert read(view_of(ops, [], {}), metric) is None
    assert value("dots3_index_keys_kept_pct.serve") == pytest.approx(70.0)
    kept = metrics["dots3_index_keys_kept_pct.serve"]
    assert loader.load_reader(kept)(view_of(ops, records, {}), kept) is None
    assert value("dots3_expert_load_imbalance.serve") == 1.5
    assert value("dots3_routed_pairs_here_pct.serve") == pytest.approx(
        100 * 400 / 3000)
    need = (3 * family.expert_ffn_flops(m, 12000)
            + family.expert_ffn_flops(m, 15360)) / 197e12
    assert value("dots3_expert_ffn_roofline_pct.serve") == pytest.approx(
        100.0 * need / 0.200)
    # a family that reckons no such need gives nothing
    dsv2 = dict(view, cell=loader.load_cell("serve_dsv2_docqa"))
    sparse = metrics["dots3_sparse_flash_fwd_roofline_pct.serve"]
    assert loader.load_reader(sparse)(dsv2, sparse) is None
    # and the other cells' flash metric does not read these kernels
    mla = metrics["dsv2_mla_flash_fwd_ms.serve"]
    assert loader.load_reader(mla)(view, mla) == pytest.approx(12.5)


def test_the_cells_step_holds_the_kernels_and_no_score_tensor():
    from tests.benchmark.test_deepseek_v2 import program_text

    text = program_text(CELL, "step2560")
    assert text.count("name=flash_attention_shared_rope") == 3  # the runs
    # the indexer's kernel: a block of queries holds all 64 heads' rows
    assert "Ref{bf16[1,64,256,128]}" in text
    assert "ragged_dot" not in text
    # reference attention's scores would be [8, 128, 2560, 2560], the
    # indexer's products [8, 64, 2560, 2560]
    assert "8,128,2560,2560" not in text
    assert "8,64,2560,2560" not in text
    # the choice reaches the kernel a byte a (query, key) pair, no head in it
    assert "i8[8,2560,2560]" in text
    # nothing sorts the index scores
    assert "f32[8,2560,2560]" in text
    assert not re.search(r"sort\[[^\]]*\] [a-z]+:f32\[8,2560,2560\]", text)


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
    assert line["metrics"]["dots3_expert_load_imbalance.serve"]["value"] >= 1
    # prompts of 16 to 100 against a choice of 24 keys: some are left out
    assert 30 < line["metrics"]["dots3_index_keys_kept_pct.serve"][
        "value"] < 100
