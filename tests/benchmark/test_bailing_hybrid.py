"""Ling-3.0-flash through the one block of ``models/llama.py`` against the
plain float32 reference, tiny, on the CPU: Kimi delta attention whose rule
runs in chunks SHORTER than the lengths tested (64 against 150 to 200
positions, lengths that are not whole chunks among them, so that the state
crosses edges and a ragged last chunk bites) beside latent attention with
full-rank queries and a head-wise gate, a leading dense layer, routed
experts of which a share is held under the two-best group router; the
decode through the rule's state and the latent rows; the four shares of a
routed layer against the uncut layer; the family module's checks and
counts; the cell's files and the readers it brings.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerances are 2e-5 of logits of order 1: each control (the
delta term left out, ``beta`` at 1, the gate's bound at -1, the output gate
before the norm, the state dropped at the chunks' edges, the head-wise gate
left out, a group scored by its best expert alone) moves the logits by
hundreds to ten thousands of times that, as the test beside the logits'
shows, and the reason each is there is written beside it.
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

from benchmark.families import bailing_hybrid as family
from benchmark.harness import lastline, loader, peaks
from benchmark.reference import bailing_hybrid as reference
from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, init_decode_state, init_llama, llama_decode, llama_forward,
    llama_logical_axes, llama_next_token)

CELL = "serve_ling3_repoctx"
CONFIG = "ling-3.0-flash-serve-ep4-l8"
TIGHT = dict(rtol=2e-5, atol=2e-5)


def published():
    """config.json of inclusionAI/Ling-3.0-flash, as the catalog beside the
    model-configs guide reads it (row Ling-3.0-flash, ``config``)."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Ling-3.0-flash":
                return row
    pytest.fail("the catalog has no row Ling-3.0-flash")


def tiny_model(**over):
    """The rehearsal's sizes (4 layers: KDA dense, KDA routed, MLA routed,
    KDA routed; 2 heads of 128, chunks of 64; 8 of 16 experts held, 4 a
    token from 2 of 4 groups), computed in float32 by the reference path."""
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, program={"attn_impl": "reference", "dtype": "float32",
                         "param_dtype": "float32"})
    m.update(over)
    return m


def randomised(params, key):
    """Norm gains off 1, so that a norm left out or misplaced shows; a
    router's bias of the size that moves the choice at some positions; and
    the decay's memories SHORTER than the initialiser's (``dt_bias``
    halved: some 2 to 14 positions where it gives 10 to 1000), so that at
    150 to 200 positions both what a state forgets and what it carries
    across a chunk's edge move the logits."""
    def moved(path, a):
        name = path[-1].key
        k = jax.random.fold_in(key, sum(map(ord, str(path))))
        if name.endswith("_norm"):
            return 1.0 + 0.3 * jax.random.normal(k, a.shape)
        if name == "router_bias":
            return 0.05 * jax.random.normal(k, a.shape)
        if name == "kda_dt_bias":
            return a * 0.5
        return a
    return jax.tree_util.tree_map_with_path(moved, params)


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = family.build_config(m)
    params = randomised(init_llama(cfg, jax.random.key(13)),
                        jax.random.key(5))
    tokens = jax.random.randint(jax.random.key(4), (2, 200), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


# --------------------------------------------------------------------------
# the configuration, the tree and its count
# --------------------------------------------------------------------------
def test_the_configuration_the_family_builds(setup):
    m, cfg, _, _ = setup
    assert cfg.layer_kinds() == ("kda_dense", "kda_routed", "latent_routed",
                                 "kda_routed")
    assert cfg.layer_runs() == (("kda_dense", 0, 1), ("kda_routed", 0, 1),
                                ("latent_routed", 0, 1), ("kda_routed", 1, 1))
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv_kernel,
            cfg.kda_chunk, cfg.kda_lower_bound) == (2, 128, 4, 64, -5.0)
    assert cfg.kda_widths() == (256, 1280)
    w = cfg.latent_widths("latent")
    assert (w.heads, w.q_rank, w.kv_rank, w.nope, w.rope, w.v) == (
        2, 0, 32, 128, 64, 128)
    assert cfg.head_gate and cfg.router_group_score == "top2"
    assert (cfg.router_scores, cfg.router_bias, cfg.router_norm_eps) == (
        "sigmoid", True, 1e-20)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.router_groups, cfg.router_topk_groups) == (16, (0, 8), 4, 4,
                                                           2)
    assert cfg.routed_scaling_factor == 2.5 and cfg.num_shared_experts == 1
    # the published model's cut: 2 dense KDA layers, then KDA x 3, MLA,
    # KDA x 2, in five runs
    whole = family.build_config(loader.load_config(CONFIG))
    assert whole.layer_kinds() == (
        "kda_dense", "kda_dense", "kda_routed", "kda_routed", "kda_routed",
        "latent_routed", "kda_routed", "kda_routed")
    assert whole.kind_counts() == {"kda_dense": 2, "kda_routed": 5,
                                   "latent_routed": 1}
    assert whole.kda_widths() == (4096, 20480)
    assert (whole.num_experts, whole.experts_held) == (512, (0, 128))
    assert (whole.rope_theta, whole.rms_eps) == (6e6, 1e-6)
    # the whole model's pattern: MLA at 5, 11, ..., 41
    full = dict(loader.load_config(CONFIG), num_hidden_layers=42,
                expert_swiglu_limit_list=[0] * 42,
                share_expert_swiglu_limit_list=[0] * 42)
    types = family.layer_types(full)
    assert [l for l, t in enumerate(types) if t == "latent_attention"] == \
        [5, 11, 17, 23, 29, 35, 41]
    assert types.count("kda") == 35
    # the defaults leave every other model as it was
    plain = LlamaConfig()
    assert plain.kda_heads == 0 and plain.router_group_score == "max"


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert set(params["layers"]) == {"kda_dense", "kda_routed",
                                     "latent_routed"}
    kda = params["layers"]["kda_routed"]
    assert kda["kda_in"].shape == (2, 64, 1280)
    assert kda["kda_beta"].shape == (2, 64, 2)
    assert kda["kda_conv_w"].shape == (2, 768, 4)
    assert kda["kda_a_log"].shape == (2, 2)
    assert kda["kda_dt_bias"].shape == (2, 256)
    assert kda["kda_norm"].shape == (2, 128)
    assert kda["kda_out"].shape == (2, 256, 64)
    assert kda["router"].shape == (2, 64, 16)
    assert kda["we_gate"].shape == (2, 8, 64, 32)            # 8 of 16 held
    latent = params["layers"]["latent_routed"]
    assert latent["wq"].shape == (1, 64, 2, 192)             # ONE matrix
    assert not {"wq_a", "q_a_norm", "wq_b"} & set(latent)
    assert latent["w_head_gate"].shape == (1, 64, 2)
    assert latent["wkv_a"].shape == (1, 64, 32 + 64)
    assert "lm_head" in params                               # untied
    count = sum(x.size for x in jax.tree.leaves(params))
    assert count == cfg.num_params() == family.num_params(m)
    # the gate's leaves give memories of 10 to 1000 positions at a = 0
    fresh = init_llama(cfg, jax.random.key(2))["layers"]["kda_routed"]
    g = -5.0 * jax.nn.sigmoid(
        jnp.exp(fresh["kda_a_log"])[..., None]
        * fresh["kda_dt_bias"].reshape(2, 2, 128))
    assert 10.0 <= float((-1 / g).min()) and float((-1 / g).max()) <= 1000.5
    assert float((-1 / g).min()) < 20 and float((-1 / g).max()) > 500


# --------------------------------------------------------------------------
# program against reference
# --------------------------------------------------------------------------
def test_logits_agree_with_the_reference(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens, cfg)
    for b in range(tokens.shape[0]):
        want = reference.logits(params, tokens[b], m)
        np.testing.assert_allclose(got[b], want, **TIGHT)
        np.testing.assert_allclose(
            got[b, -1], reference.last_logits(params, tokens[b], m), **TIGHT)


def test_the_kernels_path_is_the_reference_path(setup):
    """Both kernels, interpreted, inside the whole forward at 256
    positions, four chunks of 64: what the chip's path computes. The rows
    are padded on the right to 200 and 77 of their own tokens (no whole
    chunks), as a serving step pads them, the routed experts multiply the
    rows' own positions alone, and the served step's token is the
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
    assert set(load) == {"fullest", "mean", "all"}
    assert load["all"].tolist() == [277 * 4.0] * 3           # 3 routed layers
    for b, n in enumerate(lengths):
        want = reference.logits(params, tokens[b, :n], m)
        np.testing.assert_allclose(got[b, :n], want, **TIGHT)
        assert int(ids[b]) == int(want[n - 1].argmax())


def test_bf16_compute_is_told_from_float32(setup):
    m, cfg, params, tokens = setup
    want = reference.logits(params, tokens[0], m)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    off = float(jnp.abs(llama_forward(params, tokens[:1], bf16)[0]
                        - want).max())
    assert off > 100 * 2e-5


# Each control is one of the check's on the chip (tools/ling3_probe.py) or a
# fault this family's equations invite; here, at 200 positions against
# chunks of 64, each moves the logits by 0.01 to 1 where the program lies
# 1e-6 from the reference.
@pytest.mark.parametrize("control, why", [
    (dict(delta=False),
     "without the term that takes off what the state already answers, the "
     "rule is plain gated linear attention, which is a simpler kernel"),
    (dict(beta_one=True),
     "the writing strength is one more projection, easy to leave at 1"),
    (dict(lower_bound=-1.0),
     "the bound is a key of the configuration; the public kernels' other "
     "gate has none"),
    (dict(gate_first=True),
     "Mamba-2's gated norm as granite has it gates BEFORE the norm; this "
     "operator's gate comes after it"),
    (dict(drop_state_every=64),
     "a kernel that loses its state between grid steps computes every "
     "chunk from zeros: right up to the first edge, wrong after it"),
    (dict(head_gate=False),
     "DeepSeek-V2's MLA has no gate, and the program's plain latent "
     "operator had none before this family"),
    (dict(group_score="max"),
     "DeepSeek-V2's group-limited choice scores a group by its best "
     "expert, the program's default"),
])
def test_a_fault_fails_the_tolerance(setup, control, why):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens[:1], cfg)[0]
    assert float(jnp.abs(got - reference.logits(params, tokens[0], m)
                         ).max()) < 2e-5
    faulty = reference.logits(params, tokens[0], m, **control)
    assert float(jnp.abs(got - faulty).max()) > 100 * 2e-5, (control, why)
    if "drop_state_every" in control:  # sound up to the first edge
        np.testing.assert_allclose(got[:64], faulty[:64], **TIGHT)


def test_the_served_step_and_remat_compute_the_same(setup):
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    for remat_policy in ("dots", "full"):
        other = dataclasses.replace(cfg, remat=True,
                                    remat_policy=remat_policy)
        np.testing.assert_allclose(llama_forward(params, tokens, other), want,
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# decode through the rule's state and the latent rows
# --------------------------------------------------------------------------
@pytest.mark.parametrize("impl, prefill, chunk", [
    ("reference", 150, 1), ("reference", 90, 37),
    # the prompt's pass through the kernel: 150 positions are two chunks
    # and a ragged third, whose padding must leave the FINAL state alone
    ("flash", 150, 1)])
def test_decode_through_the_state_is_the_full_forward(setup, impl, prefill,
                                                      chunk):
    m, cfg, params, _ = setup
    total = prefill + (2 * chunk if chunk > 1 else 6)
    tokens = jax.random.randint(jax.random.key(8), (2, total), 0,
                                m["vocab_size"])
    want = jnp.stack([reference.logits(params, tokens[b], m)
                      for b in range(2)])
    cfg = dataclasses.replace(cfg, attn_impl=impl)
    state = init_decode_state(cfg, 2, total)
    taps, rule = state[0]
    assert taps.shape == (2, 3, 768)                 # the taps' last rows
    assert rule.shape == (2, 2, 128, 128) and rule.dtype == jnp.float32
    assert state[2].shape == (2, total, 32 + 64)     # the latent rows
    # (one program a shape: op by op a decode of 4 layers takes seconds)
    decode = jax.jit(lambda p, t, st, at: llama_decode(p, t, cfg, st, at))
    got, at = [], 0
    for n in [prefill] + [chunk] * ((total - prefill) // chunk):
        logits, state = decode(params, tokens[:, at:at + n], state,
                               jnp.int32(at))
        got.append(logits)
        at += n
    assert at == total
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, **TIGHT)
    assert state[0][1].dtype == jnp.float32


# --------------------------------------------------------------------------
# the share of the experts, and the router
# --------------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_layer(setup):
    """The four shares of a routed layer (a quarter of the 16 experts
    each), the shared expert counted ONCE, add up to what the uncut
    reference gives for the whole layer: the program's ``expert_ffn`` of
    each share against the reference over all 16."""
    m, _, _, _ = setup
    whole_m = dict(m, num_experts=16, expert_share={"first": 0, "of": 16})
    whole = family.build_config(whole_m)
    params = randomised(init_llama(whole, jax.random.key(21)),
                        jax.random.key(3))
    layers = params["layers"]["kda_routed"]
    x = jax.random.normal(jax.random.key(9), (120, 64))
    want = reference.moe_ffn(x, layers, 1, whole_m)
    r = reference.rms_norm(x, layers["mlp_norm"][1], 1e-6)
    shared = reference.shared_part(r, layers, 1)
    total = shared
    for first in (0, 4, 8, 12):
        cfg = dataclasses.replace(whole, experts_held=(first, 4))
        lp = {k: v[1] for k, v in layers.items()}
        lp.update({k: lp[k][first:first + 4] for k in moe.EXPERT_STACKS})
        y, books = moe.expert_ffn(cfg, r[None], lp)
        assert books["pairs"].shape == (16,)
        assert books["pairs_here"].shape == (4,)
        assert float(books["pairs"].sum()) == 120 * 4
        part_m = dict(m, num_experts=4,
                      expert_share={"first": first, "of": 16})
        held = {k: (v[:, first:first + 4] if k in moe.EXPERT_STACKS else v)
                for k, v in layers.items()}
        np.testing.assert_allclose(
            y[0], reference.routed_part(r, held, 1, part_m) + shared, **TIGHT)
        total = total + (y[0] - shared)
    np.testing.assert_allclose(total, want, **TIGHT)


def test_the_two_best_group_score_by_hand():
    """8 experts in 4 groups of 2, 2 groups kept, 2 experts a token. Group
    0 holds the single best expert (0.9) beside a poor one (0.1): sum 1.0.
    Groups 1 and 2 hold two good ones each (0.6 + 0.55, 0.5 + 0.52). By
    the best expert alone groups 0 and 1 stay and experts 0 and 2 are
    chosen; by the sum of the two best groups 1 and 2 stay and experts 2
    and 3 are chosen. The bias moves the choice and not the weights."""
    scores = jnp.array([[0.9, 0.1, 0.6, 0.55, 0.5, 0.52, 0.3, 0.2]])
    cfg = LlamaConfig(num_experts=8, experts_per_token=2, router_groups=4,
                      router_topk_groups=2)
    by_best = moe._best_groups(cfg, scores)
    np.testing.assert_array_equal(
        by_best, jnp.array([[0.9, 0.1, 0.6, 0.55, 0, 0, 0, 0]]))
    top2 = dataclasses.replace(cfg, router_group_score="top2")
    by_two = moe._best_groups(top2, scores)
    np.testing.assert_array_equal(
        by_two, jnp.array([[0, 0, 0.6, 0.55, 0.5, 0.52, 0, 0]]))
    # an expert that ties with its group's best is its second
    tie = jnp.array([[0.5, 0.5, 0.9, 0.0, 0.3, 0.3, 0.1, 0.1]])
    np.testing.assert_array_equal(
        moe._best_groups(top2, tie),
        jnp.array([[0.5, 0.5, 0.9, 0.0, 0, 0, 0, 0]]))
    with pytest.raises(ValueError, match="router_group_score 'mean'"):
        moe._best_groups(dataclasses.replace(
            cfg, router_group_score="mean"), scores)
    # the reference chooses alike, and the whole router: logits whose
    # sigmoids are the scores above, a bias that lifts expert 7's group
    logit = jnp.log(scores / (1 - scores))
    router = jnp.zeros((8, 8)).at[0].set(logit[0])
    r = jnp.zeros((1, 8)).at[0, 0].set(1.0)
    bias = jnp.zeros(8)
    for group_score, want in (("max", [0, 2]), ("top2", [2, 3])):
        _, weights, experts = reference.route(
            r, router, bias, top_k=2, groups=4, kept_groups=2,
            group_score=group_score, renormalise=True, scaling=2.5)
        assert sorted(experts[0].tolist()) == want, group_score
        np.testing.assert_allclose(float(weights.sum()), 2.5, rtol=1e-6)
    lifted = bias.at[6].set(0.5).at[7].set(0.5)      # group 3: 0.8 + 0.7
    _, weights, experts = reference.route(
        r, router, lifted, top_k=2, groups=4, kept_groups=2,
        group_score="top2", renormalise=True, scaling=1.0)
    assert sorted(experts[0].tolist()) == [6, 7]
    # the weights are the scores WITHOUT the bias, renormalised
    np.testing.assert_allclose(sorted(weights[0].tolist()),
                               [0.2 / 0.5, 0.3 / 0.5], rtol=1e-5)
    # the program's router, the same position: through expert_ffn's books
    lp = {"router": router, "router_bias": lifted,
          "we_gate": jnp.zeros((8, 8, 4)), "we_up": jnp.zeros((8, 8, 4)),
          "we_down": jnp.zeros((8, 4, 8))}
    prog = LlamaConfig(
        hidden=8, mlp_hidden=4, num_experts=8, experts_per_token=2,
        router_groups=4, router_topk_groups=2, router_group_score="top2",
        router_scores="sigmoid", router_bias=True, norm_topk_prob=True,
        router_norm_eps=1e-20, dtype=jnp.float32)
    _, books = moe.expert_ffn(prog, r[None], lp)
    assert books["pairs"].tolist() == [0, 0, 0, 0, 0, 0, 1, 1]
    _, books = moe.expert_ffn(prog, r[None], dict(lp, router_bias=bias))
    assert books["pairs"].tolist() == [0, 0, 1, 1, 0, 0, 0, 0]
    # the group-limited choice by the best expert still takes no bias
    with pytest.raises(ValueError, match="has no bias on it here"):
        moe.expert_ffn(dataclasses.replace(prog, router_group_score="max"),
                       r[None], lp)


# --------------------------------------------------------------------------
# the served class and its counters
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    m = tiny_model()
    gen = family.Served(**family.served_kwargs(m, dict(
        lora_rank=4, max_batch_size=2, allowed_batch_sizes=[2],
        max_new_tokens=4, seq_bucket=128), 12))
    yield m, gen
    gen.engine.shutdown()


def test_the_served_class_counts_the_rules_chunks(served):
    m, gen = served
    bias = np.asarray(gen._params["layers"]["kda_routed"]["router_bias"])
    assert bias.shape == (2, 16) and float(np.abs(bias).max()) > 0
    # dealt alike to the two shares of 8: each holds one of every pair of
    # neighbouring quantiles
    assert abs(float(bias[0, :8].sum() - bias[0, 8:].sum())) < 0.02
    prompt = list(range(3, 133))                     # 130: two chunks and 2
    tokens = list(gen({"prompt": prompt, "max_new": 3}))
    assert len(tokens) == 3
    stats = gen.engine_stats()
    assert set(gen.STEP_COUNTERS) <= set(stats)
    # three steps at a bucket of 256: 3 KDA layers x 2 rows x 4 chunks run,
    # of which the one live row's three hold its tokens
    assert stats["positions_computed"] == 3 * 2 * 256
    assert stats["kda_chunks_run"] == 3 * 3 * 2 * 4
    assert stats["kda_chunks_live"] == 3 * 3 * 3
    assert stats["ssm_chunks_run"] == 0 == stats["ssm_chunks_live"]
    assert stats["layer_kinds"] == {"kda_dense": 1, "kda_routed": 2,
                                    "latent_routed": 1}
    assert stats["expert_pairs_all"] == (130 + 131 + 132) * 4 * 3
    # the tokens are the reference's own first choices
    rows = reference.logits(gen._params, jnp.asarray(prompt + tokens[:-1]), m)
    assert tokens == np.asarray(rows[129:132].argmax(-1)).tolist()
    from ray_tpu.serve.llm import LlamaGenerator
    assert "kda_chunks_run" in LlamaGenerator.STEP_COUNTERS
    assert "kda_chunks_live" in LlamaGenerator.engine_stats.__doc__


# --------------------------------------------------------------------------
# the family module
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(q_lora_rank=1536), "q_lora_rank 1536"),
    (dict(kda_safe_gate=False), "kda_safe_gate False"),
    (dict(no_kda_lora=False), "no_kda_lora False"),
    (dict(use_kda_lora=True), "use_kda_lora True"),
    (dict(linear_silu=False), "linear_silu False"),
    (dict(use_qk_norm=False), "use_qk_norm False"),
    (dict(num_kv_heads_for_linear_attn=8), "num_kv_heads_for_linear_attn 8"),
    (dict(topk_method="group_limited_greedy"), "topk_method"),
    (dict(scoring_func="softmax"), "scoring_func 'softmax'"),
    (dict(use_mla_nope=True), "use_mla_nope True"),
    (dict(rope_interleave=False), "rope_interleave False"),
    (dict(gated_attention_proj_granularity_type="element_wise"),
     "gated_attention_proj_granularity_type"),
    (dict(group_norm_size=4), "group_norm_size 4"),
    (dict(use_nGPT=True), "use_nGPT True"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(layer_types=["kda"] * 8),
     r"does not understand \['layer_types'\]"),
    (dict(num_hidden_layers=36), "expert_swiglu_limit_list: a layer within "
                                 "the first 36 clamps"),
    (dict(num_hidden_layers=35), "share_expert_swiglu_limit_list"),
    (dict(kda_lower_bound=-8), "kda_lower_bound -8"),
    (dict(head_dim=64), "head_dim a multiple of 128"),
    (dict(kda_chunk_size=40), "kda_chunk_size a multiple of 16"),
    (dict(num_key_value_heads=8), "num_key_value_heads"),
    (dict(qk_head_dim=128), "qk_head_dim is qk_nope_head_dim"),
    (dict(moe_shared_expert_intermediate_size=1536),
     "moe_shared_expert_intermediate_size"),
    (dict(num_experts=100), "is no whole share"),
    (dict(n_group=7), "512 experts in 7 groups"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    m = dict(loader.load_config(CONFIG), **change)
    with pytest.raises(ValueError, match=match):
        family.check(m)


def test_a_file_that_lacks_a_key_is_refused():
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "kda_lower_bound"}
    with pytest.raises(ValueError, match=r"lacks \['kda_lower_bound'\]"):
        family.check(lacking)


def test_a_checkout_without_the_fields_is_refused_at_once(monkeypatch):
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert family._config_fields() == fields
    assert (set(family.MODEL_KEYS.values()) | set(family.BUILT)
            | set(family.MODELING)) <= fields
    monkeypatch.setattr(family, "_config_fields", lambda: fields - {
        "kda_heads", "router_group_score"})
    with pytest.raises(ValueError, match=r"LlamaConfig has no \['kda_heads'"
                                         r", 'router_group_score'\]"):
        family.check(loader.load_config(CONFIG))
    monkeypatch.undo()
    monkeypatch.setattr(family.LlamaGenerator, "STEP_COUNTERS",
                        ("host_bytes", "ssm_chunks_run"))
    with pytest.raises(ValueError, match="counts no chunks of a delta rule"):
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
    gone = ("kda_heads", "kda_head_dim", "kda_conv_kernel", "kda_chunk",
            "kda_lower_bound", "router_group_score")
    for field in gone:
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
    assert f"LlamaConfig has no {sorted(gone)}" in proc.stderr


def test_the_configuration_keeps_every_published_number():
    m = loader.load_config(CONFIG)
    row = published()
    assert m["source"] == row["source_url"]
    assert m["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    cut = {"num_hidden_layers": (42, 8), "num_experts": (512, 128),
           "vocab_size": (157184, 39296)}
    assert m["changed_from_source"] == {
        k: {"source": a, "here": b} for k, (a, b) in cut.items()}
    for key, value in row["config"].items():
        assert m[key] == (cut[key][1] if key in cut else value), key
    assert set(m) - set(row["config"]) == {
        "name", "source", "family", "expert_share", "expert_bias_init_std",
        "kda_chunk_size", "reduced", "changed_from_source", "assumed",
        "program", "deployment", "notes"}
    assert m["expert_share"] == {"first": 0, "of": 512}
    # the floors of a model_config cut: a whole period and four routed
    # layers after the leading dense ones, 8 experts, an eighth of the ids
    assert m["num_hidden_layers"] - m["first_k_dense_replace"] \
        >= m["layer_group_size"] >= 4
    assert m["num_experts"] >= 8 and m["vocab_size"] * 8 >= 157184
    assert m["vocab_size"] * 4 == 157184
    assert m["program"] == {"attn_impl": "flash", "dtype": "bfloat16",
                            "param_dtype": "bfloat16"}
    said = " ".join(m["assumed"])
    for item in ("(l + 1) % layer_group_size == 0", "'not set'",
                 "L2 normalisation", "lower_bound * sigmoid",
                 "AFTER the head's norm", "q_lora_rank null",
                 "SUM OF ITS TWO LARGEST", "1e-20", "head_wise",
                 "multi-token-prediction", "log-uniform in 10 to 1000",
                 "expert_swiglu_limit_list", "max_window_layers",
                 "expert_bias_init_std"):
        assert item in said, item
    assert "4 v5e chips" in m["deployment"]


def test_counts_by_hand():
    m = loader.load_config(CONFIG)
    h, inner = 2560, 32 * 128
    assert inner == 4096
    kda = (5 * h * inner + h * 32 + 3 * inner * 4 + 32 + inner + 128
           + inner * h)
    latent = (h * 32 * 192 + h * (512 + 64) + 512 + 512 * 32 * 256
              + 32 * 128 * h + h * 32)
    dense = 3 * h * 6144
    expert = 3 * h * 768
    routed = 128 * expert + expert + h * 512 + 512
    assert (kda, latent, dense, expert, routed) == (
        63_049_888, 31_965_696, 47_185_920, 5_898_240, 762_184_192)
    assert family.part_params(m) == {"kda": kda, "latent": latent,
                                     "dense": dense, "routed": routed}
    assert family.layer_counts(m) == {"kda": 7, "latent": 1, "dense": 2,
                                      "routed": 6}
    total = (2 * (kda + dense) + 5 * (kda + routed) + (latent + routed)
             + 8 * 2 * h + 2 * 39296 * h + h)
    assert total == 5_342_030_944 == family.num_params(m)
    assert family.build_config(m).num_params() == total
    assert round(total * 2 / 1e9, 2) == 10.68
    # the whole model by the same parts: the card's "~125B-A5.5B"
    whole = (35 * kda + 7 * latent + 2 * dense
             + 40 * (512 * expert + expert + h * 512 + 512)
             + 42 * 2 * h + 2 * 157184 * h + h)
    assert round(whole / 1e9, 1) == 124.4
    a_token = (35 * kda + 7 * latent + 2 * dense
               + 40 * (9 * expert + h * 512) + 157184 * h)
    assert round(a_token / 1e9, 1) == 5.1
    # the rule's need a live position a layer at chunks of 128: 6.29 MFLOP,
    # 49 280 bytes
    assert family.kda_flops_a_position(m) == 2 * 32 * (
        3 * 128 * 128 + 3 * 128 * 128) == 6_291_456
    step = {"positions_live": 1000, "rows": 8}
    assert family.kda_chunk_flops(m, step) == 7 * 1000 * 6_291_456
    assert family.kda_chunk_bytes(m, step) == 7 * 1000 * 49_280
    assert 49_280 == 4 * 2 * 4096 + 4 * 4096 + 4 * 32
    # latent attention's flash forward over the ONE MLA layer
    assert family.flash_fwd_pair_flops(m, 10) == 1 * 32 * 2 * 320 * 10
    assert family.flash_fwd_row_bytes(m, 3, 5) == 2 * (
        3 * 32 * 320 + 5 * (32 * 256 + 64))
    # the held experts: a quarter of a symmetric router's pairs
    assert family.held_share(m) == 0.25
    assert family.expert_ffn_flops(m, 100) == 6 * 100 * 8 * 0.25 * 6 * h * 768
    assert family.expert_ffn_bytes(m) == 6 * 128 * 3 * h * 768 * 2
    assert family.expert_ffn_bytes(m, 10) == 10 * 3 * h * 768 * 2


def test_the_family_module_imports_no_jax(repo_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.harness import loader\n"
        "from benchmark.families import bailing_hybrid\n"
        "cell = loader.load_cell('serve_ling3_repoctx')\n"
        "assert bailing_hybrid.num_params(cell['model']) > 5e9\n"
        "for m in loader.metrics_for_cell(cell): loader.load_reader(m)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % repo_root)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------------
# the cell's files and its metrics
# --------------------------------------------------------------------------
OWN = {"ling3_kda_chunk_ms.serve", "ling3_kda_chunk_roofline_pct.serve",
       "ling3_kda_chunks_live_pct.serve", "ling3_mla_flash_fwd_ms.serve",
       "ling3_mla_flash_fwd_roofline_pct.serve",
       "ling3_expert_ffn_roofline_pct.serve",
       "ling3_expert_matmul_sort_ms.serve",
       "ling3_expert_load_imbalance.serve",
       "ling3_routed_pairs_here_pct.serve"}


def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell(CELL)
    granite = loader.load_cell("serve_granite_toolcalls")
    # the engine is the other serving cells' but for the bucket and the
    # answers' length
    differ = ("seq_bucket", "max_new_tokens")
    assert {k: v for k, v in cell["engine"].items() if k not in differ} \
        == {k: v for k, v in granite["engine"].items() if k not in differ}
    assert cell["engine"]["seq_bucket"] == 512
    assert cell["engine"]["seq_bucket"] % cell["model"]["kda_chunk_size"] == 0
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 1536, "sigma": 0.5, "min": 768,
                                 "max": 3040}
    assert mix["output_len"] == {"median": 12, "sigma": 0.5, "min": 4,
                                 "max": 24}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 24
    # five buckets; a context never passes 3072
    assert serve_driver.seq_buckets(cell) == [1024, 1536, 2048, 2560, 3072]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 3072
    assert list(cell["check"]["limits"]) == ["gap_mean"]
    assert all(0 < limit < 1 for limit in cell["check"]["limits"].values())
    assert mix["rate_per_s"] / mix["knee"]["rate_per_s"] == \
        pytest.approx(0.6, abs=0.02)
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    assert OWN <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    assert not OWN & {m["name"] for m in loader.metrics_for_cell(granite)}
    listed = loader.manifest_cell(manifest, CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        CONFIG, "repo_context_completions", 1)
    assert len(listed["why"]) <= 200
    # the cell's own entries, each found by its name: where they lie in
    # their lists and what else the manifest holds is not this test's
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
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
    metrics = {m["name"]: m for m in loader.load_metric_files()}
    m = loader.load_config(CONFIG)
    ops = [("tpu_custom_call:kda_chunk.16", 0.400, 28),
           ("tpu_custom_call:flash_fwd_shared_rope.9", 0.040, 4),
           ("tpu_custom_call:ragged-dot-none-pallas.3", 0.100, 48),
           ("sort.5", 0.020, 24),
           # another model's kernels are none of them
           ("tpu_custom_call:ssd_scan.4", 0.050, 4),
           ("tpu_custom_call:checkpoint.10", 0.050, 4),
           ("fusion.120", 0.300, 48), ("convolution.4", 0.600, 80)]
    records = [whole_rows(8, 2000)] * 3 + [whole_rows(5, 3000)]
    view = view_of(ops, records, {
        "kda_chunks_run": 400, "kda_chunks_live": 300,
        "expert_pairs_fullest": 900.0, "expert_pairs_mean": 450.0,
        "expert_pairs_here": 260.0, "expert_pairs_all": 1000.0})

    def value(name):
        return loader.load_reader(metrics[name])(view, metrics[name])

    assert value("ling3_kda_chunk_ms.serve") == pytest.approx(100.0)
    assert value("ling3_mla_flash_fwd_ms.serve") == pytest.approx(10.0)
    assert value("ling3_expert_matmul_sort_ms.serve") == pytest.approx(30.0)
    want = sum(max(family.kda_chunk_flops(m, r) / 197e12,
                   family.kda_chunk_bytes(m, r) / 819e9) for r in records)
    assert value("ling3_kda_chunk_roofline_pct.serve") == pytest.approx(
        100.0 * want / 0.400)
    # the bytes bind: 60.2 ns a position a layer against 31.9
    assert family.kda_chunk_bytes(m, records[0]) / 819e9 > \
        1.8 * family.kda_chunk_flops(m, records[0]) / 197e12
    want = sum(max(
        family.flash_fwd_pair_flops(m, r["attention_pairs"]) / 197e12,
        family.flash_fwd_row_bytes(m, r["positions_live"],
                                   r["attention_keys"]) / 819e9)
        for r in records)
    assert value("ling3_mla_flash_fwd_roofline_pct.serve") == \
        pytest.approx(100.0 * want / 0.040)
    want = sum(max(
        family.expert_ffn_flops(m, r["positions_live"]) / 197e12,
        family.expert_ffn_bytes(m, None) / 819e9) for r in records)
    assert value("ling3_expert_ffn_roofline_pct.serve") == \
        pytest.approx(100.0 * want / 0.100)
    assert value("ling3_kda_chunks_live_pct.serve") == pytest.approx(75.0)
    assert value("ling3_expert_load_imbalance.serve") == pytest.approx(2.0)
    assert value("ling3_routed_pairs_here_pct.serve") == pytest.approx(26.0)
    # no such kernel in the trace, no traced step, or a program that counts
    # no chunks (the parent): None, no raise
    traced = {n for n in OWN if metrics[n]["source"] == "device_trace"}
    assert len(traced) == 6
    for name in traced:
        metric = metrics[name]
        read = loader.load_reader(metric)
        assert read(view_of(ops[4:], records, {}), metric) is None, name
        if name.endswith("roofline_pct.serve"):
            assert read(view_of(ops, [], {}), metric) is None, name
    live = metrics["ling3_kda_chunks_live_pct.serve"]
    assert loader.load_reader(live)(view_of(ops, records, {}), live) is None
    assert loader.load_reader(live)(view_of(ops, records, {
        "kda_chunks_run": 0, "kda_chunks_live": 0}), live) is None
    # the other cells' metrics are not bound to this cell, and share its
    # readers' matches
    assert CELL not in metrics["dsv2_mla_flash_fwd_ms.serve"]["cells"]
    assert metrics["ling3_mla_flash_fwd_ms.serve"]["match"] == \
        metrics["dsv2_mla_flash_fwd_ms.serve"]["match"]
    assert metrics["ling3_kda_chunk_ms.serve"]["match"] == \
        "^tpu_custom_call:kda_chunk"


def test_the_cells_step_holds_the_kernels_under_their_scopes():
    from tests.benchmark.test_deepseek_v2 import program_text

    text = program_text(CELL, "step3072")
    # five runs of like layers, four of them KDA's: a kernel a run
    # (the printer shows a body that two runs share once)
    assert 3 <= text.count("name=_kernel_kda") <= 4
    assert text.count("pallas_call[") >= 5 + 2 * 4    # + MLA, the experts'
    assert "Ref{bf16[1,128,512]}" in text      # a chunk of 4 heads of q
    assert "Ref{f32[1,4,128,128]}" in text     # their states in and out
    # no [128, 128] matrix of a head and chunk, and no decay of one, is an
    # operand or a result of anything outside the kernel
    outside = re.sub(r"Ref\{[^}]*\}", "", text)
    assert not re.search(r"\[8,\d+,\d+,128,128\]", outside)
    assert not re.search(r"\[8,32,3072,3072\]", outside)


def test_the_operators_parts_run_under_their_scopes():
    # a jaxpr's text keeps no scope; the lowered module's locations do (the
    # rehearsal's sizes: the names do not go by the widths)
    cell = loader.load_cell(CELL, rehearsal=True)
    cfg = family.served_kwargs(cell["model"], cell["engine"],
                                       1)["config"]
    shapes = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    rows, length = 2, cell["engine"]["seq_bucket"]
    lowered = jax.jit(lambda p, t, i, on: llama_next_token(
        p, t, i, cfg, live=on)).lower(
            shapes, jax.ShapeDtypeStruct((rows, length), jnp.int32),
            jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows, length), jnp.bool_))
    text = lowered.as_text(debug_info=True)
    for scope in ("kda_in_proj", "kda_conv", "kda_gate", "kda_gated_norm",
                  "latent_attention", "flash_fwd_shared_rope", "moe_router"):
        assert f"/{scope}/" in text, scope
    # the kernel's own scope is the innermost round the Pallas call
    assert re.search(r"/kda_chunk/pallas_call", text)


# --------------------------------------------------------------------------
# tools/ling3_kda_check.py: the kernel against the recurrence
# --------------------------------------------------------------------------
def kda_check(capsys, *argv):
    from benchmark.tools import ling3_kda_check

    rc = ling3_kda_check.main(["--rehearsal", *argv])
    return rc, [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


def test_the_kda_check_rehearses(capsys, tmp_path):
    out = tmp_path / "lines" / "kda.jsonl"
    rc, lines = kda_check(capsys, "--seeds", "1", "--out", str(out))
    assert rc == 0 and len(lines) == 1
    assert lines == [json.loads(ln) for ln in out.read_text().splitlines()]
    line = lines[0]
    assert line["ok"] and line["rehearsal"] and line["platform"] == "cpu"
    # the rehearsal's sizes: four chunks of 64, three edges a row
    assert (line["rows"], line["length"], line["chunk"],
            line["edges_a_row"]) == (8, 256, 64, 3)
    assert line["kernel"] == "kda_chunk"
    tol = line["tolerance"]
    assert line["sound"]["outputs_off"] < tol
    assert line["sound"]["final_state_off"] < tol
    assert line["state_dropped"]["up_to_the_first_edge_off"] < tol
    assert line["state_dropped"]["after_it_off"] > line["dropped_over"]


def test_the_kda_check_tells_a_kernel_that_loses_its_state(capsys,
                                                           monkeypatch):
    """The fault planted in the kernel itself: every chunk from zeros."""
    from ray_tpu.ops.pallas import kda_chunk as kernel

    sound = kernel.kda_chunked

    def loses_its_state(q, k, v, g, beta, s0, chunk):
        parts = [sound(*(a[:, s:s + chunk] for a in (q, k, v, g, beta)),
                       s0, chunk) for s in range(0, q.shape[1], chunk)]
        return jnp.concatenate([o for o, _ in parts], axis=1), parts[-1][1]

    monkeypatch.setattr(kernel, "kda_chunked", loses_its_state)
    rc, (line,) = kda_check(capsys, "--seeds", "1")
    assert rc == 1 and not line["ok"]
    assert line["sound"]["outputs_off"] > line["dropped_over"]
    assert line["state_dropped"]["up_to_the_first_edge_off"] < \
        line["tolerance"]


def test_the_kda_check_measures_on_a_chip_alone():
    from benchmark.tools import ling3_kda_check

    with pytest.raises(SystemExit, match="no chip"):
        ling3_kda_check.main(["--seeds", "1"])


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
