"""LFM2's mixture-of-experts decoder through the one block of
``models/llama.py`` against the plain float32 reference, tiny and with the
cell's own layer pattern, on the CPU; the short convolution and the
router's variants by themselves; the family module's checks and counts; the
cell's files and the reader it brings.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerances are a few 1e-5: computing in bf16, an ignored
bias, a missing norm or a tap out of place move the results by thousands
of times that (the tests beside each group show it). A score that ties to
within that error between the k-th and the next expert would flip an
expert; the seeds below meet no such tie.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import lfm2_moe as family
from benchmark.harness import lastline, loader, peaks
from benchmark.reference import lfm2_moe as reference
from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, LoraConfig, _rms_norm, _short_conv, init_decode_state,
    init_llama, init_lora, llama_decode, llama_forward, llama_logical_axes,
    llama_loss, llama_next_token)

CELL = "serve_lfm2_rag"
CONFIG = "lfm2-24b-a2b-serve-l9"
TIGHT = dict(rtol=5e-5, atol=5e-5)
ROUTED = ("attention_routed", "conv_routed")
# config.json of LiquidAI/LFM2-24B-A2B, as the catalog beside the
# model-configs guide reads it (row LFM2-24B-A2B, `config`)
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}


def tiny_model(**over):
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, program={"attn_impl": "reference", "dtype": "float32",
                         "param_dtype": "float32"})
    m.update(over)
    return m


def randomised(params, key):
    """Norm weights off 1, so that a norm left out or misplaced shows."""
    def off_one(path, a):
        name = path[-1].key
        if not name.endswith("_norm"):
            return a
        return 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, sum(map(ord, str(path)))), a.shape)
    return jax.tree_util.tree_map_with_path(off_one, params)


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = family.build_config(m)
    # the program starts the expert bias at zeros; the family draws it
    # (0.05 here: at these sizes it moves some choices and not all)
    params = family.with_expert_bias(
        randomised(init_llama(cfg, jax.random.key(3)), jax.random.key(5)),
        0.05, 3)
    tokens = jax.random.randint(jax.random.key(4), (2, 48), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


# --------------------------------------------------------------------------
# the pattern, the tree and what the old models keep
# --------------------------------------------------------------------------
def test_the_pattern_is_the_cells(setup):
    m, cfg, params, _ = setup
    assert m["layer_types"] == loader.load_config(CONFIG)["layer_types"]
    assert cfg.kind_counts() == {"conv_dense": 1, "attention_routed": 2,
                                 "conv_routed": 6}
    assert cfg.layer_runs() == (
        ("conv_dense", 0, 1), ("attention_routed", 0, 1),
        ("conv_routed", 0, 3), ("attention_routed", 1, 1),
        ("conv_routed", 3, 3))
    assert set(params) == {"embed", "layers", "final_norm"}   # a tied head
    assert set(params["layers"]) == set(cfg.kind_counts())
    every = dataclasses.replace(cfg, num_layers=4, num_dense_layers=2,
                                layer_types=("conv", "full_attention") * 2)
    assert set(every.layer_kinds()) == {
        a + b for a in ("attention", "conv") for b in ("_dense", "_routed")}


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for kind, n in cfg.kind_counts().items():
        for name, leaf in params["layers"][kind].items():
            assert leaf.shape[0] == n, (kind, name)
            assert len(axes["layers"][kind][name]) == leaf.ndim
    conv = params["layers"]["conv_routed"]
    assert conv["conv_in"].shape[1:] == (64, 192)
    assert conv["conv_w"].shape[1:] == (64, 3)
    assert conv["router_bias"].shape == (6, 8)
    assert params["layers"]["attention_routed"]["q_norm"].shape == (2, 16)
    assert params["layers"]["conv_dense"]["w_gate"].shape == (1, 64, 96)
    n = sum(a.size for a in jax.tree.leaves(params))
    assert n == cfg.num_params() == family.num_params(m)
    with pytest.raises(ValueError, match="layer_types names 9 layers"):
        dataclasses.replace(cfg, num_layers=8).layer_kinds()
    with pytest.raises(ValueError, match="sliding"):
        dataclasses.replace(cfg, layer_types=("sliding",) * 9).layer_kinds()


# the stacked trees of the models the benchmark had, leaf -> shape, as the
# parent commit made them: a checkpoint of either still loads
OLD_TREES = {
    "dense": (LlamaConfig.tiny(), {
        "attn_norm": (2, 128), "mlp_norm": (2, 128),
        "w_down": (2, 352, 128), "w_gate": (2, 128, 352),
        "w_up": (2, 128, 352), "wk": (2, 128, 2, 32), "wo": (2, 4, 32, 128),
        "wq": (2, 128, 4, 32), "wv": (2, 128, 2, 32)}),
    "olmoe": (dataclasses.replace(LlamaConfig.tiny(), num_experts=4,
                                  experts_per_token=2, qk_norm=True), {
        "attn_norm": (2, 128), "mlp_norm": (2, 128), "k_norm": (2, 64),
        "q_norm": (2, 128), "router": (2, 128, 4),
        "we_down": (2, 4, 352, 128), "we_gate": (2, 4, 128, 352),
        "we_up": (2, 4, 128, 352), "wk": (2, 128, 2, 32),
        "wo": (2, 4, 32, 128), "wq": (2, 128, 4, 32),
        "wv": (2, 128, 2, 32)}),
}


@pytest.mark.parametrize("which", sorted(OLD_TREES))
def test_a_model_of_one_kind_keeps_its_tree(which):
    cfg, layers = OLD_TREES[which]
    params = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    assert {k: v.shape for k, v in params["layers"].items()} == layers
    assert {k: v.shape for k, v in params.items() if k != "layers"} == {
        "embed": (256, 128), "final_norm": (128,), "lm_head": (128, 256)}
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert len(cfg.layer_runs()) == 1 and len(cfg.kind_counts()) == 1


# --------------------------------------------------------------------------
# the short convolution and the router, by themselves
# --------------------------------------------------------------------------
def test_the_short_conv_against_a_loop_over_t(setup):
    _, cfg, params, _ = setup
    lp = jax.tree.map(lambda a: np.asarray(a[2], np.float64),
                      params["layers"]["conv_routed"])
    u = np.asarray(jax.random.normal(jax.random.key(8), (2, 11, cfg.hidden)),
                   np.float64)
    want, last_z = np.zeros_like(u), []
    for b in range(2):
        z = []
        for t in range(11):
            bcx = u[b, t] @ lp["conv_in"]
            gate_b, gate_c, x = np.split(bcx, 3)
            z.append(gate_b * x)
            c = sum(lp["conv_w"][:, j] * z[t - 2 + j]
                    for j in range(3) if t - 2 + j >= 0)
            want[b, t] = (gate_c * c) @ lp["conv_out"]
        last_z.append(np.stack(z[-2:]))
    lp32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), lp)
    got, state = _short_conv(cfg, jnp.asarray(u, jnp.float32), lp32)
    np.testing.assert_allclose(got, want, **TIGHT)
    np.testing.assert_allclose(state, np.stack(last_z), **TIGHT)
    # in two parts, the state carried: what an incremental decode does
    first, state = _short_conv(cfg, jnp.asarray(u[:, :4], jnp.float32), lp32)
    rest, _ = _short_conv(cfg, jnp.asarray(u[:, 4:], jnp.float32), lp32,
                          state)
    np.testing.assert_allclose(jnp.concatenate([first, rest], 1), want,
                               **TIGHT)


def routed(cfg, params, kind, j, x, mask=None):
    """The block's routed feed-forward on x [B, S, H]: its norm, then the
    experts."""
    layers = params["layers"][kind]
    lp = moe.in_stack(jax.tree.map(lambda a: a[j], layers), layers, j, mask)
    return moe.expert_ffn(cfg, _rms_norm(x, lp["mlp_norm"], cfg.rms_eps), lp)


def test_the_routed_ffn_agrees_with_the_reference(setup):
    m, cfg, params, _ = setup
    x = jax.random.normal(jax.random.key(7), (2, 24, cfg.hidden))
    for kind, j in (("conv_routed", 4), ("attention_routed", 1)):
        got, books = routed(cfg, params, kind, j, x)
        for row in range(2):
            want = reference.routed_ffn(x[row], params["layers"][kind], j, m)
            np.testing.assert_allclose(got[row], want, **TIGHT)
        assert float(books["pairs"].sum()) == 2 * 24 * cfg.experts_per_token


def test_the_bias_moves_the_choice_and_not_the_weights(setup):
    m, cfg, params, _ = setup
    layers = params["layers"]["conv_routed"]
    x = jax.random.normal(jax.random.key(7), (24, cfg.hidden))
    eps = float(m["norm_eps"])
    kw = dict(eps=eps, top_k=2, renormalise=True, scaling=1.0)
    _, scores, weights, experts = reference.route(x, layers, 0, **kw)
    no_bias = {k: v for k, v in layers.items() if k != "router_bias"}
    _, _, _, plain = reference.route(x, no_bias, 0, **kw)
    moved = np.asarray(experts != plain).any(axis=-1)
    assert 4 <= moved.sum() <= 23            # some choices, not all
    # the weights are the chosen SCORES, renormalised with the epsilon
    s = np.take_along_axis(np.asarray(scores), np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, s / (s.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    # and the program routes the same way: with the bias ignored, or the
    # weights taken with the bias in them, it leaves the reference
    got, _ = routed(cfg, params, "conv_routed", 0, x[None])
    want = reference.routed_ffn(x, layers, 0, m)
    np.testing.assert_allclose(got[0], want, **TIGHT)
    zero = dict(params, layers=dict(params["layers"], conv_routed=dict(
        layers, router_bias=jnp.zeros_like(layers["router_bias"]))))
    ignored, _ = routed(cfg, zero, "conv_routed", 0, x[None])
    assert float(jnp.abs(ignored[0] - want).max()) > 1000 * TIGHT["atol"]
    for other in (dict(norm_topk_prob=False), dict(router_norm_eps=0.1),
                  dict(routed_scaling_factor=2.0),
                  dict(router_scores="softmax")):
        off, _ = routed(dataclasses.replace(cfg, **other), params,
                        "conv_routed", 0, x[None])
        assert float(jnp.abs(off[0] - want).max()) > 100 * TIGHT["atol"], other
    with pytest.raises(ValueError, match="router_scores 'tanh'"):
        routed(dataclasses.replace(cfg, router_scores="tanh"), params,
               "conv_routed", 0, x[None])


def test_zero_bias_and_softmax_are_olmoes_router_to_the_bit():
    cfg = dataclasses.replace(LlamaConfig.tiny(), num_experts=4,
                              experts_per_token=2, dtype=jnp.float32)
    params = init_llama(cfg, jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.hidden))
    lp = jax.tree.map(lambda a: a[1], params["layers"])
    for renorm in (False, True):
        c = dataclasses.replace(cfg, norm_topk_prob=renorm)
        want, books = moe.expert_ffn(c, x, lp)
        fields = dataclasses.replace(c, router_bias=True,
                                     router_scores="softmax")
        got, books2 = moe.expert_ffn(
            fields, x, dict(lp, router_bias=jnp.zeros((4,), jnp.float32)))
        np.testing.assert_array_equal(got, want)
        jax.tree.map(np.testing.assert_array_equal, books, books2)


# --------------------------------------------------------------------------
# the whole model against the reference
# --------------------------------------------------------------------------
def test_logits_agree_with_the_reference(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens, cfg)
    for row in range(2):
        want = reference.logits(params, tokens[row], m)
        np.testing.assert_allclose(got[row], want, **TIGHT)


def test_the_served_step_and_remat_compute_the_same(setup):
    m, cfg, params, tokens = setup
    last = jnp.array([47, 30], jnp.int32)
    live = jnp.arange(48)[None, :] <= last[:, None]
    for policy in ("dots", "full", "mixed:4"):
        c = dataclasses.replace(cfg, remat=True, remat_policy=policy)
        ids, hidden, load = llama_next_token(params, tokens, last, c,
                                             live=live)
        for row in range(2):
            want = reference.logits(params, tokens[row], m)[int(last[row])]
            assert int(ids[row]) == int(jnp.argmax(want)), policy
        # the routers' books come from the 8 routed layers, in order
        assert load["fullest"].shape == load["mean"].shape == (8,)
        np.testing.assert_allclose(
            load["mean"], (48 + 31) * cfg.experts_per_token / 8, rtol=1e-6)


def test_bf16_compute_fails_the_float32_tolerance(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens[:1],
                        dataclasses.replace(cfg, dtype=jnp.bfloat16))
    want = reference.logits(params, tokens[0], m)
    assert float(jnp.abs(got[0] - want).max()) > 1000 * TIGHT["atol"]


def test_a_norm_or_an_operator_out_of_place_is_told(setup):
    m, cfg, params, tokens = setup
    want = reference.logits(params, tokens[0], m)

    def off(c=cfg, p=params):
        return float(jnp.abs(llama_forward(p, tokens[:1], c)[0] - want).max())

    assert off() < TIGHT["atol"]
    # the norm over each head is not the norm over the whole projection
    attn = params["layers"]["attention_routed"]
    whole = dict(attn, q_norm=jnp.tile(attn["q_norm"], (1, 4)),
                 k_norm=jnp.tile(attn["k_norm"], (1, 2)))
    assert off(dataclasses.replace(cfg, qk_head_norm=False, qk_norm=True),
               dict(params, layers=dict(params["layers"],
                                        attention_routed=whole))) > 0.01
    # the taps in the other order
    conv = params["layers"]["conv_routed"]
    assert off(p=dict(params, layers=dict(params["layers"], conv_routed=dict(
        conv, conv_w=conv["conv_w"][..., ::-1])))) > 0.01


def test_decode_through_both_kinds_of_state_is_the_full_forward(setup):
    _, cfg, params, tokens = setup
    full = llama_forward(params, tokens, cfg)
    state = init_decode_state(cfg, 2, 64)
    assert [s[0].shape if isinstance(s, tuple) else s.shape
            for s in state[:2]] == [(2, 2, 64), (2, 64, 2, 16)]
    logits, state = llama_decode(params, tokens[:, :20], cfg, state,
                                 jnp.int32(0))
    np.testing.assert_allclose(logits, full[:, :20], **TIGHT)
    for t in range(20, 26):
        logits, state = llama_decode(params, tokens[:, t:t + 1], cfg, state,
                                     jnp.int32(t))
        np.testing.assert_allclose(logits[:, 0], full[:, t], **TIGHT)
    assert state[0].shape == (2, 2, 64)   # two rows of z a sequence


def test_the_loss_on_the_pattern_and_no_gradient_on_the_bias(setup):
    m, cfg, params, tokens = setup
    loss, grads = jax.value_and_grad(
        lambda p: llama_loss(p, {"tokens": tokens}, cfg))(params)
    want = np.mean([float(reference.loss(params, tokens[r, :-1],
                                         tokens[r, 1:], m)) for r in (0, 1)])
    assert float(loss) == pytest.approx(want, abs=2e-5)
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grads))
    for kind in ROUTED:
        assert not np.asarray(grads["layers"][kind]["router_bias"]).any()
        assert np.asarray(grads["layers"][kind]["router"]).any()
    assert np.asarray(grads["layers"]["conv_dense"]["conv_w"]).any()
    chunked = dataclasses.replace(cfg, remat=True, loss_chunk=47,
                                  remat_policy="mixed:2")
    assert float(llama_loss(params, {"tokens": tokens}, chunked)) == \
        pytest.approx(float(loss), abs=2e-5)


def test_lora_follows_the_attention_layers_alone(setup):
    _, cfg, params, tokens = setup
    lcfg = LoraConfig(rank=2, targets=("wq", "wv"))
    lora = init_lora(cfg, lcfg, jax.random.key(2))
    assert set(lora["layers"]) == {"attention_routed"}
    assert lora["layers"]["attention_routed"]["wq"]["a"].shape == (2, 64, 2)
    assert lcfg.num_params(cfg) == sum(
        a.size for a in jax.tree.leaves(lora))
    plain = llama_forward(params, tokens, cfg)
    np.testing.assert_allclose(
        llama_forward(params, tokens, cfg, lora=lora, lora_cfg=lcfg), plain,
        atol=1e-6)    # B = 0: the adapted model starts at the base
    lora = jax.tree.map(lambda a: a + 0.05, lora)
    assert float(jnp.abs(llama_forward(
        params, tokens, cfg, lora=lora, lora_cfg=lcfg) - plain).max()) > 1e-3
    # the one dense feed-forward can be adapted; a routed model's cannot
    dense = init_lora(cfg, LoraConfig(rank=2, targets=("w_up",)),
                      jax.random.key(2))
    assert dense["layers"]["conv_dense"]["w_up"]["b"].shape == (1, 2, 96)
    with pytest.raises(ValueError, match=r"LoRA targets \['w_gate'\]: no "
                                         "layer of this model has them"):
        init_lora(dataclasses.replace(cfg, num_dense_layers=0),
                  LoraConfig(rank=2, targets=("wq", "w_gate")),
                  jax.random.key(2))
    with pytest.raises(ValueError, match=r"LoRA targets \['conv_in'\]"):
        init_lora(cfg, LoraConfig(rank=2, targets=("conv_in",)),
                  jax.random.key(2))


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


def test_the_served_class_says_its_kinds_and_keeps_the_routers_books(served):
    m = tiny_model()
    stats = served.engine_stats()
    assert stats["layer_kinds"] == {"conv_dense": 1, "attention_routed": 2,
                                    "conv_routed": 6}
    states = [served._prefill({"prompt": list(range(3, 14)), "max_new": 4},
                              ""), None]
    served._step("", states)
    after = served.engine_stats()
    # 11 live positions x 2 experts over 8 experts, in each of 8 layers
    assert after["expert_pairs_mean"] - stats["expert_pairs_mean"] == \
        pytest.approx(8 * 11 * 2 / 8)
    assert after["host_bytes"] - stats["host_bytes"] == 2 * 4 + 8 * 8
    adapter = served._adapter("a1")
    assert set(adapter["layers"]) == {"attention_routed"}
    # what the check compares: the mean over the prompt's positions of the
    # logits, on both sides; and the served bias is the family's draw
    prompt = list(range(5, 37))
    want = reference.last_logits(served._params, jnp.asarray(prompt), m)
    np.testing.assert_allclose(served.last_position_logits(prompt), want,
                               **TIGHT)
    np.testing.assert_allclose(
        want, reference.logits(served._params, jnp.asarray(prompt),
                               m).mean(0), rtol=1e-6, atol=1e-6)
    assert float(jnp.abs(served._params["layers"]["conv_routed"][
        "router_bias"]).max()) > 0


# --------------------------------------------------------------------------
# the family module: what it refuses, what it counts
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(sliding_window=4096), r"does not understand \['sliding_window'\]"),
    (dict(layer_types=["conv"] * 8), "layer_types names 8 layers"),
    (dict(layer_types=["conv"] * 8 + ["linear_attention"]),
     r"layer_types \['linear_attention'\]"),
    (dict(conv_bias=True), "conv_bias True"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}),
     "rope_parameters"),
    (dict(num_experts_per_tok=0), "num_experts_per_tok"),
    (dict(head_dim=128), "head_dim"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    m = dict(loader.load_config(CONFIG), **change)
    with pytest.raises(ValueError, match=match):
        family.check(m)
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "moe_intermediate_size"}
    with pytest.raises(ValueError, match="lacks"):
        family.check(lacking)


def test_a_checkout_whose_configuration_lacks_the_pattern_is_refused(
        monkeypatch):
    # without jax, so that the harness process fails at once where a
    # replica that cannot build its configuration is retried for minutes
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert family._config_fields() == fields
    assert set(family.MODEL_KEYS.values()) | set(family.MODELING) <= fields
    monkeypatch.setattr(family, "_config_fields", lambda: fields - {
        "layer_types", "conv_kernel", "router_scores"})
    with pytest.raises(ValueError, match=r"LlamaConfig has no \['conv_kernel"
                                         r"', 'layer_types', 'router_scores'"):
        family.check(loader.load_config(CONFIG))


def test_the_configuration_is_the_published_one_cut_in_depth_alone():
    m = loader.load_config(CONFIG)
    assert m["source"] == ("https://huggingface.co/LiquidAI/LFM2-24B-A2B/"
                           "blob/main/config.json")
    assert m["reduced"] == ["num_hidden_layers", "num_dense_layers",
                            "layer_types"]
    for key, value in PUBLISHED.items():
        if key in m["reduced"]:
            assert m["changed_from_source"][key]["source"] == value
        else:
            assert m[key] == value, key
    # one of the two leading dense layers and the 8 layers after them
    assert m["layer_types"] == PUBLISHED["layer_types"][1:10]
    assert m["layer_types"][1:5] == m["layer_types"][5:9] == [
        "full_attention", "conv", "conv", "conv"]
    with open(os.path.join(os.path.dirname(__file__), os.pardir, os.pardir,
                           "BENCHMARK.json")) as f:
        listed = {c["name"]: c for c in json.load(f)["configs"]}[CONFIG]
    assert listed["reduced"] == m["reduced"]
    cfg = family.build_config(m)
    assert (cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) == (64, 32, 8)
    assert cfg.router_scores == "sigmoid" and cfg.qk_head_norm
    assert cfg.tie_embeddings and cfg.router_bias
    assert cfg.router_norm_eps == 1e-6
    # the program starts the bias at zeros; the family draws it from the
    # seed at the file's deviation, in the leaf's type, and nothing else
    params = init_llama(family.build_config(tiny_model()), jax.random.key(1))
    drawn = family.with_expert_bias(params, m["expert_bias_init_std"], 9)
    for kind in ROUTED:
        was, now = params["layers"][kind], drawn["layers"][kind]
        assert not np.asarray(was["router_bias"]).any()
        assert now["router_bias"].dtype == was["router_bias"].dtype
        assert float(jnp.std(now["router_bias"])) == pytest.approx(
            m["expert_bias_init_std"], rel=0.5)
        # every layer the same values (a normal's quantiles), each in an
        # order of its own
        rows = np.sort(np.asarray(now["router_bias"], np.float32), axis=-1)
        assert (rows == rows[0]).all()
        assert all(now[k] is was[k] for k in was if k != "router_bias")
    again = family.with_expert_bias(params, m["expert_bias_init_std"], 9)
    other = family.with_expert_bias(params, m["expert_bias_init_std"], 10)
    a, b, c = (t["layers"]["conv_routed"]["router_bias"]
               for t in (drawn, again, other))
    assert np.array_equal(a, b) and not np.array_equal(a, c)


def test_counts_by_hand():
    m = loader.load_config(CONFIG)
    experts = 64 * 3 * 2048 * 1536
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    assert (experts, conv, attention) == (603_979_776, 16_783_360,
                                          10_485_888)
    conv_routed = experts + 2048 * 64 + 64 + conv + 2 * 2048
    attention_routed = experts + 2048 * 64 + 64 + attention + 2 * 2048
    conv_dense = 3 * 2048 * 11776 + conv + 2 * 2048
    assert (conv_routed, attention_routed, conv_dense) == (
        620_898_368, 614_600_896, 89_139_200)
    total = (6 * conv_routed + 2 * attention_routed + conv_dense
             + 65536 * 2048 + 2048)
    assert total == 5_177_950_976 == family.num_params(m)
    assert family.build_config(m).num_params() == total
    # the whole model, by the same functions: 23.84B
    whole = dict(m, num_hidden_layers=40, num_dense_layers=2,
                 layer_types=PUBLISHED["layer_types"])
    assert family.num_params(whole) == (
        28 * conv_routed + 10 * attention_routed + 2 * conv_dense
        + 65536 * 2048 + 2048) == 23_843_661_440
    # a position meets 4 experts of three 2048 x 1536 matmuls in 8 layers
    assert family.expert_ffn_flops(m, 1) == 8 * 4 * 3 * 2 * 2048 * 1536
    assert family.expert_ffn_bytes(m) == 8 * experts * 2
    # the bytes bind up to 8 x 481 positions
    need = lambda n: (family.expert_ffn_flops(m, n) / 197e12,  # noqa: E731
                      family.expert_ffn_bytes(m) / 819e9)
    assert need(8 * 481)[0] < need(8 * 481)[1] < need(8 * 482)[0]
    # the flash forward of the 2 attention layers, the causal half
    assert family.flash_fwd_flops(m, 8, 384) == \
        2 * 8 * 32 * 2 * 2 * 384 * 384 * 64 / 2
    assert family.flash_fwd_bytes(m, 8, 384) == \
        2 * 8 * 384 * 64 * (32 + 32 + 8 + 8) * 2
    assert family.attention_kernel_flops(m, 8, 384) == \
        3.5 * family.flash_fwd_flops(m, 8, 384)


def test_the_family_module_imports_no_jax(repo_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.harness import loader\n"
        "from benchmark.families import lfm2_moe\n"
        "cell = loader.load_cell('serve_lfm2_rag')\n"
        "assert lfm2_moe.num_params(cell['model']) > 5.1e9\n"
        "for m in loader.metrics_for_cell(cell): loader.load_reader(m)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % repo_root)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------------
# the cell's files and the reader it brings
# --------------------------------------------------------------------------
def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell(CELL)
    olmoe = loader.load_cell("serve_olmoe_chat")
    # the engine is the other serving cells' but for the longest answer
    assert {k: v for k, v in cell["engine"].items()
            if k != "max_new_tokens"} == {
        k: v for k, v in olmoe["engine"].items() if k != "max_new_tokens"}
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 384, "sigma": 0.7, "min": 96,
                                 "max": 1280}
    assert mix["output_len"] == {"median": 16, "sigma": 0.5, "min": 8,
                                 "max": 48}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 48
    assert serve_driver.seq_buckets(cell) == list(range(128, 1409, 128))
    assert cell["check"]["prompt_len"] == 384
    assert mix["rate_per_s"] / mix["knee"]["rate_per_s"] == \
        pytest.approx(0.6, abs=0.02)
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    own = {"lfm2_expert_ffn_roofline_pct.serve",
           "lfm2_expert_matmul_sort_ms.serve",
           "lfm2_expert_load_imbalance.serve", "flash_fwd_d64_ms.serve",
           "flash_fwd_d64_roofline_pct.serve"}
    assert own <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    assert not own & {m["name"] for m in loader.metrics_for_cell(olmoe)}
    listed = loader.manifest_cell(manifest, CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        CONFIG, "rag_short_answers", 1)


def view_of(ops, spans, stats):
    cell = loader.load_cell(CELL)
    return {"cell": cell, "peaks": peaks.peak("TPU v5 lite"),
            "trace": {"ops": ops, "host_spans": spans, "steps": 4},
            "obs": {"engine_stats_end": stats}}


def test_the_readers_tell_the_flash_forward_from_the_grouped_matmuls():
    metrics = {m["name"]: m for m in loader.load_metric_files()}
    m = loader.load_config(CONFIG)
    ops = [("tpu_custom_call:ragged-dot-none-pallas.16", 0.100, 64),
           ("tpu_custom_call:ragged-dot-none-pallas.17", 0.060, 64),
           ("tpu_custom_call:checkpoint.8", 0.004, 4),
           ("tpu_custom_call:checkpoint.11", 0.006, 4),
           # a later Pallas kernel under a name of its own is not the flash
           # forward's time
           ("tpu_custom_call:short_conv.3", 0.050, 24),
           ("sort.3", 0.010, 64), ("fusion.120", 0.300, 48)]
    spans = {"model_step": [0.9, 4], "len_384": [0.2, 3], "len_1408": [0.4, 1]}
    view = view_of(ops, spans, {"expert_pairs_fullest": 30.0,
                                "expert_pairs_mean": 20.0})

    def value(name):
        return loader.load_reader(metrics[name])(view, metrics[name])

    assert value("flash_fwd_d64_ms.serve") == pytest.approx(1e3 * 0.010 / 4)
    assert value("lfm2_expert_matmul_sort_ms.serve") == pytest.approx(
        1e3 * 0.170 / 4)
    # 384: the bytes of q, o, k, v bind; 1408: the FLOPs
    short = family.flash_fwd_bytes(m, 8, 384) / 819e9
    long = family.flash_fwd_flops(m, 8, 1408) / 197e12
    assert short > family.flash_fwd_flops(m, 8, 384) / 197e12
    assert long > family.flash_fwd_bytes(m, 8, 1408) / 819e9
    assert value("flash_fwd_d64_roofline_pct.serve") == pytest.approx(
        100.0 * (3 * short + long) / 0.010)
    need = (3 * family.expert_ffn_bytes(m) / 819e9
            + family.expert_ffn_flops(m, 8 * 1408) / 197e12)
    assert value("lfm2_expert_ffn_roofline_pct.serve") == pytest.approx(
        100.0 * need / 0.160)
    assert value("lfm2_expert_load_imbalance.serve") == 1.5
    # a program without the kernel, the spans or the family's counts: None
    read = loader.load_reader(metrics["flash_fwd_d64_roofline_pct.serve"])
    metric = metrics["flash_fwd_d64_roofline_pct.serve"]
    assert read(view_of(ops[:2] + ops[4:], spans, {}), metric) is None
    assert read(view_of(ops, {"model_step": [0.9, 4]}, {}), metric) is None
    dense = dict(view, cell=loader.load_cell("serve_chat_steady"))
    assert read(dense, metric) is None


# --------------------------------------------------------------------------
# run.py --rehearsal of the cell, in a process of its own (25 s)
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
    assert {"lfm2_expert_matmul_sort_ms.serve", "flash_fwd_d64_ms.serve",
            "flash_fwd_d64_roofline_pct.serve",
            "lfm2_expert_ffn_roofline_pct.serve"} <= set(line["metrics"])
    assert line["metrics"]["lfm2_expert_load_imbalance.serve"]["value"] >= 1.0
