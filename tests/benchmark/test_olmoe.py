"""OLMoE through the one block of ``models/llama.py`` against the plain
float32 reference, tiny, on the CPU; the family module's checks and counts;
the readers of the three metrics the cell brings.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerances are a few 1e-5: computing in bf16, a dropped
token, a renormalised weight or a missing norm move the results by
hundreds of times that (the last tests of each group show it). A router
probability that ties to within that error between the k-th and the next
expert would flip an expert; the seeds below meet no such tie.
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

from benchmark.families import olmoe as family
from benchmark.harness import lastline, loader, peaks
from benchmark.reference import olmoe as reference
from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, _layer, _rms_norm, init_llama, llama_forward,
    llama_logical_axes, llama_loss, llama_next_token)

CELL = "serve_olmoe_chat"
TIGHT = dict(rtol=5e-5, atol=5e-5)
# config.json of allenai/OLMoE-1B-7B-0125-Instruct, as the catalog beside
# the model-configs guide reads it: every key that describes a shape
PUBLISHED = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 1024,
    "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 16,
    "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304}


def tiny_model(**over):
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, program={"attn_impl": "reference", "dtype": "float32",
                         "param_dtype": "float32"})
    m.update(over)
    return m


def randomised(params, key):
    """Norm weights off 1, so that a norm left out or misplaced shows."""
    layers = dict(params["layers"])
    for i, name in enumerate(("attn_norm", "mlp_norm", "q_norm", "k_norm")):
        layers[name] = 1.0 + 0.3 * jax.random.normal(
            jax.random.fold_in(key, i), layers[name].shape)
    return dict(params, layers=layers)


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = family.build_config(m)
    params = randomised(init_llama(cfg, jax.random.key(3)),
                        jax.random.key(5))
    tokens = jax.random.randint(jax.random.key(4), (2, 48), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


def layer_of(params, i):
    return jax.tree.map(lambda a: a[i], params["layers"])


# --------------------------------------------------------------------------
# the expert feed-forward
# --------------------------------------------------------------------------
def program_ffn(cfg, params, i, x, mask=None):
    """The block's feed-forward half on x [B, S, H]: its norm, then the
    routed experts."""
    lp = moe.in_stack(layer_of(params, i), params["layers"], i, mask)
    return moe.expert_ffn(cfg, _rms_norm(x, lp["mlp_norm"], cfg.rms_eps), lp)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_expert_ffn_agrees_with_the_reference(setup, norm_topk_prob):
    m, cfg, params, _ = setup
    m = dict(m, norm_topk_prob=norm_topk_prob)
    cfg = dataclasses.replace(cfg, norm_topk_prob=norm_topk_prob)
    x = jax.random.normal(jax.random.key(7), (2, 24, cfg.hidden))
    got, books = program_ffn(cfg, params, 1, x)
    for row in range(2):
        want, _ = reference.expert_ffn(x[row], params["layers"], 1, m)
        np.testing.assert_allclose(got[row], want, **TIGHT)
    # every (position, expert) pair is in some expert's group: none dropped
    assert float(books["pairs"].sum()) == 2 * 24 * cfg.experts_per_token
    assert float(books["positions"]) == 2 * 24


def test_renormalising_the_weights_is_told_apart(setup):
    _, cfg, params, _ = setup
    x = jax.random.normal(jax.random.key(7), (1, 24, cfg.hidden))
    plain, _ = program_ffn(cfg, params, 0, x)
    renorm, _ = program_ffn(dataclasses.replace(cfg, norm_topk_prob=True),
                            params, 0, x)
    assert float(jnp.abs(plain - renorm).max()) > 100 * TIGHT["atol"]


def test_every_position_on_the_same_experts_drops_nothing(setup):
    """The case a capacity would cut: all positions choose the same k
    experts, so those groups hold every pair and the others none."""
    m, cfg, params, _ = setup
    base = jax.random.normal(jax.random.key(11), (cfg.hidden,))
    x = base[None, None, :] + 1e-3 * jax.random.normal(
        jax.random.key(12), (1, 40, cfg.hidden))
    got, books = program_ffn(cfg, params, 0, x)
    pairs = sorted(np.asarray(books["pairs"]))
    k = cfg.experts_per_token
    assert pairs[-k:] == [40.0] * k and pairs[:-k] == [0.0] * (
        cfg.num_experts - k)
    want, _ = reference.expert_ffn(x[0], params["layers"], 0, m)
    np.testing.assert_allclose(got[0], want, **TIGHT)
    assert float(jnp.abs(want).max(axis=-1).min()) > 0  # no row came empty


def test_the_routers_books_count_live_positions_only(setup):
    _, cfg, params, _ = setup
    x = jax.random.normal(jax.random.key(7), (2, 16, cfg.hidden))
    mask = jnp.arange(16)[None, :] < jnp.array([[16], [5]])
    y_masked, books = program_ffn(cfg, params, 0, x, mask)
    y_all, every = program_ffn(cfg, params, 0, x)
    np.testing.assert_array_equal(y_masked, y_all)  # computed all the same
    assert float(books["positions"]) == 21
    assert float(books["pairs"].sum()) == 21 * cfg.experts_per_token
    assert float(every["pairs"].sum()) == 32 * cfg.experts_per_token
    assert float(books["prob"].sum()) == pytest.approx(21.0, rel=1e-5)
    load = moe.router_load({k: v[None] for k, v in books.items()})
    assert float(load["mean"][0]) == 21 * cfg.experts_per_token \
        / cfg.num_experts
    assert float(load["fullest"][0]) == float(books["pairs"].max())


# --------------------------------------------------------------------------
# the block, the logits, the loss and its gradients
# --------------------------------------------------------------------------
def test_the_block_agrees_with_the_reference(setup):
    m, cfg, params, _ = setup
    x = jax.random.normal(jax.random.key(8), (2, 32, cfg.hidden))
    positions = jnp.broadcast_to(jnp.arange(32), (2, 32))
    # the layer's leaves as they are: the block asks for nothing else
    got, cache, books = _layer(cfg, x, layer_of(params, 1), positions)
    assert cache is None and set(books) == {"pairs", "prob", "positions"}
    for row in range(2):
        want, _ = reference.block(x[row], params["layers"], 1,
                                  jnp.arange(32), m)
        np.testing.assert_allclose(got[row], want, **TIGHT)


def test_the_norms_over_queries_and_keys_are_in_the_block(setup):
    """Without them, or with the norm taken head by head, the block is
    another model: the reference tells both apart."""
    m, cfg, params, _ = setup
    x = jax.random.normal(jax.random.key(8), (1, 32, cfg.hidden))
    positions = jnp.arange(32)[None, :]
    lp = layer_of(params, 0)
    want, _ = reference.block(x[0], params["layers"], 0, jnp.arange(32), m)
    without, _, _ = _layer(dataclasses.replace(cfg, qk_norm=False), x, lp,
                           positions)
    assert float(jnp.abs(without[0] - want).max()) > 100 * TIGHT["atol"]
    # the learned weight spans heads x head_dim, as HF stores it
    assert lp["q_norm"].shape == (cfg.num_heads * cfg.head_dim,)
    assert lp["k_norm"].shape == (cfg.num_kv_heads * cfg.head_dim,)


def test_last_position_logits_agree_with_the_reference(setup):
    m, cfg, params, tokens = setup
    assert (cfg.num_layers, cfg.num_experts, cfg.experts_per_token) == (
        2, 8, 2)
    got = llama_forward(params, tokens, cfg)
    for row in range(tokens.shape[0]):
        want = reference.logits(params, tokens[row], m)
        np.testing.assert_allclose(got[row], want, rtol=2e-4, atol=2e-4)
    last = reference.last_logits(params, tokens[0], m)
    np.testing.assert_allclose(got[0, -1], last, rtol=2e-4, atol=2e-4)


def test_bf16_compute_fails_the_float32_tolerance(setup):
    """The control: bf16 passed off as float32 is told from it."""
    m, cfg, params, tokens = setup
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    got = llama_forward(params, tokens[:1], cfg16)[0]
    want = reference.logits(params, tokens[0], m)
    assert float(jnp.abs(got - want).max()) > 2e-3


def test_loss_and_gradients_agree_with_the_reference(setup):
    m, cfg, params, tokens = setup
    assert cfg.router_aux_loss_coef == 0.01
    inputs, targets = tokens[:1, :-1], tokens[:1, 1:]
    batch = {"inputs": inputs, "targets": targets}
    got, got_grads = jax.value_and_grad(
        lambda p: llama_loss(p, batch, cfg))(params)
    want, want_grads = jax.value_and_grad(
        lambda p: reference.loss(p, inputs[0], targets[0], m))(params)
    assert float(got) == pytest.approx(float(want), abs=2e-5)
    # the load-balancing term is in it: about coef x 1 at a flat router
    plain = llama_loss(params, batch, dataclasses.replace(
        cfg, router_aux_loss_coef=0.0))
    assert 0.005 < float(got - plain) < 0.05
    flat_got, _ = jax.flatten_util.ravel_pytree(got_grads)
    flat_want, _ = jax.flatten_util.ravel_pytree(want_grads)
    np.testing.assert_allclose(flat_got, flat_want, rtol=1e-3, atol=2e-6)
    # every leaf takes a gradient, the router through both terms
    for name, g in got_grads["layers"].items():
        assert float(jnp.abs(g).max()) > 0, name


def test_the_chunked_loss_and_remat_serve_the_sparse_model(setup):
    m, cfg, params, tokens = setup
    batch = {"inputs": tokens[:, :-1][:, :32], "targets": tokens[:, 1:][:, :32]}
    plain = llama_loss(params, batch, cfg)
    for remat_policy in ("dots", "mixed:1"):
        other = dataclasses.replace(cfg, remat=True, loss_chunk=16,
                                    remat_policy=remat_policy)
        assert float(llama_loss(params, batch, other)) == pytest.approx(
            float(plain), abs=2e-5), remat_policy


# --------------------------------------------------------------------------
# one configuration class, one block: what the new fields leave alone
# --------------------------------------------------------------------------
def dense_jaxprs(cfg):
    params = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    last = jax.ShapeDtypeStruct((2,), jnp.int32)
    step = jax.make_jaxpr(lambda p, t, i: llama_next_token(p, t, i, cfg))(
        params, tokens, last)
    grads = jax.make_jaxpr(jax.grad(
        lambda p, t: llama_loss(p, {"tokens": t}, cfg)))(params, tokens)
    return step, grads


def test_a_dense_configurations_programs_do_not_see_the_new_fields():
    dense = LlamaConfig.tiny()
    step, grads = dense_jaxprs(dense)
    flagged = dataclasses.replace(dense, experts_per_token=2,
                                  norm_topk_prob=True,
                                  router_aux_loss_coef=0.5)
    step2, grads2 = dense_jaxprs(flagged)
    assert str(step) == str(step2) and str(grads) == str(grads2)
    for text in (str(step), str(grads)):
        assert "ragged_dot" not in text and "top_k" not in text
    # the step returns ids and hidden states; the routers' load is None
    assert len(step.out_avals) == 2
    sparse_step, _ = dense_jaxprs(dataclasses.replace(
        dense, num_experts=4, experts_per_token=2))
    assert "ragged_dot" in str(sparse_step)
    assert len(sparse_step.out_avals) == 4


def test_the_tree_and_its_logical_axes(setup):
    _, cfg, params, _ = setup
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    for name in ("we_gate", "we_up"):
        assert axes["layers"][name] == (None, "expert", "embed", "mlp")
    assert axes["layers"]["we_down"] == (None, "expert", "mlp", "embed")
    assert "w_gate" not in params["layers"]
    assert sum(a.size for a in jax.tree.leaves(params)) == cfg.num_params()


def test_init_makes_no_float32_expert_stack():
    """At OLMoE's widths a float32 [16, 64, 2048, 1024] on the way to bf16
    is 8.6 GB: the stacks are drawn a layer at a time."""
    m = loader.load_config("olmoe-1b-7b-serve")
    cfg = family.build_config(m)
    jaxpr = jax.make_jaxpr(lambda k: init_llama(cfg, k))(jax.random.key(0))
    stack = (cfg.num_layers, cfg.num_experts)
    big = [v.aval for eqn in jaxpr.eqns for v in eqn.outvars
           if v.aval.shape[:2] == stack and len(v.aval.shape) == 4]
    assert big and all(a.dtype == jnp.bfloat16 for a in big)
    out = jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(out)) == family.num_params(m)
    assert {a.dtype for a in jax.tree.leaves(out)} == {jnp.dtype("bfloat16")}


# --------------------------------------------------------------------------
# the served class: prefill shapes of two buckets, the counters
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    m = tiny_model(program={"attn_impl": "reference", "dtype": "float32",
                            "param_dtype": "float32"})
    engine = {"lora_rank": 2, "max_batch_size": 2, "allowed_batch_sizes": [2],
              "max_new_tokens": 4, "seq_bucket": 16}
    gen = family.Served(**family.served_kwargs(m, engine, 3000000019))
    yield m, gen
    gen.engine.shutdown()


def test_two_buckets_compile_once_each_and_a_step_compiles_nothing(served):
    from benchmark.harness.onchip import count_compiles

    _, gen = served
    compiles = count_compiles()
    before = gen.compiled_step_programs()
    gen.warm_step_programs(16)
    gen.warm_step_programs(32)
    assert gen.compiled_step_programs() == before + 2
    warmed = len(compiles)
    states = [gen._prefill({"prompt": list(range(2, 12))}, ""), None]
    gen._step("", states)                      # bucket 16, one padded row
    states = [gen._prefill({"prompt": list(range(2, 12))}, ""),
              gen._prefill({"prompt": list(range(2, 22))}, "")]
    out = gen._step("", states)                # bucket 32
    assert all(0 <= tok < gen._cfg.vocab_size for tok, _ in out)
    assert len(compiles) == warmed
    assert gen.compiled_step_programs() == before + 2


def test_the_steps_counters(served):
    _, gen = served
    s0 = gen.engine_stats()
    states = [gen._prefill({"prompt": list(range(2, 12))}, ""), None]
    gen._step("", states)
    s1 = gen.engine_stats()
    cfg = gen._cfg
    assert s1["positions_computed"] - s0["positions_computed"] == 2 * 16
    assert s1["positions_live"] - s0["positions_live"] == 10
    mean = 10 * cfg.experts_per_token / cfg.num_experts * cfg.num_layers
    assert s1["expert_pairs_mean"] - s0["expert_pairs_mean"] == \
        pytest.approx(mean)
    fullest = s1["expert_pairs_fullest"] - s0["expert_pairs_fullest"]
    assert mean <= fullest <= 10 * cfg.num_layers
    # 4 bytes a row, and two float32 a layer of the routers' load
    assert s1["host_bytes"] - s0["host_bytes"] == 2 * 4 + 8 * cfg.num_layers


def test_served_logits_are_the_references(served):
    m, gen = served
    prompt = [3, 5, 7, 11, 13, 17, 19, 23, 29, 31]
    got = gen.last_position_logits(prompt)
    want = reference.last_logits(gen._params, jnp.asarray(prompt), m)
    assert got.shape == (m["vocab_size"],) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    state = gen._prefill({"prompt": prompt}, "")
    assert gen._step("", [state, None])[0][0] == int(got.argmax())


def test_each_step_runs_under_a_span_named_for_its_padded_length(served,
                                                                 monkeypatch):
    _, gen = served
    seen = []

    class Span:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    gen._step("", [gen._prefill({"prompt": list(range(2, 22))}, ""), None])
    assert seen == ["bench:len_32"]


# --------------------------------------------------------------------------
# the family module: refusals, counts by hand, no jax
# --------------------------------------------------------------------------
@pytest.mark.parametrize("key,value", [
    ("clip_qkv", 8.0), ("attention_bias", True),
    ("rope_scaling", {"type": "linear", "factor": 2.0}),
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("sliding_window", 4096), ("shared_expert_intermediate_size", 1024),
    ("num_experts_per_tok", 65)])
def test_the_family_refuses_what_the_program_does_not_compute(key, value):
    m = dict(loader.load_config("olmoe-1b-7b-serve"), **{key: value})
    with pytest.raises(ValueError, match=key):
        family.check(m)
    with pytest.raises(ValueError, match=key):
        family.build_config(m)


def test_the_configuration_is_the_published_one():
    cfg = loader.load_config("olmoe-1b-7b-serve")
    for key, value in PUBLISHED.items():
        assert cfg[key] == value and type(cfg[key]) is type(value), key
    assert cfg["reduced"] == [] and cfg["changed_from_source"] == {}
    assert cfg["head_dim"] == cfg["hidden_size"] // cfg["num_attention_heads"]
    assert cfg["family"] == "olmoe" and family.REFERENCE == "olmoe"
    assert loader.load_reference(cfg) is reference
    built = family.build_config(cfg)
    assert (built.num_experts, built.experts_per_token, built.mlp_hidden,
            built.qk_norm, built.norm_topk_prob, built.attn_impl) == (
        64, 8, 1024, True, False, "flash")
    assert built.param_dtype == jnp.bfloat16


def test_counts_by_hand():
    m = loader.load_config("olmoe-1b-7b-serve")
    attention = 4 * 2048 * 2048 + 2 * 2048          # projections, two norms
    experts = 64 * 3 * 2048 * 1024
    layer = attention + 2048 * 64 + experts + 2 * 2048
    assert family.layer_params(m) == layer == 419_569_664
    assert experts == 402_653_184
    total = 16 * layer + 2 * 50304 * 2048 + 2048
    assert family.num_params(m) == total == 6_919_161_856
    assert family.build_config(m).num_params() == total
    # a position meets 8 experts: 3 matmuls of 2048 x 1024, 2 FLOP each
    assert family.expert_ffn_flops(m, 1) == 16 * 8 * 3 * 2 * 2048 * 1024
    assert family.expert_ffn_flops(m, 1) / 16 == pytest.approx(100.66e6,
                                                               rel=1e-3)
    assert family.expert_ffn_flops(m, 9216) == 9216 * family.expert_ffn_flops(
        m, 1)
    # every expert's three matrices once, bf16: 805 MB a layer
    assert family.expert_ffn_bytes(m) == 16 * experts * 2
    assert family.expert_ffn_bytes(m) / 16 == pytest.approx(805.3e6, rel=1e-3)
    # training: 6 FLOP a weight a token meets, and causal attention
    met = 16 * (4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024) \
        + 50304 * 2048
    square = 16 * 7 * 2.0 * 16 * 128 * 4096 / 2
    assert family.train_flops_per_token(m, 4096) == 6.0 * met + square
    assert family.attention_kernel_flops(m, 2, 4096) == 2 * 4096 * square


def test_the_family_module_imports_no_jax(repo_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.harness import loader\n"
        "from benchmark.families import olmoe\n"
        "cell = loader.load_cell('serve_olmoe_chat')\n"
        "assert loader.load_family(cell['model']) is olmoe\n"
        "assert olmoe.num_params(cell['model']) > 6.9e9\n"
        "for m in loader.metrics_for_cell(cell): loader.load_reader(m)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % repo_root)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------------
# the cell's files and the readers of the metrics it brings
# --------------------------------------------------------------------------
def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell(CELL)
    steady = loader.load_cell("serve_chat_steady")
    # the engine is serve_chat_steady's but for the longest answer
    assert {k: v for k, v in cell["engine"].items()
            if k != "max_new_tokens"} == {
        k: v for k, v in steady["engine"].items() if k != "max_new_tokens"}
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 256, "sigma": 0.7, "min": 64,
                                 "max": 1024}
    assert mix["output_len"] == {"median": 24, "sigma": 0.5, "min": 8,
                                 "max": 64}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"]
    assert serve_driver.seq_buckets(cell) == list(range(128, 1153, 128))
    assert 0.4 <= mix["rate_per_s"] / mix["knee"]["rate_per_s"] <= 0.6
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    assert {"expert_matmul_sort_ms.serve", "expert_ffn_roofline_pct.serve",
            "expert_load_imbalance.serve"} <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    own = {m["name"] for m in loader.metrics_for_cell(steady)}
    assert not own & {"expert_matmul_sort_ms.serve", "expert_load_imbalance.serve"}


def view_of(ops, spans, stats):
    cell = loader.load_cell(CELL)
    return {"cell": cell, "peaks": peaks.peak("TPU v5 lite"),
            "trace": {"ops": ops, "host_spans": spans, "steps": 3},
            "obs": {"engine_stats_end": stats}}


def test_the_roofline_reader_counts_the_traced_steps_own_lengths():
    metrics = {m["name"]: m for m in loader.load_metric_files()}
    metric = metrics["expert_ffn_roofline_pct.serve"]
    read = loader.load_reader(metric)
    m = loader.load_config("olmoe-1b-7b-serve")
    ops = [("tpu_custom_call:ragged-dot-none.2", 0.100, 48),
           ("tpu_custom_call:ragged-dot-none", 0.080, 48),
           ("tpu_custom_call:checkpoint.7", 0.500, 48),   # the flash forward
           ("fusion.120", 0.300, 48)]
    spans = {"model_step": [0.9, 3], "len_128": [0.2, 2], "len_1152": [0.4, 1]}
    # 128: 8 x 128 positions need 0.52 ms of MXU a layer and 0.98 ms of
    # HBM (the weights bound it); 1152: 4.71 ms of MXU
    short = 16 * 805_306_368 / 819e9
    long = family.expert_ffn_flops(m, 8 * 1152) / 197e12
    assert short > family.expert_ffn_flops(m, 8 * 128) / 197e12
    assert long > short
    got = read(view_of(ops, spans, {}), metric)
    assert got == pytest.approx(100.0 * (2 * short + long) / 0.180)
    assert read(view_of(ops[2:], spans, {}), metric) is None   # no kernel
    assert read(view_of(ops, {"model_step": [0.9, 3]}, {}), metric) is None
    # the time a step of the layer's operations the trace can name
    ms = metrics["expert_matmul_sort_ms.serve"]
    assert loader.load_reader(ms)(view_of(ops, spans, {}), ms) == \
        pytest.approx(1e3 * 0.180 / 3)


def test_the_imbalance_reader_and_a_program_without_the_counters():
    metric = {m["name"]: m for m in loader.load_metric_files()}[
        "expert_load_imbalance.serve"]
    read = loader.load_reader(metric)
    stats = {"steps": 9, "expert_pairs_fullest": 180.0,
             "expert_pairs_mean": 120.0}
    assert read(view_of([], {}, stats), metric) == 1.5
    assert read(view_of([], {}, {"steps": 9}), metric) is None


# --------------------------------------------------------------------------
# run.py --rehearsal of the cell, in a process of its own
# --------------------------------------------------------------------------
@pytest.mark.slow  # a cluster in a subprocess, 20-30 s
@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(repo_root, manifest, trace):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONASYNCIODEBUG")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "5", "--trace", str(trace),
         "--rehearsal"], cwd=repo_root, env=env, capture_output=True,
        text=True, timeout=240)
    assert proc.returncode == 3, proc.stderr[-3000:]
    head = "[bench REHEARSAL] would-be last line: "
    found = [ln for ln in proc.stdout.splitlines() if ln.startswith(head)]
    assert len(found) == 1
    line = json.loads(found[0][len(head):])
    lastline.validate(line, manifest, CELL, bool(trace))
    assert line["attempted"] == 10 and line["failed"] == 0
    assert "NOT CORRECT" not in proc.stdout
    if trace:
        assert {"expert_matmul_sort_ms.serve", "expert_ffn_roofline_pct.serve",
                "expert_load_imbalance.serve"} <= set(line["metrics"])
        assert line["metrics"]["expert_load_imbalance.serve"]["value"] >= 1.0
