"""granite-4.0-h-micro through the one block of ``models/llama.py`` against
the plain float32 reference, tiny, on the CPU: Mamba-2 mixers whose scan
runs in chunks SHORTER than the lengths tested (128 against 200 to 384
positions, lengths that are not whole chunks among them, so that the state
crosses edges and a ragged last chunk bites) beside grouped-query attention
without rope, the four scalar multipliers, the tied head; the decode
through the mixer's state; the family module's checks and counts; the
cell's files and the readers it brings.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerances are 1e-5 of logits of order 1: each control (the
state dropped at the chunks' edges, ``D x`` left out, the taps' bias left
out, the norm before the gate, ``residual_multiplier`` at 1,
``attention_multiplier`` at ``head_dim ** -0.5``, rope left on) moves the
logits by hundreds to ten thousands of times that, as the test beside the
logits' shows, and the reason each is there is written beside it.
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

from benchmark.families import granite_hybrid as family
from benchmark.harness import lastline, loader, peaks
from benchmark.reference import granite_hybrid as reference
from ray_tpu.models.llama import (
    LlamaConfig, init_decode_state, init_llama, llama_decode, llama_forward,
    llama_logical_axes, llama_loss, llama_next_token)

CELL = "serve_granite_toolcalls"
CONFIG = "granite-4.0-h-micro-serve"
TIGHT = dict(rtol=1e-5, atol=1e-5)
MAMBA, ATTENTION = "mamba", "attention"
# config.json of ibm-granite/granite-4.0-h-micro, as the catalog beside the
# model-configs guide reads it (row granite-4.0-h-micro, `config`)
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ([MAMBA] * 5 + [ATTENTION] + ([MAMBA] * 9 + [ATTENTION])
                    * 3 + [MAMBA] * 4),
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


def tiny_model(**over):
    """The rehearsal's sizes (4 layers, the second attention; 4 mixer heads
    of 32 over a state of 16, chunks of 128), computed in float32 by the
    reference path."""
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, program={"attn_impl": "reference", "dtype": "float32",
                         "param_dtype": "float32"})
    m.update(over)
    return m


def randomised(params, key):
    """Norm gains off 1 and ``D`` off 1, so that a norm left out or
    misplaced, or a skip scaled wrongly, shows; and the mixers' steps
    longer and their decays slower than the initialiser's (``dt`` some 0.1
    to 0.5, ``A`` 1 to 2.3), so that at these 16 state dims the state that
    crosses a chunk's edge carries as much of an output as it does at the
    published 128."""
    def moved(path, a):
        name = path[-1].key
        k = jax.random.fold_in(key, sum(map(ord, str(path))))
        if name.endswith("_norm"):
            return 1.0 + 0.3 * jax.random.normal(k, a.shape)
        if name == "mamba_d":
            return 1.0 + 0.2 * jax.random.normal(k, a.shape)
        if name == "mamba_dt_bias":
            return a * 0.3
        if name == "mamba_a_log":
            return a * 0.3
        return a
    return jax.tree_util.tree_map_with_path(moved, params)


@pytest.fixture(scope="module")
def setup():
    # (a softmax scale of 1 at these heads of 16, a power of two from
    # head_dim ** -0.5 as the published one is: at the published 1/64 so
    # narrow a head's scores are flat and attention tells nothing)
    m = tiny_model(attention_multiplier=1.0)
    cfg = family.build_config(m)
    # (the embedding at the scale the family serves it at: logits of
    # order 1, which the tolerances are set by)
    params = randomised(family.with_unit_input(
        init_llama(cfg, jax.random.key(13)), cfg.embedding_multiplier),
        jax.random.key(5))
    tokens = jax.random.randint(jax.random.key(4), (2, 200), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


# --------------------------------------------------------------------------
# the configuration, the tree and its count
# --------------------------------------------------------------------------
def test_the_configuration_the_family_builds(setup):
    m, cfg, _, _ = setup
    assert cfg.layer_kinds() == ("mamba_dense", "attention_dense",
                                 "mamba_dense", "mamba_dense")
    assert cfg.layer_runs() == (("mamba_dense", 0, 1),
                                ("attention_dense", 0, 1),
                                ("mamba_dense", 1, 2))
    assert (cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_state,
            cfg.mamba_conv_kernel, cfg.mamba_chunk) == (4, 32, 16, 4, 128)
    assert cfg.mamba_widths() == (128, 160, 128 + 160 + 4)
    assert (cfg.head_dim, cfg.num_heads, cfg.num_kv_heads) == (16, 4, 2)
    assert not cfg.use_rope and cfg.tie_embeddings
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling, cfg.attention_multiplier) == (
        12.0, 0.22, 8.0, 1.0)
    assert cfg.num_experts == 0 and cfg.mlp_hidden == 96
    # the published model: nine runs of like layers
    whole = family.build_config(loader.load_config(CONFIG))
    assert [n for _, _, n in whole.layer_runs()] == [5, 1, 9, 1, 9, 1, 9, 1,
                                                     4]
    assert whole.kind_counts() == {"mamba_dense": 36, "attention_dense": 4}
    assert whole.mamba_widths() == (4096, 4352, 8512)
    assert (whole.head_dim, whole.mamba_chunk) == (64, 256)
    # the defaults leave every other model as it was
    plain = LlamaConfig()
    assert plain.use_rope and plain.mamba_heads == 0
    assert (plain.embedding_multiplier, plain.residual_multiplier,
            plain.logits_scaling, plain.attention_multiplier) == (
        1.0, 1.0, 1.0, 0.0)


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(params) == jax.tree.structure(
        axes, is_leaf=lambda x: isinstance(x, tuple))
    assert set(params["layers"]) == {"mamba_dense", "attention_dense"}
    assert "lm_head" not in params                           # tied
    mixer = params["layers"]["mamba_dense"]
    assert mixer["mamba_in"].shape == (3, 64, 292)
    assert mixer["mamba_conv_w"].shape == (3, 160, 4)
    assert mixer["mamba_conv_b"].shape == (3, 160)
    assert (mixer["mamba_dt_bias"].shape == mixer["mamba_a_log"].shape
            == mixer["mamba_d"].shape == (3, 4))
    assert mixer["mamba_norm"].shape == (3, 128)
    assert mixer["mamba_out"].shape == (3, 128, 64)
    assert mixer["w_gate"].shape == (3, 64, 96)
    assert not [k for k in mixer if k in ("wq", "conv_in", "router")]
    attention = params["layers"]["attention_dense"]
    assert attention["wq"].shape == (1, 64, 4, 16)
    assert not [k for k in attention if k.startswith("mamba_")
                or k in ("q_norm", "k_norm")]
    total = sum(a.size for a in jax.tree.leaves(params))
    assert total == cfg.num_params() == family.num_params(m)
    # the initialiser's ranges, which set how far a state remembers
    fresh = init_llama(cfg, jax.random.key(2))["layers"]["mamba_dense"]
    a = np.exp(np.asarray(fresh["mamba_a_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(fresh["mamba_dt_bias"])))  # softplus
    assert 0.00099 <= dt.min() and dt.max() <= 0.1001
    assert np.all(np.asarray(fresh["mamba_d"]) == 1.0)
    for leaf in ("mamba_conv_w", "mamba_conv_b"):
        w = np.asarray(fresh[leaf])
        assert 0.3 < np.abs(w).max() <= 0.5


# --------------------------------------------------------------------------
# program against reference
# --------------------------------------------------------------------------
def test_logits_agree_with_the_reference(setup):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens, cfg)
    for b in range(tokens.shape[0]):
        np.testing.assert_allclose(
            got[b], reference.logits(params, tokens[b], m), **TIGHT)


def test_the_kernels_path_is_the_reference_path(setup):
    """Both kernels, interpreted, inside the whole forward at 384
    positions, three chunks of 128: what the chip's path computes. The
    rows are padded on the right to 300 and 77 of their own tokens (no
    whole chunks), as a serving step pads them, and the served step's
    token is the reference's."""
    m, cfg, params, _ = setup
    tokens = jax.random.randint(jax.random.key(6), (2, 384), 2,
                                m["vocab_size"])
    lengths = (300, 77)
    tokens = jnp.where(jnp.arange(384)[None] < jnp.array(lengths)[:, None],
                       tokens, 0)
    flash = dataclasses.replace(cfg, attn_impl="flash")
    got = llama_forward(params, tokens, flash)
    ids, _, load = llama_next_token(
        params, tokens, jnp.array(lengths, jnp.int32) - 1, flash)
    assert load is None
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
    assert off > 100 * 1e-5


# Each control is one of the check's on the chip (tools/granite_probe.py)
# or a fault this family's equations invite; here, at 200 positions
# against chunks of 128, each moves the logits by 0.01 to 0.5 where the
# program lies 2e-7 from the reference.
@pytest.mark.parametrize("control, why", [
    (dict(drop_state_every=128),
     "a kernel that loses its state between grid steps computes every "
     "chunk from zeros: right up to the first edge, wrong after it"),
    (dict(skip=False),
     "D x is added outside the recurrence and easy to leave in the kernel's "
     "caller or out of both"),
    (dict(conv_bias=False),
     "the only bias of the model; the short convolution the taps are shared "
     "with has none"),
    (dict(gate_first=False),
     "Mamba-2's own gated norm gates after the norm; granitemoehybrid's "
     "gates before it"),
    (dict(residual=1.0),
     "every other model adds a sub-layer's output at weight 1"),
    (dict(attention_scale=16 ** -0.5),
     "every other model's softmax scale is head_dim ** -0.5, the kernels' "
     "own"),
    (dict(rope=True),
     "every other attention layer of the block is rotated"),
])
def test_a_fault_fails_the_tolerance(setup, control, why):
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens[:1], cfg)[0]
    assert float(jnp.abs(got - reference.logits(params, tokens[0], m)
                         ).max()) < 1e-5
    faulty = reference.logits(params, tokens[0], m, **control)
    assert float(jnp.abs(got - faulty).max()) > 100 * 1e-5, (control, why)
    if "drop_state_every" in control:  # sound up to the first edge
        np.testing.assert_allclose(got[:128], faulty[:128], **TIGHT)


def test_the_embedding_and_the_logits_are_scaled(setup):
    m, cfg, params, tokens = setup
    want = llama_forward(params, tokens[:1], cfg)[0]
    unscaled = llama_forward(params, tokens[:1], dataclasses.replace(
        cfg, logits_scaling=1.0))[0]
    np.testing.assert_allclose(unscaled / 8.0, want, rtol=1e-6, atol=1e-6)
    plain = llama_forward(params, tokens[:1], dataclasses.replace(
        cfg, embedding_multiplier=1.0))[0]
    assert float(jnp.abs(plain - want).max()) > 100 * 1e-5
    # the loss reads the scaled logits, chunked or not
    batch = {"tokens": tokens}
    whole = llama_loss(params, batch, cfg)
    lp = jax.nn.log_softmax(llama_forward(params, tokens[:, :-1], cfg))
    by_hand = -jnp.take_along_axis(lp, tokens[:, 1:, None], axis=-1).mean()
    np.testing.assert_allclose(whole, by_hand, rtol=1e-5)
    chunked = llama_loss(params, {"tokens": tokens[:, :161]},
                         dataclasses.replace(cfg, loss_chunk=32))
    np.testing.assert_allclose(
        chunked, llama_loss(params, {"tokens": tokens[:, :161]}, cfg),
        rtol=1e-5)


def test_the_served_step_and_remat_compute_the_same(setup):
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    for remat_policy in ("dots", "full", "mixed:2"):
        other = dataclasses.replace(cfg, remat=True,
                                    remat_policy=remat_policy)
        np.testing.assert_allclose(llama_forward(params, tokens, other), want,
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# decode through the mixer's state
# --------------------------------------------------------------------------
@pytest.mark.parametrize("impl, prefill, chunk", [
    ("reference", 150, 1), ("reference", 1, 1), ("reference", 90, 37),
    # the prompt's pass through the kernel: 300 positions are two chunks
    # and a ragged third, whose padding must leave the FINAL state alone
    ("flash", 300, 1), ("flash", 129, 64)])
def test_decode_through_the_state_is_the_full_forward(setup, impl, prefill,
                                                      chunk):
    m, cfg, params, _ = setup
    total = prefill + (3 * chunk if chunk > 1 else 12)
    tokens = jax.random.randint(jax.random.key(8), (2, total), 0,
                                m["vocab_size"])
    want = llama_forward(params, tokens, cfg)
    cfg = dataclasses.replace(cfg, attn_impl=impl)
    state = init_decode_state(cfg, 2, total)
    mixer_state = state[0]
    assert mixer_state[0].shape == (2, 3, 160)       # the taps' last rows
    assert mixer_state[1].shape == (2, 4, 32, 16)    # the scan's state
    assert mixer_state[1].dtype == jnp.float32
    assert len(state[1]) == 2 and state[1][0].shape == (2, total, 2, 16)
    got, at = [], 0
    for n in [prefill] + [chunk] * ((total - prefill) // chunk):
        logits, state = llama_decode(params, tokens[:, at:at + n], cfg,
                                     state, jnp.int32(at))
        got.append(logits)
        at += n
    assert at == total
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, **TIGHT)
    assert state[0][1].dtype == jnp.float32


# --------------------------------------------------------------------------
# the served class and its counters
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served():
    m = tiny_model(final_norm_signs=True)
    gen = family.Served(**family.served_kwargs(m, dict(
        lora_rank=4, max_batch_size=2, allowed_batch_sizes=[2],
        max_new_tokens=4, seq_bucket=128), 12))
    yield m, gen
    gen.engine.shutdown()


def test_the_served_class_counts_the_scans_chunks(served):
    m, gen = served
    gain = np.asarray(gen._params["final_norm"])
    assert set(np.unique(gain)) == {-1.0, 1.0}       # signs from the seed
    prompt = list(range(3, 133))                     # 130: a chunk and 2
    tokens = list(gen({"prompt": prompt, "max_new": 3}))
    assert len(tokens) == 3
    stats = gen.engine_stats()
    assert set(gen.STEP_COUNTERS) <= set(stats)
    # three steps at a bucket of 256: 3 mixers x 2 rows x 2 chunks run, of
    # which the one live row's two hold its tokens
    assert stats["positions_computed"] == 3 * 2 * 256
    assert stats["ssm_chunks_run"] == 3 * 3 * 2 * 2
    assert stats["ssm_chunks_live"] == 3 * 3 * 2
    assert stats["layer_kinds"] == {"mamba_dense": 3, "attention_dense": 1}
    assert stats["expert_pairs_skipped"] == 0 == stats["index_keys_seen"]
    # the tokens are the reference's own first choices
    rows = reference.logits(gen._params, jnp.asarray(prompt + tokens[:-1]), m)
    assert tokens == np.asarray(rows[129:132].argmax(-1)).tolist()
    # a model without the operator counts none
    from ray_tpu.serve.llm import LlamaGenerator
    assert "ssm_chunks_run" in LlamaGenerator.STEP_COUNTERS
    assert "ssm_chunks_live" in LlamaGenerator.engine_stats.__doc__


# --------------------------------------------------------------------------
# the family module
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(num_local_experts=8), "num_local_experts 8"),
    (dict(num_experts_per_tok=2), "num_experts_per_tok 2"),
    (dict(mamba_n_groups=8), "mamba_n_groups 8"),
    (dict(mamba_conv_bias=False), "mamba_conv_bias False"),
    (dict(mamba_proj_bias=True), "mamba_proj_bias True"),
    (dict(position_embedding_type="rope"), "position_embedding_type 'rope'"),
    (dict(attention_bias=True), "attention_bias True"),
    (dict(hidden_act="gelu"), "hidden_act 'gelu'"),
    (dict(normalization_function="layernorm"), "normalization_function"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
    (dict(time_step_limit=[0.0, 0.1]),
     r"does not understand \['time_step_limit'\]"),
    (dict(sliding_window=4096), r"does not understand \['sliding_window'\]"),
    (dict(layer_types=[MAMBA] * 4), "layer_types names 4 layers"),
    (dict(layer_types=[MAMBA] * 39 + ["conv"]), r"layer_types \['conv'\]"),
    (dict(mamba_expand=4), "mamba_expand x hidden_size"),
    (dict(mamba_chunk_size=200), "mamba_chunk_size a multiple of 128"),
    (dict(num_key_value_heads=5), "share the key/value heads evenly"),
    (dict(logits_scaling=0), "logits_scaling divides"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    m = dict(loader.load_config(CONFIG), **change)
    with pytest.raises(ValueError, match=match):
        family.check(m)


def test_a_file_that_lacks_a_key_is_refused():
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "mamba_d_state"}
    with pytest.raises(ValueError, match=r"lacks \['mamba_d_state'\]"):
        family.check(lacking)


def test_a_checkout_without_the_fields_is_refused_at_once(monkeypatch):
    fields = {f.name for f in dataclasses.fields(LlamaConfig)}
    assert family._config_fields() == fields
    assert (set(family.MODEL_KEYS.values()) | set(family.BUILT)) <= fields
    monkeypatch.setattr(family, "_config_fields", lambda: fields - {
        "mamba_heads", "use_rope", "residual_multiplier"})
    with pytest.raises(ValueError, match=r"LlamaConfig has no \['mamba_heads'"
                                         r", 'residual_multiplier', 'use_rop"):
        family.check(loader.load_config(CONFIG))
    monkeypatch.undo()
    monkeypatch.setattr(family.LlamaGenerator, "STEP_COUNTERS",
                        ("host_bytes", "expert_pairs_all"))
    with pytest.raises(ValueError, match="counts no chunks of a state-space"):
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
    gone = ("mamba_heads", "mamba_head_dim", "mamba_state",
            "mamba_conv_kernel", "mamba_chunk", "use_rope",
            "embedding_multiplier", "residual_multiplier", "logits_scaling",
            "attention_multiplier")
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


def test_the_configuration_is_the_published_one_whole():
    m = loader.load_config(CONFIG)
    assert m["source"] == ("https://huggingface.co/ibm-granite/"
                           "granite-4.0-h-micro/blob/main/config.json")
    assert m["reduced"] == [] and m["changed_from_source"] == {}
    for key, value in PUBLISHED.items():
        assert m[key] == value, key
    assert set(m) - set(PUBLISHED) == {
        "name", "source", "family", "final_norm_signs", "reduced",
        "changed_from_source", "assumed", "program", "deployment", "notes"}
    assert m["final_norm_signs"] is True
    assert m["program"] == {"attn_impl": "flash", "dtype": "bfloat16",
                            "param_dtype": "bfloat16"}
    said = " ".join(m["assumed"])
    for item in ("A_log = log(U(1, 16))", "0.001 to 0.1", "1e-4", "D ones",
                 "(1/4) ** 0.5", "No clamp on dt", "BEFORE the norm",
                 "no routed part", "final_norm_signs"):
        assert item in said, item


def test_counts_by_hand():
    m = loader.load_config(CONFIG)
    in_proj = 2048 * 8512
    assert 8512 == 4096 + 4352 + 64 and 4352 == 4096 + 2 * 1 * 128
    mixer = in_proj + (4352 * 4 + 4352) + 3 * 64 + 4096 + 4096 * 2048
    swiglu = 3 * 2048 * 8192
    attention = 2048 * 32 * 64 + 2 * 2048 * 8 * 64 + 32 * 64 * 2048
    assert (in_proj, mixer, swiglu, attention) == (
        17_432_576, 25_847_232, 50_331_648, 10_485_760)
    mamba_layer = mixer + swiglu + 2 * 2048
    attention_layer = attention + swiglu + 2 * 2048
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    total = (36 * mamba_layer + 4 * attention_layer + 100_352 * 2048 + 2048)
    assert total == 3_191_396_096 == family.num_params(m)
    assert family.build_config(m).num_params() == total
    assert round(total * 2 / 1e9, 2) == 6.38
    assert family.part_params(m) == {"mamba": mixer, "attention": attention,
                                     "dense": swiglu}
    # the scan's need a live position a layer: 4.26 MFLOP, 17 152 bytes
    assert family.scan_flops_a_position(m) == 2 * (
        64 * 64 * (256 + 2 * 128) + 128 * 256) == 4_259_840
    step = {"positions_live": 1000, "rows": 8}
    assert family.ssd_scan_flops(m, step) == 36 * 1000 * 4_259_840
    assert family.ssd_scan_bytes(m, step) == 36 * 1000 * 17_152
    assert 17_152 == 2 * (2 * 4096 + 2 * 128) + 4 * 64
    # the flash forward at serve_lfm2_rag's geometry, over 4 layers
    assert family.flash_fwd_pair_flops(m, 10) == 4 * 10 * 2 * 2 * 32 * 64
    assert family.flash_fwd_row_bytes(m, 3, 5) == 4 * 2 * 64 * (
        2 * 32 * 3 + 2 * 8 * 5)
    # a position's FLOPs in the layers: 6.12 GFLOP (ISSUE 46 reckoned
    # 5.97), a Mamba layer 157 MFLOP of it, its mixer 56
    layers = (36 * (2 * (in_proj + 4096 * 2048 + swiglu) + 4_259_840)
              + 4 * 2 * (attention + swiglu))
    assert round(layers / 1e9, 2) == 6.12
    assert round((2 * (in_proj + 4096 * 2048) + 4_259_840) / 1e6) == 56


def test_the_family_module_imports_no_jax(repo_root):
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from benchmark.harness import loader\n"
        "from benchmark.families import granite_hybrid\n"
        "cell = loader.load_cell('serve_granite_toolcalls')\n"
        "assert granite_hybrid.num_params(cell['model']) > 3e9\n"
        "for m in loader.metrics_for_cell(cell): loader.load_reader(m)\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n" % repo_root)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# --------------------------------------------------------------------------
# the cell's files and its metrics
# --------------------------------------------------------------------------
OWN = {"granite_ssd_scan_ms.serve", "granite_ssd_scan_roofline_pct.serve",
       "granite_flash_fwd_d64_ms.serve",
       "granite_flash_fwd_d64_roofline_pct.serve",
       "granite_ssm_chunks_live_pct.serve"}


def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver

    cell = loader.load_cell(CELL)
    lfm2 = loader.load_cell("serve_lfm2_rag")
    # the engine is the other serving cells' but for the bucket: a bucket
    # is whole chunks
    assert {k: v for k, v in cell["engine"].items() if k != "seq_bucket"} \
        == {k: v for k, v in lfm2["engine"].items() if k != "seq_bucket"}
    assert cell["engine"]["seq_bucket"] == 256 == \
        cell["model"]["mamba_chunk_size"]
    mix = cell["traffic"]
    assert mix["generator"] == "open_loop_lognormal"
    assert mix["prompt_len"] == {"median": 320, "sigma": 0.6, "min": 128,
                                 "max": 976}
    assert mix["output_len"] == {"median": 16, "sigma": 0.5, "min": 8,
                                 "max": 48}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 48
    # four buckets; a context never passes 1024
    assert serve_driver.seq_buckets(cell) == [256, 512, 768, 1024]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] == 1024
    assert list(cell["check"]["limits"]) == ["gap_mean"]
    assert all(0 < limit < 1 for limit in cell["check"]["limits"].values())
    assert mix["rate_per_s"] / mix["knee"]["rate_per_s"] == \
        pytest.approx(0.6, abs=0.02)
    names = {m["name"] for m in loader.metrics_for_cell(cell)}
    assert OWN <= names
    assert names == set(lastline.required_metrics(manifest, CELL, True))
    assert not OWN & {m["name"] for m in loader.metrics_for_cell(lfm2)}
    listed = loader.manifest_cell(manifest, CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        CONFIG, "tool_calls_short_turns", 1)
    # the cell's own entries, each found by its name: where they lie in
    # their lists and what else the manifest holds is not this test's
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == []
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
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


def test_the_readers_tell_the_two_kernels_apart():
    metrics = {m["name"]: m for m in loader.load_metric_files()}
    m = loader.load_config(CONFIG)
    ops = [("tpu_custom_call:ssd_scan.16", 0.200, 36),
           ("tpu_custom_call:checkpoint.10", 0.020, 4),
           # another model's kernels are neither
           ("tpu_custom_call:flash_fwd_shared_rope.9", 0.050, 4),
           ("fusion.120", 0.300, 48), ("convolution.4", 0.600, 80)]
    records = [whole_rows(8, 700)] * 3 + [whole_rows(5, 1000)]
    view = view_of(ops, records, {"ssm_chunks_run": 400,
                                  "ssm_chunks_live": 300})

    def value(name):
        return loader.load_reader(metrics[name])(view, metrics[name])

    assert value("granite_ssd_scan_ms.serve") == pytest.approx(50.0)
    assert value("granite_flash_fwd_d64_ms.serve") == pytest.approx(5.0)
    want = sum(max(family.ssd_scan_flops(m, r) / 197e12,
                   family.ssd_scan_bytes(m, r) / 819e9) for r in records)
    assert value("granite_ssd_scan_roofline_pct.serve") == pytest.approx(
        100.0 * want / 0.200)
    want = sum(max(
        family.flash_fwd_pair_flops(m, r["attention_pairs"]) / 197e12,
        family.flash_fwd_row_bytes(m, r["positions_live"],
                                   r["attention_keys"]) / 819e9)
        for r in records)
    assert value("granite_flash_fwd_d64_roofline_pct.serve") == \
        pytest.approx(100.0 * want / 0.020)
    assert value("granite_ssm_chunks_live_pct.serve") == pytest.approx(75.0)
    # no such kernel in the trace, no traced step, or a program that counts
    # no chunks (the parent): None, no raise
    for name in OWN - {"granite_ssm_chunks_live_pct.serve"}:
        metric = metrics[name]
        read = loader.load_reader(metric)
        assert read(view_of(ops[2:], records, {}), metric) is None, name
        if name.endswith("roofline_pct.serve"):
            assert read(view_of(ops, [], {}), metric) is None, name
    live = metrics["granite_ssm_chunks_live_pct.serve"]
    assert loader.load_reader(live)(view_of(ops, records, {}), live) is None
    assert loader.load_reader(live)(view_of(ops, records, {
        "ssm_chunks_run": 0, "ssm_chunks_live": 0}), live) is None
    # serve_lfm2_rag's flash metric is not bound to this cell
    assert CELL not in metrics["flash_fwd_d64_ms.serve"]["cells"]
    assert metrics["granite_flash_fwd_d64_ms.serve"]["match"] == \
        metrics["flash_fwd_d64_ms.serve"]["match"]


def test_the_cells_step_holds_the_kernels_and_no_decay_tensor():
    from tests.benchmark.test_deepseek_v2 import program_text

    text = program_text(CELL, "step1024")
    # nine runs of like layers: five scans of mixers, four of attention
    assert text.count("name=_kernel_scan") == 5
    assert text.count("name=flash_attention") == 4
    assert text.count("pallas_call[") == 9
    assert "Ref{bf16[1,256,512]}" in text       # a chunk of 8 heads of x
    assert "Ref{f32[1,8,128,64]}" in text       # their states in and out
    # the decays of a head and chunk live in the kernel alone: no tensor
    # of [.., 256, 256] a head (or a chunk) is an operand or a result of
    # anything outside it
    outside = re.sub(r"Ref\{[^}]*\}", "", text)
    assert not re.search(r"\[8,\d+,\d+,256,256\]", outside)
    assert not re.search(r"\[8,\d+,256,256\]", outside)
    assert "8,64,1024,1024" not in outside and "8,32,1024,1024" not in outside
    # no rope: nothing takes a sine
    assert not re.search(r"\bsin\b", text)
    # the step's tokens alone come back: a dense model has no load
    assert "ragged_dot" not in text


# --------------------------------------------------------------------------
# tools/granite_scan_check.py: the kernel against the recurrence, which on
# the chip holds what served tokens do not tell (the state at the edges)
# --------------------------------------------------------------------------
def scan_check(capsys, *argv):
    from benchmark.tools import granite_scan_check

    rc = granite_scan_check.main(["--rehearsal", *argv])
    return rc, [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]


def test_the_scan_check_rehearses(capsys, tmp_path):
    out = tmp_path / "lines" / "scan.jsonl"
    rc, lines = scan_check(capsys, "--seeds", "2", "--out", str(out))
    assert rc == 0 and len(lines) == 2
    assert lines == [json.loads(ln) for ln in out.read_text().splitlines()]
    assert lines[0]["seed"] != lines[1]["seed"]
    for line in lines:
        assert line["ok"] and line["rehearsal"] and line["platform"] == "cpu"
        # the rehearsal's sizes: two chunks of 128, one edge a row
        assert (line["rows"], line["length"], line["chunk"],
                line["edges_a_row"]) == (8, 256, 128, 1)
        assert line["kernel"] == "ssd_scan"
        tol = line["tolerance"]
        assert line["sound"]["outputs_off"] < tol
        assert line["sound"]["final_state_off"] < tol
        assert line["state_dropped"]["up_to_the_first_edge_off"] < tol
        assert line["state_dropped"]["after_it_off"] > line["dropped_over"]


def test_the_scan_check_tells_a_kernel_that_loses_its_state(capsys,
                                                            monkeypatch):
    """The fault planted in the kernel itself: every chunk from zeros."""
    from ray_tpu.ops.pallas import ssd_scan as kernel

    sound = kernel.ssd_scan_chunked

    def loses_its_state(x, dt, a, b, c, d, h0, chunk):
        parts = [sound(x[:, s:s + chunk], dt[:, s:s + chunk], a,
                       b[:, s:s + chunk], c[:, s:s + chunk], d, h0, chunk)
                 for s in range(0, x.shape[1], chunk)]
        return jnp.concatenate([y for y, _ in parts], axis=1), parts[-1][1]

    monkeypatch.setattr(kernel, "ssd_scan_chunked", loses_its_state)
    rc, (line,) = scan_check(capsys, "--seeds", "1")
    assert rc == 1 and not line["ok"]
    assert line["sound"]["outputs_off"] > line["dropped_over"]
    assert line["state_dropped"]["up_to_the_first_edge_off"] < \
        line["tolerance"]


def test_the_scan_check_measures_on_a_chip_alone():
    from benchmark.tools import granite_scan_check

    with pytest.raises(SystemExit, match="no chip"):
        granite_scan_check.main(["--seeds", "1"])


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
