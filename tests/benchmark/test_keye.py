"""Keye-VL-2.0-30B-A3B's language model through the one block of
``models/llama.py`` against the plain float32 reference, tiny, on the CPU:
grouped-query attention (8 query heads on 2 key/value heads of 16, so
groups of 4 are told from groups of 2) whose query attends the 24 keys
that an indexer of 4 heads of 16 chooses, an RMSNorm a head, multi-axis
rope (``mrope_section [2, 3, 3]``), 16 experts 4 a token under a softmax
router; the decode through the held keys, values and index keys; three
position streams; the family module's checks and counts; the cell's files;
the readers of the metrics the cell brings; the two tools.

Both sides compute in float32 here, so they differ by the order of sums
alone and the tolerance is a few 1e-5 (``TIGHT``): any of the twelve faults
that ``reference/keye.py`` can plant moves the logits by thousands of
times that (the test of each says so). The choice is a step function of
the index scores, so where two scores lie within float32's rounding of
each other the two sides could choose apart: the seeds here have no such
pair among a query's 24th and 25th (the float32 test would say so). In
bf16 the program's logits lie some 0.02 to 0.06 from the reference's IN
THE MEAN at these sizes (``BF16_MEAN``: three layers whose every product is
rounded to 8 bits of mantissa, a choice and a router that flip near-ties),
which the next precision down (3 bits of mantissa passed off as bf16)
misses by twice and more, and float32's tolerance by a thousand.
Contexts are 64 to 160 against a ``topk`` of 24, so the choice bites in
every row and straddles the kernels' blocks of 128.
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

from benchmark.families import keye as family
from benchmark.harness import lastline, loader, peaks, tokengap
from benchmark.reference import keye as reference
from ray_tpu.models.llama import (
    LlamaConfig, _rope, init_decode_state, init_llama, llama_decode,
    llama_forward, llama_hidden, llama_logical_axes, llama_next_token)

CELL = "serve_keye_clipqa"
CONFIG = "keye-vl-2.0-30b-a3b-serve-l6"
TIGHT = dict(rtol=5e-5, atol=5e-5)
BF16_MEAN = 0.1
OWN = {"keye_indexer_ms.serve", "keye_indexer_roofline_pct.serve",
       "keye_sparse_flash_fwd_ms.serve",
       "keye_sparse_flash_fwd_roofline_pct.serve",
       "keye_index_keys_kept_pct.serve",
       "keye_expert_ffn_roofline_pct.serve",
       "keye_expert_matmul_sort_ms.serve",
       "keye_expert_load_imbalance.serve"}
# every fault the reference can plant, as its `logits` is told it
CONTROLS = {
    "the choice ignored": dict(selection=False),
    "topk halved": dict(topk=12),
    "the indexer's ReLU left out": dict(index_relu=False),
    "the indexer's heads' weights all 1": dict(index_weights=False),
    "the index key without its LayerNorm": dict(index_key_norm=False),
    "the indexer's rope left out": dict(index_rope=False),
    "the head norms left out": dict(head_norms=False),
    "theta 10 000": dict(theta=10000.0),
    "groups of 2 for 4": dict(group=2),
    "the chosen weights not renormalised": dict(renormalise=False),
    "3 experts a token for 4": dict(experts_per_token=3),
}


def published():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog beside the model-configs guide is not here")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")


def tiny_model(**over):
    """The rehearsal's sizes: hidden 64, 3 layers, 8 query heads on 2
    key/value heads of 16, 4 index heads of 16, topk 24, 16 experts of 32,
    4 a token, mrope_section [2, 3, 3]."""
    m = loader.load_cell(CELL, rehearsal=True)["model"]
    m = dict(m, program={"attn_impl": "reference", "dtype": "float32",
                         "param_dtype": "float32"})
    m.update(over)
    return m


def randomised(params, key):
    """Norm weights off 1 and the index key's bias off 0, so that a norm
    left out or misplaced shows."""
    def move(path, leaf):
        name = path[-1].key
        if name.endswith("_norm") or name == "wi_k_bias":
            return (1.0 if name.endswith("_norm") else 0.0) + 0.3 * \
                jax.random.normal(jax.random.fold_in(key, len(str(path))),
                                  leaf.shape, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(move, params)


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """The reference pads to 1024 and walks blocks of 256 queries for the
    chip's lengths; here 32 and 32."""
    monkeypatch.setattr(reference, "PAD_TO", 32)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 32)


@pytest.fixture(scope="module")
def setup():
    m = tiny_model()
    cfg = family.build_config(m)
    params = randomised(init_llama(cfg, jax.random.key(3)),
                        jax.random.key(5))
    tokens = jax.random.randint(jax.random.key(4), (2, 160), 0,
                                m["vocab_size"])
    return m, cfg, params, tokens


def reference_logits(params, tokens, m, **controls):
    return jnp.stack([reference.logits(params, row, m, **controls)
                      for row in tokens])


# --------------------------------------------------------------------------
# the configuration the family builds, the tree, the count
# --------------------------------------------------------------------------
def test_the_configuration_the_family_builds(setup):
    m, cfg, _, _ = setup
    assert cfg.layer_types == ("indexed_attention",) * 3
    assert cfg.layer_kinds() == ("chosen_routed",) * 3
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (8, 2, 16)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (
        4, 16, 24)
    assert cfg.mrope_section == (2, 3, 3) and cfg.rope_theta == 1e7
    assert cfg.qk_head_norm and not cfg.qk_norm
    assert cfg.router_scores == "softmax" and cfg.norm_topk_prob
    assert (cfg.num_experts, cfg.experts_per_token, cfg.mlp_hidden) == (
        16, 4, 32)
    assert cfg.num_shared_experts == 0 and not cfg.tie_embeddings
    full = family.build_config(loader.load_config(CONFIG))
    assert (full.num_layers, full.num_heads, full.num_kv_heads,
            full.head_dim, full.hidden) == (6, 32, 4, 128, 2048)
    assert (full.index_heads, full.index_head_dim, full.index_topk) == (
        16, 64, 2048)
    assert full.mrope_section == (16, 24, 24) and full.attn_impl == "flash"
    assert full.dtype == full.param_dtype == jnp.bfloat16


def test_the_defaults_leave_every_other_model_as_it_was():
    cfg = LlamaConfig()
    assert cfg.mrope_section == () and cfg.index_topk == 0
    assert "chosen" not in "".join(cfg.layer_kinds())
    with pytest.raises(ValueError, match="layer_types"):
        LlamaConfig(num_layers=1, layer_types=("indexed",)).layer_kinds()


def test_the_tree_its_logical_axes_and_its_count(setup):
    m, cfg, params, _ = setup
    layers = params["layers"]
    shapes = {k: v.shape for k, v in layers.items()}
    assert shapes["wq"] == (3, 64, 8, 16) and shapes["wk"] == (3, 64, 2, 16)
    assert shapes["q_norm"] == shapes["k_norm"] == (3, 16)
    assert shapes["wi_q"] == (3, 64, 4, 16) and shapes["wi_k"] == (3, 64, 16)
    assert shapes["wi_k_norm"] == shapes["wi_k_bias"] == (3, 16)
    assert shapes["wi_w"] == (3, 64, 4)
    assert shapes["router"] == (3, 64, 16)
    assert shapes["we_gate"] == (3, 16, 64, 32)
    axes = llama_logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(
        a, tuple)) == jax.tree.structure(params)
    assert axes["layers"]["wi_q"] == (None, "embed", None, None)
    count = sum(leaf.size for leaf in jax.tree.leaves(params))
    assert count == cfg.num_params() == family.num_params(m)


def test_the_table_of_the_issue():
    """ISSUE 62's table, part by part."""
    m = loader.load_config(CONFIG)
    parts = family.part_params(m)
    assert parts == {"attention": 18_874_624, "indexer": 2_261_120,
                     "routed": 262_144 + 603_979_776, "norms": 4_096}
    assert sum(parts.values()) == 625_381_760
    assert family.num_params(m) == 4_374_622_464
    assert family.build_config(m).num_params() == 4_374_622_464
    uncut = dict(m, num_hidden_layers=48)
    assert family.num_params(uncut) == 30_640_656_384
    active = family.part_params(m, active=True)
    assert sum(active.values()) == 59_150_720       # 59.2 M a layer


def test_counts_by_hand():
    m = loader.load_config(CONFIG)
    step = {"rows": 3, "positions_live": 8000 + 6000 + 4000,
            "attention_keys": 18000,
            "attention_pairs": sum(n * (n + 1) // 2
                                   for n in (8000, 6000, 4000))}
    # six layers, eight experts a position, three matmuls of 2048 x 768
    assert family.expert_ffn_flops(m, 1000) == 6 * 1000 * 8 * 3 * 2 * 2048 \
        * 768
    assert family.expert_ffn_bytes(m) == 6 * 128 * 3 * 2048 * 768 * 2
    assert family.expert_ffn_bytes(m, 10) == 10 * 3 * 2048 * 768 * 2
    # 16 heads x 64 x 2 FLOP a causal pair; the float32 score of each
    assert family.index_scores_flops(m, step) == 6 * step[
        "attention_pairs"] * 2 * 16 * 64
    assert family.index_scores_bytes(m, step) == 6 * (
        18000 * (2 * 16 * 64 + 4 * 16) + 18000 * 2 * 64
        + step["attention_pairs"] * 4)
    # the kept pairs: sum_t min(t, 2048) a row, each row past 2048
    kept = sum(n * 2048 - 2048 * 2047 // 2 for n in (8000, 6000, 4000))
    assert family.kept_pairs(step, 2048) == kept
    assert family.sparse_flash_flops(m, step) == 6 * kept * 32 * 128 * 4
    assert family.sparse_flash_bytes(m, step) == 6 * 2 * 128 * (
        2 * 32 * 18000 + 2 * 4 * 18000)
    # a share of a roofline over the kept pairs cannot pass one over the
    # causal pairs
    assert kept < step["attention_pairs"]


# --------------------------------------------------------------------------
# multi-axis rope
# --------------------------------------------------------------------------
def test_rope_with_three_streams_by_hand():
    """The formula written out: pair i of a head of 16 turns by the
    temporal stream for i in 0..1, by the height for 2..4, by the width
    for 5..7, at theta ** (-2 i / 16), rotate-half."""
    key = jax.random.key(2)
    x = jax.random.normal(key, (2, 5, 3, 16))
    positions = jax.random.randint(jax.random.fold_in(key, 1), (3, 2, 5), 0,
                                   900)
    got = np.asarray(_rope(x, positions, 1e7, (2, 3, 3)))
    want = np.zeros_like(got)
    xs, ps = np.asarray(x, np.float64), np.asarray(positions)
    stream = [0, 0, 1, 1, 1, 2, 2, 2]
    for b in range(2):
        for s in range(5):
            for i in range(8):
                a = ps[stream[i], b, s] * 1e7 ** (-2 * i / 16)
                x1, x2 = xs[b, s, :, i], xs[b, s, :, i + 8]
                want[b, s, :, i] = x1 * np.cos(a) - x2 * np.sin(a)
                want[b, s, :, i + 8] = x2 * np.cos(a) + x1 * np.sin(a)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # three equal streams are one stream, to the bit, whatever the sections
    one = positions[0]
    np.testing.assert_array_equal(
        _rope(x, jnp.stack([one] * 3), 1e7, (2, 3, 3)), _rope(x, one, 1e7))
    np.testing.assert_array_equal(_rope(x, one, 1e7, (2, 3, 3)),
                                  _rope(x, one, 1e7))
    with pytest.raises(ValueError, match="mrope_section"):
        _rope(x, positions, 1e7, (2, 3, 2))
    with pytest.raises(ValueError, match="mrope_section"):
        _rope(x, positions, 1e7)


def test_three_unequal_streams_against_the_reference(setup):
    m, cfg, params, tokens = setup
    at = jnp.arange(160)
    # a clip's grid: the frame, the row and the column of a position
    streams = jnp.stack([at // 35, at % 35 // 7, at % 7 * 3])
    got = llama_hidden(params, tokens, cfg,
                       positions=jnp.stack([streams] * 2, axis=1))
    want = jnp.stack([reference.hidden_states(
        params, row, m, positions=streams) for row in tokens])
    np.testing.assert_allclose(got, want, **TIGHT)
    # two streams changed places are another model
    other = jnp.stack([reference.hidden_states(
        params, row, m, positions=streams, swap_streams=True)
        for row in tokens])
    assert float(jnp.abs(other - want).max()) > 0.1


def test_three_equal_streams_are_the_plain_positions_to_the_bit(setup):
    _, cfg, params, tokens = setup
    at = jnp.broadcast_to(jnp.arange(160), (2, 160))
    np.testing.assert_array_equal(
        llama_hidden(params, tokens, cfg, positions=jnp.stack([at] * 3)),
        llama_hidden(params, tokens, cfg))
    np.testing.assert_array_equal(
        llama_hidden(params, tokens, cfg, positions=at),
        llama_hidden(params, tokens, cfg))


def test_mrope_takes_no_scaling_beside_it(setup):
    from ray_tpu.models.llama import RopeScaling

    _, cfg, params, tokens = setup
    for change in (dict(partial_rotary_factor=0.5),
                   dict(rope_scaling=RopeScaling(factor=4.0))):
        with pytest.raises(ValueError, match="mrope_section"):
            llama_hidden(params, tokens[:, :32],
                         dataclasses.replace(cfg, **change))


# --------------------------------------------------------------------------
# the forward against the reference
# --------------------------------------------------------------------------
def test_logits_agree_with_the_reference_in_float32(setup):
    m, cfg, params, tokens = setup
    np.testing.assert_allclose(llama_forward(params, tokens, cfg),
                               reference_logits(params, tokens, m), **TIGHT)


def test_logits_agree_with_the_reference_in_bf16(setup):
    """bf16 against float32: the mean difference is what is held (module
    docstring), float32's tolerance is missed by a thousand, and the
    precision below bf16 misses bf16's by twice and more."""
    m, cfg, params, tokens = setup
    want = reference_logits(params, tokens, m)
    low = dataclasses.replace(cfg, dtype=jnp.bfloat16,
                              param_dtype=jnp.bfloat16)
    half = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    got = llama_forward(half, tokens, low)
    off = float(jnp.mean(jnp.abs(got - want)))
    assert 50 * TIGHT["atol"] < off < BF16_MEAN
    rounded = tokengap.to_mantissa_bits(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), 3)
    lower = float(jnp.mean(jnp.abs(
        llama_forward(rounded, tokens, low) - want)))
    assert lower > 2 * off


@pytest.mark.parametrize("length", [128, 256])
def test_the_kernels_path_is_the_reference_path(setup, length):
    """attn_impl flash: the index-score kernel and the equal-width forward
    under the choice, interpreted, told the rows' lengths through the
    served step and not."""
    m, cfg, params, _ = setup
    tokens = jax.random.randint(jax.random.key(6), (2, length), 0,
                                m["vocab_size"])
    flash = dataclasses.replace(cfg, attn_impl="flash")
    want = reference_logits(params, tokens, m)
    np.testing.assert_allclose(llama_forward(params, tokens, flash), want,
                               **TIGHT)
    # the served step: one whole row, one that ends inside a block
    lengths = np.array([length, length - 70])
    live = jnp.arange(length)[None, :] < lengths[:, None]
    ids, _, load = llama_next_token(params, tokens, jnp.asarray(lengths - 1),
                                    flash, live=live)
    best = [int(want[b, lengths[b] - 1].argmax()) for b in range(2)]
    assert np.asarray(ids).tolist() == best
    # what the choice kept: min(t + 1, 24) a live query (ties apart)
    floor = sum(n * 24 - 24 * 23 // 2 for n in lengths)
    kept = np.asarray(load["index_kept"])
    assert kept.shape == (3,) and (kept >= floor).all()
    assert (kept < 1.05 * floor).all()


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_fault_fails_the_tolerance(setup, control):
    """Each fault moves the logits by thousands of times the tolerance
    that the sound program meets."""
    m, cfg, params, tokens = setup
    got = llama_forward(params, tokens[:1], cfg)
    faulty = reference_logits(params, tokens[:1], m, **CONTROLS[control])
    assert float(jnp.abs(got - faulty).max()) > 1000 * TIGHT["atol"]
    assert float(jnp.mean(jnp.abs(got - faulty))) > 100 * TIGHT["atol"]


def test_the_served_step_and_remat_compute_the_same(setup):
    _, cfg, params, tokens = setup
    want = llama_forward(params, tokens, cfg)
    for remat_policy in ("dots", "full"):
        other = dataclasses.replace(cfg, remat=True,
                                    remat_policy=remat_policy)
        np.testing.assert_allclose(llama_forward(params, tokens, other), want,
                                   rtol=1e-5, atol=1e-5)


def test_no_backward_under_a_choice_on_the_kernels_path(setup):
    from ray_tpu.models.llama import llama_loss

    _, cfg, params, tokens = setup
    batch = {"inputs": tokens[:, :128], "targets": tokens[:, 1:129]}
    flash = dataclasses.replace(cfg, attn_impl="flash")
    with pytest.raises(NotImplementedError,
                       match="attn_impl='reference'"):
        jax.grad(lambda p: llama_loss(p, batch, flash))(params)
    # the reference path trains
    grads = jax.grad(lambda p: llama_loss(p, batch, cfg))(params)
    assert np.isfinite(float(jnp.abs(grads["layers"]["wi_q"]).max()))


# --------------------------------------------------------------------------
# decode through the held keys, values and index keys
# --------------------------------------------------------------------------
@pytest.mark.parametrize("prefill, chunk", [(10, 1), (64, 1), (40, 9)])
def test_decode_through_the_state_is_the_full_forward(setup, prefill, chunk):
    """A prompt shorter than topk 24 and one longer, then token by token
    (and in chunks of 9) well past it: the choice over the held index keys.
    Logits, not tokens."""
    m, cfg, params, _ = setup
    total = prefill + (6 * chunk if chunk > 1 else 40)
    tokens = jax.random.randint(jax.random.key(8), (2, total), 0,
                                m["vocab_size"])
    want = reference_logits(params, tokens, m)
    state = init_decode_state(cfg, 2, total)
    assert len(state) == 3
    for keys, values, index_keys in state:
        assert keys.shape == values.shape == (2, total, 2, 16)
        assert index_keys.shape == (2, total, 16)
    decode = jax.jit(lambda p, t, st, at: llama_decode(p, t, cfg, st, at))
    got, at = [], 0
    for n in [prefill] + [chunk] * ((total - prefill) // chunk):
        logits, state = decode(params, tokens[:, at:at + n], state,
                               jnp.int32(at))
        got.append(logits)
        at += n
    assert at == total
    np.testing.assert_allclose(jnp.concatenate(got, axis=1), want, **TIGHT)


def test_decode_takes_three_streams(setup):
    m, cfg, params, tokens = setup
    at = jnp.arange(48)
    streams = jnp.stack([at // 9, at % 9 // 3, at % 3 * 5])
    want = reference.logits(params, tokens[0, :48], m, positions=streams)
    state = init_decode_state(cfg, 1, 48)
    got = []
    for lo, hi in ((0, 30), (30, 48)):
        logits, state = llama_decode(
            params, tokens[:1, lo:hi], cfg, state, jnp.int32(lo),
            positions=streams[:, None, lo:hi])
        got.append(logits[0])
    np.testing.assert_allclose(jnp.concatenate(got), want, **TIGHT)


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


def test_the_served_class_counts_what_the_indexer_kept(served):
    from ray_tpu.ops.pallas import flash_attention as fa

    m, gen = served
    prompt = list(range(3, 133))                     # 130 positions
    tokens = list(gen({"prompt": prompt, "max_new": 3}))
    assert len(tokens) == 3
    stats = gen.engine_stats()
    assert stats["layer_kinds"] == {"chosen_routed": 3}
    assert stats["positions_computed"] == 3 * 2 * 256
    lengths = (130, 131, 132)
    assert stats["index_keys_seen"] == 3 * sum(
        n * (n + 1) // 2 for n in lengths)
    floor = 3 * sum(n * 24 - 24 * 23 // 2 for n in lengths)
    assert floor <= stats["index_keys_kept"] < 1.05 * floor
    assert stats["window_keys_kept"] == stats["window_keys_seen"] == 0
    # under a choice the forward skips no block but those past a row's
    # end: 3 layers at 8 heads over the causal walk
    tiles = fa.flash_tiles(256, 256, head_dim=16)
    run = live = 0
    for n in lengths:
        r, own = fa.causal_blocks(256, np.array([n, 0]), tiles)
        run += 3 * 8 * r
        live += 3 * 8 * own
    assert stats["attn_blocks_run"] == run > 0
    assert stats["attn_blocks_live"] == live > 0
    assert stats["attn_blocks_skipped"] == run - live
    assert stats["flash_blocks_run"] == 0
    assert stats["expert_pairs_all"] == sum(lengths) * 3 * 4
    # the tokens are the reference's own first choices
    rows = reference.logits(gen._params, jnp.asarray(prompt + tokens[:-1]), m)
    assert tokens == np.asarray(rows[129:132].argmax(-1)).tolist()
    # an adapter reaches the projections
    adapted = list(gen({"prompt": prompt, "max_new": 2, "adapter": "a1"}))
    assert len(adapted) == 2


# --------------------------------------------------------------------------
# the family module
# --------------------------------------------------------------------------
@pytest.mark.parametrize("change, match", [
    (dict(attention_bias=True), "attention_bias True"),
    (dict(hidden_act="gelu"), "hidden_act 'gelu'"),
    (dict(decoder_sparse_step=2), "decoder_sparse_step 2"),
    (dict(mlp_only_layers=[0]), "mlp_only_layers"),
    (dict(use_sliding_window=True), "use_sliding_window True"),
    (dict(sliding_window=4096), "sliding_window 4096"),
    (dict(num_local_experts=64), "num_local_experts repeats"),
    (dict(num_key_value_heads=5), "whole groups"),
    (dict(num_experts_per_tok=0), "1..num_experts"),
    (dict(sa_config={"topk": 2048}), "sa_config"),
    (dict(rope_scaling=None), "rope_scaling None"),
    (dict(q_lora_rank=1536), r"does not understand \['q_lora_rank'\]"),
])
def test_the_family_refuses_what_the_program_does_not_compute(change, match):
    with pytest.raises(ValueError, match=match):
        family.check(dict(loader.load_config(CONFIG), **change))


@pytest.mark.parametrize("group, change, match", [
    ("sa_config", dict(indexer_num_kv_heads=2), "ONE key a position"),
    ("sa_config", dict(topk=0), "topk counts keys"),
    ("rope_scaling", dict(mrope_section=[16, 24, 16]), "three counts"),
    ("rope_scaling", dict(rope_type="yarn"), "rope_type and type"),
])
def test_the_family_refuses_another_indexer_or_rope(group, change, match):
    m = loader.load_config(CONFIG)
    with pytest.raises(ValueError, match=match):
        family.check(dict(m, **{group: dict(m[group], **change)}))


def test_a_file_that_lacks_a_key_is_refused():
    lacking = {k: v for k, v in loader.load_config(CONFIG).items()
               if k != "sa_config"}
    with pytest.raises(ValueError, match=r"lacks \['sa_config'\]"):
        family.check(lacking)


def test_the_parent_fails_on_the_cell_within_seconds(repo_root, tmp_path):
    """This PR's benchmark files over a program whose configuration has no
    multi-axis rope: ``run.py`` exits at once and says so (the driver tries
    each new cell on the parent first, and a parent that hangs there
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
        "    mrope_section: Tuple[int, ...] = ()\n", ""))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    t = time.time()
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "1", "--seconds", "5", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=60)
    assert time.time() - t < 30
    assert proc.returncode not in (0, 3)
    assert "LlamaConfig has no ['mrope_section']" in proc.stderr


def test_the_configuration_keeps_every_published_number():
    m = loader.load_config(CONFIG)
    row = published()
    assert m["source"] == row["source_url"]
    assert m["reduced"] == ["num_hidden_layers"]
    assert m["changed_from_source"] == {
        "num_hidden_layers": {"source": 48, "here": 6}}
    for key, value in row["config"].items():
        assert m[key] == (6 if key == "num_hidden_layers" else value), key
    assert set(m) - set(row["config"]) == {
        "name", "source", "family", "reduced", "changed_from_source",
        "assumed", "program", "deployment", "notes"}
    assert m["sa_config"] == row["config"]["sa_config"]
    assert m["rope_scaling"] == row["config"]["rope_scaling"]
    assert (m["vocab_size"], m["rope_theta"]) == (151936, 10000000)
    assert m["program"] == {"attn_impl": "flash", "dtype": "bfloat16",
                            "param_dtype": "bfloat16"}
    said = " ".join(m["assumed"])
    for item in ("no network", "vision tower", "Qwen3MoeConfig",
                 "RMSNorm over each head", "no bias anywhere", "softmax",
                 "intermediate_size 6144", "max_window_layers 48",
                 "lightning indexer", "topk counts KEYS",
                 "q_chunk_size and kv_chunk_size", "no q_lora_rank",
                 "LayerNorm", "Hadamard", "the whole 64",
                 "temporal stream", "apply_multimodal_rotary_pos_emb",
                 "rotate-half", "random from --seed",
                 "a random 2048 of a query's causal keys"):
        assert item in said, item
    assert "eight-stage pipeline" in m["deployment"]
    assert "layers 0-5" in m["deployment"]
    notes = " ".join(m["notes"])
    assert "4 374 622 464" in notes and "30 640 656 384" in notes
    assert "710 MFLOP" in notes


def test_the_family_module_imports_no_jax(repo_root):
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark.families import keye as f; "
            "from benchmark.harness import loader; "
            "m = loader.load_config(%r); f.check(m); "
            "print(f.num_params(m)); "
            "assert 'jax' not in sys.modules, 'jax was imported'"
            % (repo_root, CONFIG))
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip() == "4374622464"


# --------------------------------------------------------------------------
# the cell's files
# --------------------------------------------------------------------------
def test_the_cells_files(manifest):
    from benchmark.drivers import serve as serve_driver
    from ray_tpu.ops.pallas import flash_attention as fa
    from ray_tpu.ops.pallas import grouped_matmul as gm
    from ray_tpu.ops.pallas import index_scores as ix

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
    assert mix["prompt_len"] == {"median": 6144, "sigma": 0.3, "min": 3584,
                                 "max": 8160}
    assert mix["output_len"] == {"median": 8, "sigma": 0.5, "min": 4,
                                 "max": 16}
    assert cell["engine"]["max_new_tokens"] == mix["output_len"]["max"] == 16
    # five programs; a context never passes 8192; every prompt is longer
    # than the indexer's topk, so the choice bites in every request
    assert serve_driver.seq_buckets(cell) == [4096, 5120, 6144, 7168, 8192]
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] <= 8192
    assert mix["prompt_len"]["min"] > cell["model"]["sa_config"]["topk"]
    # two numbers, either of which refuses: the mean gap, and the share of
    # tokens off the reference's best, which parts the weakest controls
    # from the sound runs by more than the mean does (check.why)
    assert list(cell["check"]["limits"]) == ["gap_mean", "off_best_share"]
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
        CONFIG, "clip_context_short_answers", 1)
    assert len(listed["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    assert config["reduced"] == cell["model"]["reduced"]
    assert config["file"] == f"benchmark/configs/{CONFIG}.json"
    assert config["source"] == cell["model"]["source"]
    assert manifest["configs"][-1] is config
    assert manifest["workloads"][-1] is listed
    own = [m for m in manifest["per_layer"] if m["name"] in OWN]
    assert {m["name"] for m in own} == OWN and len(own) == len(OWN)
    assert own == manifest["per_layer"][-len(OWN):]
    for metric in own:
        assert metric["moves"] == "serve_gap_p95_ms"
        assert metric["workloads"] == [CELL]
    # every metric that lists the serving cells lists this one, last
    serving = [m for g in ("end_to_end", "per_layer") for m in manifest[g]
               if "serve_chat_steady" in m.get("workloads", ())]
    assert serving and all(m["workloads"][-1] == CELL for m in serving)
    # the plain rule's tiles under the choice, the index kernel's tiles
    for seq in serve_driver.seq_buckets(cell):
        assert fa.flash_tiles(seq, seq, head_dim=128) == (1024, 1024)
        assert ix.index_tiles(seq) == (256, 512)
    # the grouped matmuls' tiles at the smallest and the largest step
    for rows in (4 * 4096 * 8, 4 * 8192 * 8):
        assert gm.gmm_tiles(rows, 2048, 768, stacks=2) == (128, 768)
        assert gm.gmm_tiles(rows, 768, 2048) == (256, 2048)


def view_of(ops, records, stats):
    return {"cell": loader.load_cell(CELL),
            "peaks": peaks.peak("TPU v5 lite"),
            "trace": {"ops": ops, "step_records": records,
                      "steps": len(records)},
            "obs": {"engine_stats_end": stats}}


def test_the_readers_tell_the_kernels_apart():
    """Each new metric reads its own kernel's time and the family's need
    for it; a share of a roofline stays under 100 at the least time."""
    cell = loader.load_cell(CELL)
    m = cell["model"]
    rows, length = 4, 8192
    record = {"start_s": 0.0, "end_s": 1.0, "rows": rows,
              "positions_computed": rows * length,
              "positions_live": rows * length,
              "attention_keys": rows * length,
              "attention_pairs": rows * length * (length + 1) // 2,
              "experts_met": None}
    pk = peaks.peak("TPU v5 lite")
    least = {
        "index": max(
            family.index_scores_flops(m, record) / pk["bf16_flops_per_s"],
            family.index_scores_bytes(m, record) / pk["hbm_bytes_per_s"]),
        "flash": max(
            family.sparse_flash_flops(m, record) / pk["bf16_flops_per_s"],
            family.sparse_flash_bytes(m, record) / pk["hbm_bytes_per_s"]),
        "experts": max(
            family.expert_ffn_flops(m, rows * length)
            / pk["bf16_flops_per_s"],
            family.expert_ffn_bytes(m) / pk["hbm_bytes_per_s"])}
    # at the chip's peaks the MXU binds all three: a pair's 2 048 FLOP of
    # index products are 10.4 ps, the 4 bytes of its float32 score 4.9
    assert family.index_scores_flops(m, record) / pk["bf16_flops_per_s"] \
        > family.index_scores_bytes(m, record) / pk["hbm_bytes_per_s"]
    ops = [("tpu_custom_call:index_scores.3", 2 * least["index"], 6),
           ("tpu_custom_call:flash_fwd_chosen.5", 4 * least["flash"], 6),
           ("tpu_custom_call:flash_fwd_selected.5", 99.0, 6),
           ("tpu_custom_call:checkpoint.7", 99.0, 6),
           ("tpu_custom_call:ragged-dot-none-pallas.9",
            5 * least["experts"], 12),
           ("sort.4", 0.001, 18), ("fusion.11", 99.0, 40)]
    view = view_of(ops, [record], {
        "index_keys_seen": 1000, "index_keys_kept": 440,
        "expert_pairs_fullest": 15.0, "expert_pairs_mean": 10.0})
    got = {}
    for metric in loader.metrics_for_cell(cell):
        if metric["name"] in OWN:
            got[metric["name"]] = loader.load_reader(metric)(view, metric)
    assert set(got) == OWN
    assert got["keye_indexer_ms.serve"] == pytest.approx(
        2e3 * least["index"])
    assert got["keye_indexer_roofline_pct.serve"] == pytest.approx(50.0)
    assert got["keye_sparse_flash_fwd_ms.serve"] == pytest.approx(
        4e3 * least["flash"])
    assert got["keye_sparse_flash_fwd_roofline_pct.serve"] == \
        pytest.approx(25.0)
    assert got["keye_expert_ffn_roofline_pct.serve"] == pytest.approx(20.0)
    assert got["keye_expert_matmul_sort_ms.serve"] == pytest.approx(
        1e3 * (5 * least["experts"] + 0.001))
    assert got["keye_index_keys_kept_pct.serve"] == pytest.approx(44.0)
    assert got["keye_expert_load_imbalance.serve"] == pytest.approx(1.5)
    # a program without the kernels (the parent's) reads nothing, and
    # does not raise
    bare = view_of([("fusion.1", 1.0, 1)], [record], {})
    for metric in loader.metrics_for_cell(cell):
        if metric["name"] in OWN:
            assert loader.load_reader(metric)(bare, metric) is None


def test_the_cells_step_holds_the_kernels_under_their_scopes():
    from tests.benchmark.test_deepseek_v2 import program_text

    text = program_text(CELL, "step4096")
    assert text.count("name=flash_attention_selected") == 1  # one run of 6
    assert "name=flash_attention " not in text
    # reference attention's scores would be [8, 32, 4096, 4096], the
    # einsums' index products [8, 16, 4096, 4096]
    assert "8,32,4096,4096" not in text and "8,16,4096,4096" not in text
    # the choice reaches the kernel as a byte a pair, the keys at their 4
    # heads, never repeated
    assert "i8[8,4096,4096]" in text and "f32[8,4096,4096]" in text
    assert "bf16[8,32,4096,128]" in text and "bf16[8,4,4096,128]" in text
    assert "bf16[8,16,4096,64]" in text


def test_the_probe_rehearses(capsys, tmp_path):
    from benchmark.tools import keye_probe

    out = tmp_path / "probe.jsonl"
    rc = keye_probe.check_probe.main([
        "--workload", CELL, "--seeds", "1", "--control-seeds", "1",
        "--requests", "2", "--rehearsal", "--out", str(out)])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    controls = set(keye_probe.check_probe.CONTROLS["keye"]) | {
        "mantissa_3_bits"}
    assert len(controls) == 12
    assert controls | {"program", "tokens_shifted", "tokens_stale"} \
        <= set(line)
    # float32 on the CPU: the program's tokens are the reference's own
    assert line["program"]["gap_mean"] == 0.0
    assert line["program"]["correct"]
    told = [name for name in controls if not line[name]["correct"]]
    # tokens do not tell every control at these sizes: the logits do, in
    # test_a_fault_fails_the_tolerance
    assert len(told) >= 6
    assert not line["tokens_shifted"]["correct"]
    assert not line["tokens_stale"]["correct"]


def test_the_probe_reads_the_named_controls_alone(monkeypatch):
    from benchmark.tools import keye_probe

    probe = keye_probe.check_probe
    monkeypatch.setitem(probe.CONTROLS, "keye", dict(probe.CONTROLS["keye"]))
    seen = {}
    monkeypatch.setattr(probe, "main", lambda argv: seen.update(
        argv=argv, controls=list(probe.CONTROLS["keye"])) or 0)
    assert keye_probe.main([
        "--workload", CELL, "--controls", "topk_halved,index_relu_left_out",
        "--seeds", "2"]) == 0
    assert seen == {"argv": ["--workload", CELL, "--seeds", "2"],
                    "controls": ["topk_halved", "index_relu_left_out"]}
    with pytest.raises(KeyError):
        keye_probe.main(["--workload", CELL, "--controls", "no_such_fault"])


# --------------------------------------------------------------------------
# the indexer's kernels at their outputs (tools/keye_select_check.py)
# --------------------------------------------------------------------------
def select_check(capsys, *argv):
    from benchmark.tools import keye_select_check

    rc = keye_select_check.main(["--rehearsal", *argv])
    return rc, [json.loads(ln) for ln in
                capsys.readouterr().out.strip().splitlines()]


def test_the_select_check_rehearses(capsys, tmp_path):
    out = tmp_path / "lines" / "select.jsonl"
    rc, lines = select_check(capsys, "--seeds", "1", "--out", str(out))
    assert rc == 0 and len(lines) == 1
    assert lines == [json.loads(ln) for ln in out.read_text().splitlines()]
    line = lines[0]
    assert line["sound_ok"] and line["faults_told"] and line["rehearsal"]
    assert (line["rows"], line["length"], line["heads"], line["kv_heads"],
            line["index_heads"], line["index_head_dim"], line["topk"]) == (
                4, 256, 8, 2, 4, 16, 24)
    assert line["choice_wrong"] == 0
    assert max(line["scores"], line["sound"], line["sound_told"]) \
        < line["tolerance"] < line["off_over"] < min(
            line["one_key_fewer"], line["one_key_more"],
            line["choice_ignored"], line["groups_of_4"])


def test_the_select_check_tells_a_choice_one_key_off(capsys, monkeypatch):
    """The fault planted in the choice itself: `k + 1` keys for `k`."""
    from ray_tpu.models import llama

    sound = llama._chosen_keys
    monkeypatch.setattr(llama, "_chosen_keys",
                        lambda scores, seen, k: sound(scores, seen, k + 1))
    rc, (line,) = select_check(capsys, "--seeds", "1")
    assert rc == 1 and not line["sound_ok"]
    assert line["choice_wrong"] > 0


def test_the_select_check_measures_on_a_chip_alone():
    from benchmark.tools import keye_select_check

    with pytest.raises(SystemExit, match="no chip"):
        keye_select_check.main(["--seeds", "1"])


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
