"""A serving step reads a partial run's layers where they lie in their
stack (``models/llama.py::_hidden_and_books`` under ``in_place``, which
``llama_next_token`` asks for and ``llama_loss`` does not): on a patterned
toy whose dense kind lies in three runs and whose routed kind lies in two
(another dense kind in two, another routed kind in one whole run), with
and without adapters,

(a) the step's jaxpr holds no top-level ``slice`` of a leaf of
    ``params["layers"]`` or of the adapters' stacks (the loss's holds one a
    leaf a partial run);
(b) the step's ids and hidden states are the sliced form's to the bit (the
    sliced form is ``_hidden_and_books`` as the loss calls it), and a
    per-layer Python loop's to a float32's last places;
(c) ``jax.grad(llama_loss)`` is what a per-layer Python loop over
    ``cfg.layer_places()`` gives, to the bit;
(d) the CPU's compiled step reads no layer of an expert stack by
    ``dynamic-slice``: ``expert_ffn`` reads them through ``moe.in_stack``,
    and the layer the scan's body indexes for form's sake compiles away.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import moe
from ray_tpu.models.llama import (
    LlamaConfig, LoraConfig, _by_kind, _embed, _hidden_and_books, _layer,
    _lm_head, _nll_from_logits, _rms_norm, init_llama, init_lora,
    llama_head, llama_loss, llama_next_token)

# dense: conv 2 + 1 + 1 (three runs), attention 1 + 1 (two);
# routed: conv 2 + 1 (two runs), attention 1 (its whole stack)
LAYERS = ("conv", "conv", "full_attention", "conv", "full_attention", "conv",
          "conv", "conv", "full_attention", "conv")
E, H, M = 8, 128, 256
B, S = 2, 16


def toy(kernel: bool = True) -> LlamaConfig:
    """On the lane grid the repo's grouped-matmul kernel (interpreted)
    multiplies the experts; off it (an expert's width of 192) XLA's
    ``ragged_dot`` does, over the whole stack's groups."""
    return LlamaConfig(
        vocab_size=256, hidden=H, mlp_hidden=M if kernel else 192,
        num_layers=len(LAYERS), num_heads=4, num_kv_heads=2, head_dim=32,
        max_seq_len=64, remat=False, attn_impl="reference",
        dtype=jnp.float32, param_dtype=jnp.float32, num_experts=E,
        experts_per_token=2, router_scores="sigmoid", router_bias=True,
        norm_topk_prob=True, router_norm_eps=1e-20, layer_types=LAYERS,
        num_dense_layers=6, dense_mlp_hidden=384, qk_head_norm=True,
        tie_embeddings=True)


LCFG = LoraConfig(rank=4, targets=("wq", "wv", "w_up"))


def drawn(cfg, with_lora):
    params = init_llama(cfg, jax.random.key(0))
    lora = None
    if with_lora:
        lora = init_lora(cfg, LCFG, jax.random.key(1))
        # B starts at zeros, where an adapter adds nothing
        lora = jax.tree.map(
            lambda a: a if a.any() else 0.05 * jax.random.normal(
                jax.random.key(2), a.shape, a.dtype), lora)
    tokens = jax.random.randint(jax.random.key(3), (B, S), 0, cfg.vocab_size)
    live = jnp.arange(S)[None, :] < jnp.array([[S], [S - 5]])
    last = jnp.array([S - 1, S - 6], jnp.int32)
    return params, lora, tokens, last, live


def step(cfg, with_lora):
    lcfg = LCFG if with_lora else None
    return lambda p, lo, t, i, on: llama_next_token(
        p, t, i, cfg, lora=lo, lora_cfg=lcfg, live=on)


def sliced_step(cfg, with_lora):
    """``llama_next_token`` over the sliced form: the parent's program."""
    lcfg = LCFG if with_lora else None

    def fn(p, lo, t, i, on):
        x, _ = _hidden_and_books(p, t, cfg, lora=lo, lora_cfg=lcfg,
                                 router_mask=on)
        rows = jnp.take_along_axis(x, i[:, None, None], axis=1)[:, 0]
        return jnp.argmax(llama_head(p, rows, cfg), -1).astype(jnp.int32), x
    return fn


def by_layer(params, lora, tokens, cfg, lcfg, mask=None):
    """Hidden states and each layer's books by a Python loop over
    ``cfg.layer_places()``: a layer's leaves are ``stack[j]``."""
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                                 tokens.shape)
    x = _embed(params, tokens, cfg)
    stacks = _by_kind(params["layers"], cfg)
    lo_stacks = _by_kind(lora["layers"], cfg) if lora is not None else {}
    books = []
    for kind, j in cfg.layer_places():
        lp, lo_j = jax.tree.map(lambda a: a[j],
                                (stacks[kind], lo_stacks.get(kind) or {}))
        if "router" in lp:
            lp = moe.in_stack(lp, stacks[kind], j, mask, skip_unmasked=True)
        x, _, b = _layer(cfg, x, lp, positions, lora=lo_j,
                         lora_scale=lcfg.scale if lcfg else 0.0,
                         operator=kind.split("_")[0], live=mask)
        if b is not None:
            books.append(b)
    return _rms_norm(x, params["final_norm"], cfg.rms_eps), books


def loop_loss(params, lora, batch, cfg, lcfg):
    """``llama_loss`` with the layers by ``by_layer``."""
    inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    x, books = by_layer(params, lora, inputs, cfg, lcfg)
    logits = jnp.einsum("bsh,hv->bsv", x, _lm_head(params).astype(cfg.dtype))
    ce = jnp.mean(_nll_from_logits(logits, targets))
    joined = {k: jnp.stack([b[k] for b in books]) for k in books[0]}
    return ce + cfg.router_aux_loss_coef * moe.load_balancing_loss(joined,
                                                                   cfg)


def sliced_stacks(fn, stacks, *args):
    """The top-level ``slice`` equations of ``fn(*args)``'s jaxpr whose
    operand is a leaf of ``stacks``, which is a part of ``args``."""
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    flat = jax.tree.leaves(args)
    assert len(flat) == len(jaxpr.invars)
    mine = {id(a) for a in jax.tree.leaves(stacks)}
    found = {v for v, a in zip(jaxpr.invars, flat) if id(a) in mine}
    assert len(found) == len(mine)
    return [e for e in jaxpr.eqns
            if e.primitive.name == "slice" and e.invars[0] in found]


WITH_LORA = pytest.mark.parametrize("with_lora", [False, True],
                                    ids=["base", "lora"])


def test_the_toys_runs():
    runs = toy().layer_runs()
    of = {kind: [n for k, _, n in runs if k == kind]
          for kind in toy().kind_counts()}
    assert of == {"conv_dense": [2, 1, 1], "attention_dense": [1, 1],
                  "conv_routed": [2, 1], "attention_routed": [1]}


@WITH_LORA
def test_the_step_slices_no_stack(with_lora):
    cfg = toy()
    args = params, lora, tokens, last, live = drawn(cfg, with_lora)
    # neither a layer stack nor an adapter stack
    assert sliced_stacks(step(cfg, with_lora), (params["layers"], lora),
                         *args) == []
    # the sliced form: every leaf of a kind in partial runs, once a run
    # (the routed attention layer is its whole stack and is not sliced)
    stacks = _by_kind(params["layers"], cfg)
    sliced = sliced_stacks(sliced_step(cfg, with_lora), params["layers"],
                           *args)
    assert len(sliced) == sum(
        len(stacks[kind]) for kind, _, n in cfg.layer_runs()
        if n != cfg.kind_counts()[kind])
    # and so does the loss, which keeps it
    assert len(sliced_stacks(
        lambda p, lo, t: llama_loss(
            p, {"tokens": t}, cfg, lora=lo,
            lora_cfg=LCFG if with_lora else None),
        params["layers"], params, lora, tokens)) == len(sliced)


@WITH_LORA
@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
def test_the_step_is_the_sliced_forms_to_the_bit(with_lora, kernel):
    cfg = toy(kernel)
    params, lora, tokens, last, live = drawn(cfg, with_lora)
    ids, hidden, load = jax.jit(step(cfg, with_lora))(
        params, lora, tokens, last, live)
    want_ids, want_hidden = jax.jit(sliced_step(cfg, with_lora))(
        params, lora, tokens, last, live)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_array_equal(hidden, want_hidden)
    # the four routed layers' books, over the rows' own 27 positions
    np.testing.assert_array_equal(load["mean"], [27 * 2 / E] * 4)
    # and a per-layer loop's where the rows' own positions lie
    loop, _ = jax.jit(lambda p, lo, t, on: by_layer(
        p, lo, t, cfg, LCFG if with_lora else None, on))(
            params, lora, tokens, live)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(hidden)[on], np.asarray(loop)[on],
                               rtol=2e-5, atol=2e-5)
    if with_lora:   # the adapters are in it
        bare = jax.jit(sliced_step(cfg, False))(params, None, tokens, last,
                                                live)[1]
        assert not np.allclose(hidden, bare, atol=1e-3)


@WITH_LORA
def test_the_losss_gradient_is_a_per_layer_loops(with_lora):
    cfg = toy()
    params, lora, tokens, _, _ = drawn(cfg, with_lora)
    lcfg = LCFG if with_lora else None
    batch = {"tokens": tokens}
    got = jax.jit(jax.grad(lambda p, lo: llama_loss(
        p, batch, cfg, lora=lo, lora_cfg=lcfg), argnums=(0, 1)))(params, lora)
    want = jax.jit(jax.grad(lambda p, lo: loop_loss(
        p, lo, batch, cfg, lcfg), argnums=(0, 1)))(params, lora)
    jax.tree.map(np.testing.assert_array_equal, got, want)
    # every leaf takes part, but the bias, which moves the choice alone
    idle = [jax.tree_util.keystr(path) for path, g
            in jax.tree_util.tree_leaves_with_path(got) if not g.any()]
    assert all("router_bias" in name for name in idle), idle


@pytest.mark.parametrize("kernel", [True, False],
                         ids=["kernel", "ragged_dot"])
def test_the_compiled_step_reads_no_layer_of_an_expert_stack(kernel):
    cfg = toy(kernel)
    params, lora, tokens, last, live = drawn(cfg, False)
    text = jax.jit(step(cfg, False)).lower(
        params, lora, tokens, last, live).compile().as_text()
    m = cfg.mlp_hidden
    sliced = re.findall(r"= (\w+\[[\d,]*\])\S* dynamic-slice\(", text)
    # the body does read its layer of the other leaves out of their stacks
    assert f"f32[1,{H},{E}]" in sliced                  # the router
    assert f"f32[1,{H},384]" in sliced                  # a dense SwiGLU
    # and no layer of [E, H, m] or [E, m, H]
    for shape in (f"[1,{E},{H},{m}]", f"[1,{E},{m},{H}]",
                  f"[{E},{H},{m}]", f"[{E},{m},{H}]"):
        assert not [s for s in sliced if s.endswith(shape)], shape
