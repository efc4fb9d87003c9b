"""The text of each benchmark cell's programs, hashed: a PR that did not
mean to change a cell's program finds here that it left it alone, and one
that did re-baselines the lines it meant to move and no other.

A program is ``tests/benchmark/test_deepseek_v2.py::program_text``'s: the
jaxpr, at the cell's real sizes and with addresses blanked, of the model's
initialiser (``init``), of a serving step at 8 rows of the cell's shortest
and of its longest warmed length (``step<length>``; ``told<length>`` is
this file's ``told_text``: the same step handed its mask, for a model
without experts), or of training's value-and-gradient at 2 x 4096
(``grad``). Only shapes are traced: no
weight is made.

Regenerate (prints the table below; paste the lines a PR means to move)::

    JAX_PLATFORMS=cpu python tests/test_cell_programs.py

History. PR 37 (a step's padding stays off the routed experts) moved
``serve_olmoe_chat``'s and ``serve_lfm2_rag``'s four step programs; the ten
others are what its parent ``2e5068b`` gives (``serve_chat_steady`` and
``train_l2_seq4k`` have no routed layer, and ``serve_dsv2_docqa``'s share
kept its padding off already). PR 42 (where some pairs are not kept the
kept pairs' rows alone move; `grouped_matmul`'s `tail` is gone) moved the
six step programs of the three cells with experts; the inits, the dense
cell's steps and training's are what its parent ``6f115a1`` gives. PR 43
(dots3-note-prev: ``_latent_attention`` told its widths, a window and a
choice of keys in ``attention()`` and the two-width forward) moved none of
the fourteen: they are what its parent ``33f40e9`` gives to the character;
``serve_dots3_longdoc``'s three are new (traced at 8 rows like the others'
steps; the cell serves 4). PR 46 (granite-4.0-h-micro: a state-space
operator, ``_short_conv``'s taps shared with it through ``_causal_taps``,
the family's four scalars and ``use_rope`` behind defaults) moved none of
the seventeen: they are what its parent ``74f24a8`` gives to the
character; ``serve_granite_toolcalls``'s three are new. PR 48
(``remat_policy="dots"`` keeps the flash forward's two results by name and
the loss's scan keeps its matmul's output; PR 47 was the same change and
left nothing in the tree) moved ``train_l2_seq4k.grad``, as it meant to:
three flash kernels a layer where there were four, three matmuls of the
head's size where there were four. The seven ``init`` lines are what its
parent ``800f69f`` gives to the character. The twelve ``step<length>``
lines are re-baselined for ONE word an equation: a serving configuration
leaves ``remat`` at its default, so its step holds a ``checkpoint``
equation a run of layers, and that equation prints its policy's name
(``dots_with_no_batch_dims_saveable`` at the parent,
``save_from_both_policies.<locals>.policy`` now). What the twelve LOWER to
did not move: a ``checkpoint`` that is not differentiated lowers to its
body, the policy is in no StableHLO, and the sha256 of each step's
``jax.jit(...).lower(...).as_text()`` at the cells' shapes is the parent's
(builder's check, PR 48; the table is in CHANGES.md's PR 48 line): to the
character as the CPU lowers it, and as a described v5e lowers it once the
Mosaic kernels' debug locations are dropped (a kernel's serialised body
holds its call stack's file names and line numbers, so there any line
added above a frame of ``llama.py`` or ``flash_attention.py`` moves the
text and nothing that is compiled). PR 50 (a serving step reads a partial
run's layers where they lie in their stack: ``llama_next_token`` scans such
a run over its layers' indices and the body indexes the whole stack, so no
``slice`` of a layer stack is left outside a loop; PR 49 was the same change
and left nothing in the tree) moved the four step programs of the two cells
whose kinds lie in several runs, ``serve_granite_toolcalls`` (nine runs of
two kinds) and ``serve_lfm2_rag`` (five of three, four of them partial);
the seven ``init`` lines, ``train_l2_seq4k.grad`` (the loss keeps its
slices, and Mistral has one kind) and the eight steps of the five cells
whose every run is its whole stack are what its parent ``60e176b`` gives to
the character. PR 52 (Ling-3.0-flash: a delta-rule operator, latent
attention with full-rank queries and the head-wise gate on the plain
``latent`` operator, a group of the router scored by its two best behind
``router_group_score``, whose default is the parent's) moved none of the
twenty: they are what its parent ``78fee0c`` gives to the character;
``serve_ling3_repoctx``'s three are new. PR 53 (the delta rule's kernel is
told its rows' lengths: ``llama_next_token`` of a model with a ``kda``
operator sums the mask it is handed anyway to a length a row,
``_hidden_and_books`` -> ``_layer`` -> ``_kda`` -> ``ops.kda.kda`` carry it,
and ``kda_chunked`` takes a row's live chunks as a scalar-prefetched operand
and runs no chunk past them) moved ``serve_ling3_repoctx.step1024`` and
``.step3072``, as it meant to: a ``reduce_sum`` of the mask and four
kernels with one operand more. The twenty-one others, that cell's ``init``
among them, are what its parent ``f43b5c9`` gives to the character: no
length is made for a model without the operator. PR 54 (the two-width flash
forward is told its rows' lengths: ``llama_next_token`` makes them for a
model with latent attention too, ``_layer`` -> ``_latent_attention`` ->
``ops.attention.attention`` -> ``flash_attention_shared_rope`` carry them,
and ``_flash_fwd_shared_rope`` takes a row's live blocks as a
scalar-prefetched operand and computes no block past them) moved the six
step programs of the three cells with latent attention,
``serve_dsv2_docqa``, ``serve_dots3_longdoc`` and ``serve_ling3_repoctx``,
as it meant to: a ``reduce_sum`` of the mask where there was none (the
third cell had it), and every two-width kernel with one operand more and
``lax`` primitives alone in its index maps. The seventeen others, every
``init`` and the five other cells' steps and gradient, are what its parent
``0f2f053`` gives to the character: the equal-width kernels, which they
run, are not touched. PR 55 (Mellum2-12B-A2.5B: a ``sliding`` operator on
the ``attention`` layers' leaves, ``_fwd_kernel`` and ``_flash_fwd`` told a
window behind ``window=None``, ``_layer``'s attention branch through
``_yarn_rope``, whose ``None`` is ``_rope``) moved none of the twenty-three:
they are what its parent ``760214b`` gives to the character;
``serve_mellum2_projctx``'s three are new (traced at 8 rows like the others'
steps; the cell serves 4). PR 56 (the equal-width flash forward is told its
rows' lengths: ``llama_next_token`` makes them whenever it is handed a
mask, ``_layer``'s attention branch -> ``ops.attention.attention`` ->
``flash_attention`` / ``flash_attention_window`` carry them, and
``_flash_fwd`` takes a row's live blocks as a scalar-prefetched operand and
computes no block past them) moved the six step programs of the three cells
whose model has experts and runs that forward, ``serve_olmoe_chat``,
``serve_lfm2_rag`` and ``serve_mellum2_projctx``, as it meant to: a
``reduce_sum`` of the mask where there was none, and every equal-width
kernel with one operand more, one result fewer (no logsumexp: the call is
the forward's alone) and ``lax`` primitives alone in its index maps. The twenty others are what its parent ``3fc52f6`` gives to the
character: ``train_l2_seq4k.grad`` (no lengths: ``flash_attention`` with
``lengths=None`` is the call it was, forward and backward), every ``init``,
and the three latent cells' steps (``serve_dsv2_docqa``,
``serve_dots3_longdoc``, ``serve_ling3_repoctx``: the two-width forward and
the delta rule alone, told already). The four step lines of the two cells
whose model has no experts, ``serve_chat_steady`` and
``serve_granite_toolcalls``, did not move either, and not because their
served step did not: ``program_text`` (the benchmark's) traces such a
model's step with no mask, which is what ``_run_step`` handed it until this
PR and what ``_FullLogits`` still runs. What the served class runs for them
now, the same step handed the mask (one input more, the ``reduce_sum``, the
kernels told), is ``told_text``'s, on record as ``told<length>``: four new
lines. PR 59 (the state-space scan is told its rows' lengths: ``_layer`` ->
``_mamba`` -> ``ops.ssm.ssd_scan`` carry what ``llama_next_token`` already
made, ``dt`` is taken for 0 past a row's end, and ``ssd_scan_chunked`` takes
a row's live chunks as a scalar-prefetched operand and runs no chunk past
them) moved ``serve_granite_toolcalls.told256`` and ``.told1024``, as it
meant to: a select on ``dt`` a run of mixers, and every scan's kernel with
one operand more, ``lax`` primitives alone in its index maps and no
``custom_vjp_call`` round it (told, the kernel is called bare: a bucket's
warm-up call was 1.8 s longer with it, PERF.md section 6). The
twenty-eight others are what its parent ``7c64761`` gives to the character:
that cell's ``init``, ``step256`` and ``step1024`` among them (no mask, so
no lengths: ``ssd_scan_chunked`` with ``lengths=None`` is the call it was),
and every other cell's lines, no other model having the operator. PR 60
(Laguna-XS.2: the ``attention`` and ``sliding`` operators take their query
heads by kind behind ``swa_num_heads`` 0, the sliding layers their own
``swa_rope_theta`` behind 0, a full layer's rope the first
``partial_rotary_factor`` of its head behind 1.0, and ``head_gate`` is
honoured by the grouped-query branch, which no earlier model with that
branch sets) moved none of the thirty: they are what its parent ``0df960f``
gives to the character; ``serve_laguna_agentturns``'s three are new (traced
at 8 rows like the others' steps; the cell serves 4). PR 61 (the
window's one step: where ``window_step`` says a query block holds its
window's tail beside it, ``_flash_fwd`` under a window runs
``_window_step_kernel``, one grid step a query block over the block's own
keys and the tail before them, with no key dim and no running softmax)
moved ``serve_laguna_agentturns.step6144``, as it meant to: the three
sliding layers' call on a grid of (row, head, 12 query blocks of 512) with
the keys and the values passed twice (blocks of 512, the tail's and the
block's own) where it walked two blocks of 1024 keys a block of 1024
queries, no scratch, and ``_live_blocks``' divisors 512. The thirty-two
others are what its parent ``5e15c0b`` gives to the character: that cell's
``init`` and its ``step1024`` (ISSUE 61 expected that line to move too, to
blocks of 512 x 512 in the walk; the chip's sweep found them 12 % slower
than the plain tile, section 6 of PERF.md, and where the plain rule's keys
are ONE block the walk is one step a block already and stays);
``serve_mellum2_projctx``'s three (a window of 1024 has a tail of 1024,
which beside a block of 1024 is past VMEM's reckoning: the walk, which is
the proof that its programs are the parent's); ``serve_dots3_longdoc``'s
(the two-width forward keeps its walk) and every other line, no other call
passing a window, and ``flash_tiles`` being the function it was. PR 62
(Keye-VL-2.0-30B-A3B's language model: an ``indexed_attention`` operator
on the ``attention`` layers' leaves behind its own layer type, ``_rope``
told three position streams behind ``mrope_section`` ``()``,
``_index_queries_and_key`` told its queries' source and its rotary width,
``_fwd_kernel`` and ``_flash_fwd`` told a choice of keys behind
``keep=None``, and ``init_llama``'s indexer leaves drawn by one helper for
both kinds of indexer) moved none of the thirty-three: they are what its
parent ``dea3e11`` gives to the character, ``serve_dots3_longdoc``'s
``init`` and steps among them; ``serve_keye_clipqa``'s three are new
(traced at 8 rows like the others' steps; the cell serves 4).
"""

import hashlib

import pytest

PROGRAMS = {
    "train_l2_seq4k.init": "d02563bf9b97ea28",
    "train_l2_seq4k.grad": "45b9dbc41a5406f2",
    "serve_chat_steady.init": "3ff45d688c80d7f8",
    "serve_chat_steady.step128": "e007e82555a4c201",
    "serve_chat_steady.step384": "3bf862312efd7151",
    "serve_olmoe_chat.init": "126fada9fb96dc80",
    "serve_olmoe_chat.step128": "d39476f9ff26cc72",
    "serve_olmoe_chat.step1152": "66ac03a0fa292003",
    "serve_lfm2_rag.init": "5766fc6f6af74d3d",
    "serve_lfm2_rag.step128": "bc33adeb50b44ec3",
    "serve_lfm2_rag.step1408": "e677dd673bf28574",
    "serve_dsv2_docqa.init": "91b10ec8ff63401e",
    "serve_dsv2_docqa.step256": "3694036b0c6db661",
    "serve_dsv2_docqa.step1792": "d4eca275aeee6adb",
    "serve_dots3_longdoc.init": "af5788ce0837f29f",
    "serve_dots3_longdoc.step2560": "bc492a5899efa585",
    "serve_dots3_longdoc.step5120": "62f8cde7b9c7635f",
    "serve_granite_toolcalls.init": "ce77369b6d8feac3",
    "serve_granite_toolcalls.step256": "4adf109acac83db4",
    "serve_granite_toolcalls.step1024": "ee9ab836cd96137f",
    "serve_ling3_repoctx.init": "1e230ff66f2de897",
    "serve_ling3_repoctx.step1024": "093dcdc0ed5ccc8c",
    "serve_ling3_repoctx.step3072": "c2d07b3b2dc38a22",
    "serve_mellum2_projctx.init": "26e74b5260e71edd",
    "serve_mellum2_projctx.step4096": "636a25abce39cf8c",
    "serve_mellum2_projctx.step8192": "2d3f004b61858767",
    "serve_laguna_agentturns.init": "e4025d438f2ceb1d",
    "serve_laguna_agentturns.step1024": "049e8daa592d60d0",
    "serve_laguna_agentturns.step6144": "a4e4961a94882f1f",
    "serve_keye_clipqa.init": "7c1b75c937e18797",
    "serve_keye_clipqa.step4096": "5702a65fb4d25282",
    "serve_keye_clipqa.step8192": "80a3186e7471b989",
    "serve_chat_steady.told128": "db30cd54d721b7ac",
    "serve_chat_steady.told384": "e905645daec74f90",
    "serve_granite_toolcalls.told256": "5146abf0b9b112f0",
    "serve_granite_toolcalls.told1024": "83d739cca3a05509",
}


def told_text(cell_name: str, length: int) -> str:
    """``program_text``'s ``step<length>`` handed the mask: what the served
    class runs since PR 56, which hands every model its rows' own tokens
    (``program_text`` traces a model without experts with no mask)."""
    import re

    import jax
    import jax.numpy as jnp

    from benchmark.harness import loader
    from ray_tpu.models.llama import init_llama, llama_next_token

    cell = loader.load_cell(cell_name)
    engine = dict(cell.get("engine") or {}, lora_rank=4, max_batch_size=8,
                  allowed_batch_sizes=[8], max_new_tokens=8, seq_bucket=128)
    cfg = loader.load_family(cell["model"]).served_kwargs(
        cell["model"], engine, 1)["config"]
    jaxpr = jax.make_jaxpr(lambda p, t, i, on: llama_next_token(
        p, t, i, cfg, live=on))(
            jax.eval_shape(lambda k: init_llama(cfg, k), jax.random.key(0)),
            jax.ShapeDtypeStruct((8, length), jnp.int32),
            jax.ShapeDtypeStruct((8,), jnp.int32),
            jax.ShapeDtypeStruct((8, length), jnp.bool_))
    return re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))


def program_hash(program: str) -> str:
    from tests.benchmark.test_deepseek_v2 import program_text

    cell, which = program.split(".")
    text = (told_text(cell, int(which[len("told"):]))
            if which.startswith("told") else program_text(cell, which))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_a_cells_program_is_the_one_on_record(program):
    assert program_hash(program) == PROGRAMS[program], (
        f"{program} is another program than the one on record: if this PR "
        "means to change it, regenerate (this file's docstring)")


if __name__ == "__main__":
    import os
    import sys

    import jax

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    jax.config.update("jax_platforms", "cpu")
    for name in PROGRAMS:
        print(f'    "{name}": "{program_hash(name)}",')
