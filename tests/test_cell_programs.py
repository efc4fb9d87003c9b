"""The text of each benchmark cell's programs, hashed: a PR that did not
mean to change a cell's program finds here that it left it alone, and one
that did re-baselines the lines it meant to move and no other.

A program is ``tests/benchmark/test_deepseek_v2.py::program_text``'s: the
jaxpr, at the cell's real sizes and with addresses blanked, of the model's
initialiser (``init``), of a serving step at 8 rows of the cell's shortest
and of its longest warmed length (``step<length>``), or of training's
value-and-gradient at 2 x 4096 (``grad``). Only shapes are traced: no
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
character; ``serve_granite_toolcalls``'s three are new.
"""

import hashlib

import pytest

PROGRAMS = {
    "train_l2_seq4k.init": "d02563bf9b97ea28",
    "train_l2_seq4k.grad": "cc40eeae09e6ec41",
    "serve_chat_steady.init": "3ff45d688c80d7f8",
    "serve_chat_steady.step128": "ad4fedae56577eac",
    "serve_chat_steady.step384": "f2df55b80038d939",
    "serve_olmoe_chat.init": "126fada9fb96dc80",
    "serve_olmoe_chat.step128": "c5d5a9838815288c",
    "serve_olmoe_chat.step1152": "b6d7aefc5f242f6b",
    "serve_lfm2_rag.init": "5766fc6f6af74d3d",
    "serve_lfm2_rag.step128": "e2128e6a78e96d87",
    "serve_lfm2_rag.step1408": "3f34f48cd5d440ad",
    "serve_dsv2_docqa.init": "91b10ec8ff63401e",
    "serve_dsv2_docqa.step256": "c3465377128f97b8",
    "serve_dsv2_docqa.step1792": "f6519a8cefe24e4f",
    "serve_dots3_longdoc.init": "af5788ce0837f29f",
    "serve_dots3_longdoc.step2560": "f4c2f0fc384c3613",
    "serve_dots3_longdoc.step5120": "ec3ce3ece2252171",
    "serve_granite_toolcalls.init": "ce77369b6d8feac3",
    "serve_granite_toolcalls.step256": "055167584e2c5775",
    "serve_granite_toolcalls.step1024": "ac5a47f5f04e9fbe",
}


def program_hash(program: str) -> str:
    from tests.benchmark.test_deepseek_v2 import program_text

    cell, which = program.split(".")
    return hashlib.sha256(program_text(cell, which).encode()).hexdigest()[:16]


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
