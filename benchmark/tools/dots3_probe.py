"""``tools/check_probe.py`` for ``serve_dots3_longdoc``, with the dots3_note
family's own faults among the controls: the indexer's choice ignored (every
causal key attended), the window ignored, the head-wise gate left out, the
rescale of the latents left out; each a switch of
``reference/dots3_note.py::latent_attention``, read at the same positions
of the same prompts and tokens as the sound reference. The lower precision
(the weights at 3 mantissa bits) is ``check_probe``'s own, as are its
arguments.

    chiprun -- python3 benchmark/tools/dots3_probe.py --workload serve_dots3_longdoc \
        --seeds 8 --control-seeds 3 --out chiprun_out/probe_dots3.jsonl
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import check_probe  # noqa: E402

check_probe.CONTROLS["dots3_note"] = {
    "selection_ignored": {"kwargs": lambda m: {"selection": False}},
    "window_ignored": {"kwargs": lambda m: {"window": False}},
    "gate_left_out": {"kwargs": lambda m: {"gate": False}},
    "rescale_left_out": {"kwargs": lambda m: {"rescale": False}},
}

if __name__ == "__main__":
    sys.exit(check_probe.main())
