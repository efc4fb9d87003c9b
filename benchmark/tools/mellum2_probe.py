"""``tools/check_probe.py`` for ``serve_mellum2_projctx``, with the mellum
family's own faults among the controls: the window ignored in the sliding
layers (every causal key attended), the window one key short (``q - k <
sliding_window - 1``), YaRN left out of the full layers (plain rope at
amplitude 1), ``norm_topk_prob`` off (the chosen experts' weights not
renormalised); each a switch of ``reference/mellum.py::logits``, read at
the same positions of the same prompts and tokens as the sound reference.
The lower precision (the weights at 3 mantissa bits) is ``check_probe``'s
own, as are its arguments.

    chiprun -- python3 benchmark/tools/mellum2_probe.py --workload serve_mellum2_projctx \
        --seeds 8 --control-seeds 4 --out chiprun_out/probe_mellum2.jsonl
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import check_probe  # noqa: E402

check_probe.CONTROLS["mellum"] = {
    "window_ignored": {"kwargs": lambda m: {"window": False}},
    "window_one_key_short": {
        "kwargs": lambda m: {"window_keys": m["sliding_window"] - 1}},
    "yarn_left_out": {"kwargs": lambda m: {"yarn": False}},
    "topk_not_renormalised": {"kwargs": lambda m: {"renormalise": False}},
}

if __name__ == "__main__":
    sys.exit(check_probe.main())
