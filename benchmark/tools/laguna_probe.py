"""``tools/check_probe.py`` for ``serve_laguna_agentturns``, with the laguna
family's own faults among the controls, each a switch of
``reference/laguna.py::logits`` read at the same positions of the same
prompts and tokens as the sound reference: the head-wise gate left out; the
full layers' rope over the whole head (no partial rotary factor); the
sliding layers' rope at the full layers' theta; plain rope in place of
YaRN; the window ignored; the window one key short; the full layers' query
heads grouped by 8 where their groups are of 6; the routed scaling factor
1.0; the shared expert left out; softmax scores in place of sigmoid. The
lower precision (the weights at 3 mantissa bits) is ``check_probe``'s own,
as are its arguments.

    chiprun -- python3 benchmark/tools/laguna_probe.py --workload serve_laguna_agentturns \
        --seeds 8 --control-seeds 2 --out chiprun_out/pr60/probe.jsonl
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import check_probe  # noqa: E402

check_probe.CONTROLS["laguna"] = {
    "gate_left_out": {"kwargs": lambda m: {"gate": False}},
    "full_rope_whole_head": {"kwargs": lambda m: {"partial": False}},
    "sliding_theta_full": {"kwargs": lambda m: {"sliding_theta": m[
        "rope_parameters"]["full_attention"]["rope_theta"]}},
    "yarn_left_out": {"kwargs": lambda m: {"yarn": False}},
    "window_ignored": {"kwargs": lambda m: {"window": False}},
    "window_one_key_short": {
        "kwargs": lambda m: {"window_keys": m["sliding_window"] - 1}},
    "full_heads_grouped_by_8": {"kwargs": lambda m: {"full_group": max(
        m["num_attention_heads_per_layer"]) // m["num_key_value_heads"]}},
    "scaling_factor_one": {"kwargs": lambda m: {"scaling": 1.0}},
    "shared_expert_left_out": {"kwargs": lambda m: {"shared": False}},
    "softmax_scores": {"kwargs": lambda m: {"scores": "softmax"}},
}

if __name__ == "__main__":
    sys.exit(check_probe.main())
