"""``tools/check_probe.py`` for ``serve_granite_toolcalls``, with the
granite_hybrid family's own faults among the controls, each a keyword of
``reference/granite_hybrid.py::logits`` read at the same positions of the
same prompts and tokens as the sound reference: the scan's state dropped at
every chunk's edge (every 256 positions: a kernel that loses its state
between grid steps), ``residual_multiplier`` at 1, the taps' bias left out,
``D x`` left out, the norm before the gate, the softmax scale at ``head_dim
** -0.5``, the rotation left on. The lower precision (the weights at 3
mantissa bits) is ``check_probe``'s own, as are its arguments.

    chiprun -- python3 benchmark/tools/granite_probe.py \
        --workload serve_granite_toolcalls --seeds 8 --control-seeds 3 \
        --out chiprun_out/probe_granite.jsonl
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import check_probe  # noqa: E402

check_probe.CONTROLS["granite_hybrid"] = {
    "state_dropped_at_chunk_edges": {"kwargs": lambda m: {
        "drop_state_every": m["mamba_chunk_size"]}},
    "residual_multiplier_at_1": {"kwargs": lambda m: {"residual": 1.0}},
    "conv_bias_left_out": {"kwargs": lambda m: {"conv_bias": False}},
    "skip_left_out": {"kwargs": lambda m: {"skip": False}},
    "norm_before_gate": {"kwargs": lambda m: {"gate_first": False}},
    "softmax_scale_at_head_dim": {"kwargs": lambda m: {
        "attention_scale": (m["hidden_size"]
                            // m["num_attention_heads"]) ** -0.5}},
    "rope_left_on": {"kwargs": lambda m: {"rope": True}},
}

if __name__ == "__main__":
    sys.exit(check_probe.main())
