"""``tools/check_probe.py`` for ``serve_ling3_repoctx``, with the
bailing_hybrid family's own faults among the controls, each a keyword of
``reference/bailing_hybrid.py::logits`` read at the same positions of the
same prompts and tokens as the sound reference: the delta term left out
(plain gated linear attention), ``beta`` at 1, the decay gate's bound at -1,
the output gate before the head's norm, the rule's state dropped at every
chunk's edge (every ``kda_chunk_size`` positions: a kernel that loses its
state between grid steps), MLA's head-wise gate left out, a group of the
router scored by its best expert alone. The lower precision (the weights
at 3 mantissa bits) is ``check_probe``'s own, as are its arguments.

    chiprun -- python3 benchmark/tools/ling3_probe.py \
        --workload serve_ling3_repoctx --seeds 8 --control-seeds 3 \
        --out chiprun_out/probe_ling3.jsonl
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import check_probe  # noqa: E402

check_probe.CONTROLS["bailing_hybrid"] = {
    "delta_term_left_out": {"kwargs": lambda m: {"delta": False}},
    "beta_at_1": {"kwargs": lambda m: {"beta_one": True}},
    "gate_bound_at_minus_1": {"kwargs": lambda m: {"lower_bound": -1.0}},
    "output_gate_before_norm": {"kwargs": lambda m: {"gate_first": True}},
    "state_dropped_at_chunk_edges": {"kwargs": lambda m: {
        "drop_state_every": m["kda_chunk_size"]}},
    "head_gate_left_out": {"kwargs": lambda m: {"head_gate": False}},
    "group_score_by_best_expert": {"kwargs": lambda m: {
        "group_score": "max"}},
}

if __name__ == "__main__":
    sys.exit(check_probe.main())
