"""``tools/check_probe.py`` for ``serve_keye_clipqa``, with the keye
family's own faults among the controls, each a switch of
``reference/keye.py::logits`` read at the same positions of the same
prompts and tokens as the sound reference: the indexer's choice ignored
(every causal key attended); ``topk`` halved; the indexer's ReLU left out;
its heads' weights all 1; its key without the LayerNorm; its rope left out;
the RMSNorm over the heads of q and k left out; theta 10 000 in place of
10 000 000; the grouping wrong (query head ``n`` reading key/value head ``n
// 4 % 4``); the chosen experts' weights not renormalised; 7 experts a
token. The lower precision (the weights at 3 mantissa bits) is
``check_probe``'s own, as are its arguments but one: ``--controls a,b``
reads the named ones of these alone (a control costs some 50 s a seed at
this cell's sizes; the lower precision is read whatever is named).

    chiprun -- python3 benchmark/tools/keye_probe.py --workload serve_keye_clipqa \
        --seeds 8 --control-seeds 2 --out chiprun_out/pr62/probe.jsonl
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import check_probe  # noqa: E402

check_probe.CONTROLS["keye"] = {
    "choice_ignored": {"kwargs": lambda m: {"selection": False}},
    "topk_halved": {
        "kwargs": lambda m: {"topk": m["sa_config"]["topk"] // 2}},
    "index_relu_left_out": {"kwargs": lambda m: {"index_relu": False}},
    "index_weights_all_one": {"kwargs": lambda m: {"index_weights": False}},
    "index_key_norm_left_out": {
        "kwargs": lambda m: {"index_key_norm": False}},
    "index_rope_left_out": {"kwargs": lambda m: {"index_rope": False}},
    "head_norms_left_out": {"kwargs": lambda m: {"head_norms": False}},
    "theta_10000": {"kwargs": lambda m: {"theta": 10000.0}},
    "heads_grouped_by_4": {"kwargs": lambda m: {"group": m[
        "num_attention_heads"] // m["num_key_value_heads"] // 2}},
    "weights_not_renormalised": {"kwargs": lambda m: {"renormalise": False}},
    "one_expert_fewer": {"kwargs": lambda m: {
        "experts_per_token": m["num_experts_per_tok"] - 1}},
}



def main(argv=None) -> int:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--controls", default=None)
    own, rest = ap.parse_known_args(argv)
    if own.controls is not None:
        every = check_probe.CONTROLS["keye"]
        check_probe.CONTROLS["keye"] = {
            name: every[name] for name in own.controls.split(",") if name}
    return check_probe.main(rest)


if __name__ == "__main__":
    sys.exit(main())
