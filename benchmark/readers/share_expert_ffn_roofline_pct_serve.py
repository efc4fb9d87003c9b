"""Reader of `dsv2_expert_ffn_roofline_pct.serve`; what it reads is in metrics/dsv2_expert_ffn_roofline_pct.serve.json."""

import re

from benchmark.harness import loader

SPAN = re.compile(r"^live_(\d+)$")


def read(view, metric):
    """`expert_ffn_roofline_pct_serve:read` for a replica whose held experts
    are spared a step's padding: the FLOPs are needed over each traced
    step's own live positions, which the family's served class names in a
    span `bench:live_<positions>` beside the step's `bench:len_<length>`,
    not over rows x padded length. A program without such spans gives
    nothing."""
    trace, cell, pk = view["trace"], view["cell"], view["peaks"]
    rx = re.compile(metric["match"])
    measured_s = sum(secs for name, secs, _ in trace["ops"] if rx.search(name))
    steps = {int(SPAN.match(name).group(1)): count
             for name, (_, count) in trace["host_spans"].items()
             if SPAN.match(name)}
    family = loader.load_family(cell["model"])
    if not measured_s or not steps or not hasattr(family, "expert_ffn_flops"):
        return None
    m = cell["model"]
    need_s = sum(count * max(
        family.expert_ffn_flops(m, live) / pk["bf16_flops_per_s"],
        family.expert_ffn_bytes(m) / pk["hbm_bytes_per_s"])
        for live, count in steps.items())
    return 100.0 * need_s / measured_s
