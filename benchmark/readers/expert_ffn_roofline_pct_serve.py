"""Reader of `expert_ffn_roofline_pct.serve`; what it reads is in metrics/expert_ffn_roofline_pct.serve.json."""

import re

from benchmark.harness import loader

SPAN = re.compile(r"^len_(\d+)$")


def read(view, metric):
    trace, cell, pk = view["trace"], view["cell"], view["peaks"]
    rx = re.compile(metric["match"])
    measured_s = sum(secs for name, secs, _ in trace["ops"] if rx.search(name))
    # the traced steps by their padded length: the family's served class
    # puts each under a span `bench:len_<length>`
    steps = {int(SPAN.match(name).group(1)): count
             for name, (_, count) in trace["host_spans"].items()
             if SPAN.match(name)}
    family = loader.load_family(cell["model"])
    if not measured_s or not steps or not hasattr(family, "expert_ffn_flops"):
        return None
    rows, m = cell["engine"]["max_batch_size"], cell["model"]
    need_s = sum(count * max(
        family.expert_ffn_flops(m, rows * length) / pk["bf16_flops_per_s"],
        family.expert_ffn_bytes(m) / pk["hbm_bytes_per_s"])
        for length, count in steps.items())
    return 100.0 * need_s / measured_s
