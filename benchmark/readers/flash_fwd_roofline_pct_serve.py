"""Readers of `flash_fwd_d64_ms.serve` (`ms`) and `flash_fwd_d64_roofline_pct.serve` (`read`); what they read is in metrics/flash_fwd_d64_*.serve.json."""

import re

from benchmark.harness import loader
from benchmark.readers import common
from benchmark.readers.expert_ffn_roofline_pct_serve import SPAN


def ms(view, metric):
    return common.ops_ms_per_step(view["trace"], metric["match"])


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
    if not measured_s or not steps or not hasattr(family, "flash_fwd_flops"):
        return None
    rows, m = cell["engine"]["max_batch_size"], cell["model"]
    need_s = sum(count * max(
        family.flash_fwd_flops(m, rows, length) / pk["bf16_flops_per_s"],
        family.flash_fwd_bytes(m, rows, length) / pk["hbm_bytes_per_s"])
        for length, count in steps.items())
    return 100.0 * need_s / measured_s
