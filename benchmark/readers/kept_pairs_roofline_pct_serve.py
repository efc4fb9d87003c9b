"""Reader of a kernel's share of its roofline where the family reckons the need a traced step (`dots3_sparse_flash_fwd_roofline_pct.serve`, `dots3_window_flash_fwd_roofline_pct.serve`, `dots3_indexer_roofline_pct.serve`); what each reads is in its file under metrics/."""

from benchmark.harness import loader
from benchmark.readers import common


def read(view, metric):
    """For each traced step (its record, `harness/steprecord.py`) the larger
    of the family's `<need>_flops(m, step)` at the bf16 peak and
    `<need>_bytes(m, step)` at the HBM peak, `need` named by the metric's
    file, summed, over the measured self time of the operations that `match`
    names. None where nothing matches, no step was traced or the family
    reckons no such need."""
    trace, cell, pk = view["trace"], view["cell"], view["peaks"]
    measured_s = common.ops_seconds(trace, metric["match"])
    steps = trace.get("step_records")
    family = loader.load_family(cell["model"])
    flops = getattr(family, metric["need"] + "_flops", None)
    bytes_ = getattr(family, metric["need"] + "_bytes", None)
    if not measured_s or not steps or flops is None or bytes_ is None:
        return None
    m = cell["model"]
    need_s = sum(max(flops(m, step) / pk["bf16_flops_per_s"],
                     bytes_(m, step) / pk["hbm_bytes_per_s"])
                 for step in steps)
    return 100.0 * need_s / measured_s
