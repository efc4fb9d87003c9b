"""Reader of `flash_attn_roofline_pct.train`; what it reads is in metrics/flash_attn_roofline_pct.train.json."""

from benchmark.harness import loader
from benchmark.readers import common


def read(view, metric):
    ms = common.ops_ms_per_step(view["trace"], metric["match"])
    if not ms:
        return None
    cell, pk = view["cell"], view["peaks"]
    family = loader.load_family(cell["model"])
    rows = cell["traffic"]["rows_per_step"] / view["device"]["count"]
    need_s = max(
        family.attention_kernel_flops(cell["model"], rows, cell["traffic"]["seq"]) / pk["bf16_flops_per_s"],
        family.attention_kernel_bytes(cell["model"], rows, cell["traffic"]["seq"]) / pk["hbm_bytes_per_s"])
    return 100.0 * need_s / (1e-3 * ms)
