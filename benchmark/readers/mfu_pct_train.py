"""Reader of `mfu_pct.train`; what it reads is in metrics/mfu_pct.train.json."""

from benchmark.harness import loader


def read(view, metric):
    model = view["cell"]["model"]
    per_token = loader.load_family(model).train_flops_per_token(model, view["cell"]["traffic"]["seq"])
    peak = view["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_token * view["e2e"]["train_tokens_per_s_per_chip"] / peak
