"""Find a serving cell's knee once: one replica kept up, a few rates each
offered for a fixed time, the table printed and written as JSON.

    chiprun -- python3 benchmark/tools/sweep_rate.py --workload serve_chat_steady \
        --rates 1.0,1.4,1.6,1.8,2.0,2.4 --order-seeds 1,2 --seconds 40 \
        --out chiprun_out/sweep.json

The knee is the highest rate the system sustains: beyond it the completed
tokens per second stop following the offered tokens per second, and the
wait for a first token grows all through the run (the table gives it for
the first and the last quarter of the requests; below the knee one burst
can raise either, so read the two columns over the neighbouring rates).
The cell then runs at about four fifths of the knee (``rate_per_s`` in the
mix's file). Every
rate is offered in the mix's own order of arrivals, as ``run.py`` offers
it; ``--order-seeds`` then offers the mix's own rate in other orders of
the same requests, which is how far one order stands for the others. Like
``run.py`` this process never imports jax.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated, per s")
    ap.add_argument("--order-seeds", default="", help="comma-separated: "
                    "the mix's own rate again, each in another order")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=3000000001)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    t_start = time.time()
    sys.path.insert(0, REPO_ROOT)
    from benchmark.harness import loader, stats

    loader.export_environment(rehearsal=args.rehearsal)

    import ray_tpu
    from ray_tpu import serve

    from benchmark.drivers import serve as driver

    cell = loader.load_cell(args.workload, rehearsal=args.rehearsal)
    traffic = loader.load_traffic(cell)
    vocab = cell["model"]["vocab_size"]

    def say(phase, **fields):
        print(f"[sweep] {phase}: " + " ".join(
            f"{k}={v}" for k, v in fields.items()), flush=True)

    ctx = {"seed": args.seed, "rehearsal": args.rehearsal, "say": say}
    rows = []
    handle = None
    try:
        handle, call, facts = driver.bring_up(cell, ctx)
        say("up", setup_s=round(time.time() - t_start, 1))
        own = cell["traffic"]
        points = [(float(r), own["order_seed"])
                  for r in args.rates.split(",")]
        points += [(own["rate_per_s"], int(o))
                   for o in args.order_seeds.split(",") if o]
        for rate, order_seed in points:
            mix = dict(own, rate_per_s=rate, order_seed=order_seed)
            offered = traffic.describe(mix, args.seconds)
            sched = traffic.schedule(mix, seed=args.seed,
                                     seconds=args.seconds, vocab=vocab)
            win = driver.offer(handle, call, sched, seconds=args.seconds,
                               client_threads=cell["client_threads"])
            w = driver.reduce_window(win, seconds=args.seconds, vocab=vocab)
            steps = call("bench_steps")
            q = max(1, len(w["ttft_s"]) // 4)
            row = {
                "rate_per_s": rate, "order_seed": order_seed,
                "requests": w["requests"],
                "failed": w["failed"],
                "offered_tokens_per_s": offered["offered_tokens_per_s"],
                "tokens_per_s": w["tokens_in_window"] / w["window_s"],
                "ttft_mean_ms": 1e3 * sum(w["ttft_s"]) / len(w["ttft_s"]),
                "ttft_p50_ms": 1e3 * stats.median(w["ttft_s"]),
                "ttft_p90_ms": 1e3 * stats.percentile(w["ttft_s"], 90),
                "ttft_first_quarter_p50_ms":
                    1e3 * stats.median(w["ttft_s"][:q]),
                "ttft_last_quarter_p50_ms":
                    1e3 * stats.median(w["ttft_s"][-q:]),
                "gap_p50_ms": 1e3 * stats.median(w["gap_s"] or [0.0]),
                "gap_p95_ms": 1e3 * stats.percentile(w["gap_s"] or [0.0], 95),
                "engine_step_ms": 1e3 * w["window_s"] / max(
                    1, w["engine_steps"]),
                "model_step_ms_p50": 1e3 * stats.median(steps or [0.0]),
                "occupancy_pct": 100.0 * w["engine_emitted"] / max(
                    1, w["engine_steps"] * cell["engine"]["max_batch_size"]),
                "late_p95_ms": 1e3 * stats.percentile(w["late_s"] or [0.0],
                                                      95),
                "unfinished_after_drain": win["unfinished"],
            }
            rows.append(row)
            say("rate", **{k: round(v, 2) if isinstance(v, float) else v
                           for k, v in row.items()})
    finally:
        if handle is not None:
            serve.shutdown()
        ray_tpu.shutdown()
    if "jax" in sys.modules:
        raise SystemExit("the sweep's process imported jax")
    out = {"workload": args.workload, "seconds": args.seconds,
           "device": facts["device"], "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
