"""Run one cell of the benchmark and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is found by its name: ``workloads/<cell>.json`` names the
configuration (``configs/``), the traffic mix (``traffic/<mix>.json``,
which names its generator) and the kind of cell (the driver under
``drivers/``); the per-layer metrics are the files under ``metrics/``
that list the cell or its kind, each with a reader under ``readers/``.

This process never imports jax: a parent that has touched jax holds the
chip, and the worker or replica that needs it then fails. Device kind,
count, memory, the trace and its reduction all come from the process that
holds the chip. Without a chip, or on a ``device_kind`` that
``harness/peaks.py`` does not know, the run fails and prints no result.

``--rehearsal`` walks the same control flow on the CPU at the tiny sizes
of ``<cell>.rehearsal.json``; every line says so, ``correct`` is false,
the exit code is 3 and no line it prints is a result.
"""

from __future__ import annotations

import time

PROCESS_START_UNIX = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import glob  # noqa: E402
import os  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)
REHEARSAL_EXIT = 3
# the driver ends a run after 360 s, or 1200 s where it compiles: give up
# a little before that, say where the time went, and leave nothing running
LIMIT_S = 1150


def _dump_session_logs(out=sys.stderr, tail: int = 3000) -> None:
    """The end of every log of the cluster this run started: a run that
    stalls says nothing itself, and the logs go with the shutdown."""
    node = getattr(sys.modules.get("ray_tpu"), "_global_node", None)
    log_dir = os.path.join(getattr(node, "session_dir", "") or "", "logs")
    for path in sorted(glob.glob(os.path.join(log_dir, "*"))):
        try:
            with open(path, "rb") as f:
                f.seek(max(0, os.path.getsize(path) - tail))
                text = f.read().decode("utf-8", "replace")
        except OSError:
            continue
        if text.strip():
            print(f"---- {path}\n{text}", file=out, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny sizes, control flow only: never a result")
    args = ap.parse_args(argv)

    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    from benchmark.harness import lastline, loader, peaks

    loader.export_environment(rehearsal=args.rehearsal)

    tag = "[bench REHEARSAL]" if args.rehearsal else "[bench]"

    def say(phase: str, **fields) -> None:
        print(f"{tag} {phase}: " + " ".join(
            f"{k}={json.dumps(v) if isinstance(v, (dict, list)) else v}"
            for k, v in fields.items()), flush=True)

    manifest = loader.load_manifest()
    listed = loader.manifest_cell(manifest, args.workload)
    if listed is None:
        raise SystemExit(f"BENCHMARK.json lists no workload {args.workload!r}")
    cell = loader.load_cell(args.workload, rehearsal=args.rehearsal)
    if (cell["config"], cell["traffic"]["name"], cell["chips"]) != (
            listed["config"], listed["traffic"], listed["chips"]):
        raise SystemExit(f"workloads/{args.workload}.json and BENCHMARK.json "
                         "disagree on config, traffic or chips")
    if args.rehearsal:  # as many virtual CPU devices as the cell has chips
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={cell['chips']}")
    trace = bool(args.trace)
    say("cell", workload=cell["name"], config=cell["config"],
        traffic=cell["traffic"]["name"], kind=cell["kind"],
        chips=cell["chips"], seed=args.seed, seconds=args.seconds,
        trace=int(trace))

    driver = loader.load_driver(cell)
    ctx = {
        "seed": args.seed, "seconds": args.seconds, "trace": trace,
        "rehearsal": args.rehearsal, "say": say,
        "process_start_unix": PROCESS_START_UNIX,
        "traffic": loader.load_traffic(cell),
    }

    def give_up(signum, frame):
        _dump_session_logs()
        raise TimeoutError(f"the run passed its limit of {LIMIT_S} s")

    signal.signal(signal.SIGALRM, give_up)
    signal.alarm(LIMIT_S)
    try:
        res = driver.run(cell, ctx)
    except TimeoutError:
        signal.alarm(0)
        import ray_tpu

        ray_tpu.shutdown()  # it may be the driver's own that was cut short
        raise
    finally:
        signal.alarm(0)
    if "jax" in sys.modules:
        raise SystemExit("the harness process imported jax")

    values = dict(res["e2e"])
    device = dict(res["device"])
    if args.rehearsal:  # the CPU has no peaks and reports no memory
        chip_peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
                      "source": "rehearsal stand-in, not a chip"}
        device["memory_peak_bytes"] = device["memory_peak_bytes"] or 1
    else:
        chip_peaks = peaks.peak(device["kind"])
    breakdown = None
    if trace:
        tr = res["trace"]
        if not tr or not tr.get("n_devices"):
            raise SystemExit(
                "the traced window holds no device operation: planes "
                f"{json.dumps((tr or {}).get('planes'))}")
        say("trace", planes=tr["planes"], host_spans=tr["host_spans"],
            steps=tr["steps"])
        device["window_s"] = max(tr["window_s"], tr["span_s"])
        device["busy_s"] = tr["busy_s"]
        view = {"cell": cell, "obs": res["obs"], "trace": tr,
                "e2e": res["e2e"], "device": device, "peaks": chip_peaks}
        # a rehearsal's CPU trace has no Mosaic kernel: its file says
        # which operations stand in for one
        overrides = cell.get("metric_overrides", {}) if args.rehearsal else {}
        for metric in loader.metrics_for_cell(cell):
            metric = {**metric, **overrides.get(metric["name"], {})}
            reader = loader.load_reader(metric)
            values[metric["name"]] = reader(view, metric)
        breakdown = {
            "device_ops": [[name, secs] for name, secs, _ in tr["ops"][:10]],
            "idle_gaps": tr["gaps"][:5]}
    for name in sorted(values):
        say("metric", name=name, value=values[name])
    for p in res["problems"]:
        say("NOT CORRECT", reason=p)

    line = lastline.build(
        manifest, cell["name"], trace, values=values, device=device,
        correct=res["correct"] and not args.rehearsal,
        attempted=res["attempted"], failed=res["failed"],
        breakdown=breakdown)
    try:
        lastline.validate(line, manifest, cell["name"], trace)
    except lastline.LastLineError as e:
        print(f"{tag} the last line breaks the contract and is not "
              f"printed: {e}", file=sys.stderr, flush=True)
        return 2
    if args.rehearsal:
        print(f"{tag} would-be last line: {lastline.dumps(line)}")
        print(f"{tag} REHEARSAL ONLY: not a result", flush=True)
        return REHEARSAL_EXIT
    print(lastline.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
