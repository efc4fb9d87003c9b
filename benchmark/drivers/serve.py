"""Serving cells: ``serve.run`` of one replica, driven by an open loop.

``run`` is the harness side (load generator, clocks at the client) and
never touches jax. What is served is the deployment class of the
configuration's family (``benchmark/families/``) under ``BenchGenerator``,
which adds nothing to the served path but a clock round ``_step``, plus
methods that are called through the handle, as ``chip_smoke.py`` calls
``device_info``: only the replica holds the chip, so only it can trace the
chip, read its memory or compare the model with the family's reference on
the weights it holds. Warming a shape, the check's logits and the count of
compiled step programs are the served class's own methods.
"""

from __future__ import annotations

import concurrent.futures
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List

from benchmark.harness import loader, stats

HANDLE_TIMEOUT_S = 55.0  # the router gives a request 60 s


# ----------------------------------------------------------- replica side
class BenchGenerator:
    """The benchmark's half of what is served; ``bind_app`` puts it in
    front of the family's ``Served``, whose ``__init__`` and ``_step`` the
    ``super()`` calls below reach."""

    def __init__(self, model: Dict[str, Any], engine: Dict[str, Any],
                 seed: int, rehearsal: bool, chips: int = 1):
        from benchmark.harness import onchip

        self._bench_model = model
        self._bench_rehearsal = rehearsal
        self._bench_chips = chips
        self._bench_step_s: List[float] = []
        self._bench_trace: Dict[str, Any] = {}
        self._bench_compiles = onchip.count_compiles()
        super().__init__(
            **loader.load_family(model).served_kwargs(model, engine, seed))

    def _step(self, model_id, states):
        import jax

        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:model_step"):
            out = super()._step(model_id, states)
        self._bench_step_s.append(time.perf_counter() - t)
        return out

    # ---- called through the handle
    def bench_device(self) -> Dict[str, Any]:
        import jax

        from benchmark.harness import onchip

        info = onchip.device_facts()
        if not self._bench_rehearsal:
            onchip.require_chips(info, self._bench_chips)
        info.update(
            forward_compiles=self.compiled_step_programs(),
            compiles=len(self._bench_compiles),
            compile_s=sum(self._bench_compiles),
            param_dtypes=sorted({str(x.dtype) for x in
                                 jax.tree.leaves(self._params)}),
            attn_impl=self._cfg.attn_impl)
        return info

    def bench_warm(self, seq_len: int) -> float:
        """Seconds the served class takes to compile (or find in the
        cache) and run what a step runs at one sequence bucket."""
        t = time.perf_counter()
        self.warm_step_programs(seq_len)
        return time.perf_counter() - t

    def bench_check(self, prompt: List[int]) -> Dict[str, Any]:
        """The program's logits after the prompt's last token against the
        float32 reference's, on the weights this replica serves."""
        import jax.numpy as jnp
        import numpy as np

        t = time.perf_counter()
        got = self.last_position_logits(prompt)
        want = np.asarray(loader.load_reference(self._bench_model).last_logits(
            self._params, jnp.asarray(prompt, jnp.int32), self._bench_model))
        return {
            "max_abs_diff": float(np.abs(got - want).max()),
            "reference_max_abs": float(np.abs(want).max()),
            "argmax_program": int(got.argmax()),
            "argmax_reference": int(want.argmax()),
            "seconds": time.perf_counter() - t,
        }

    def bench_trace_start(self) -> bool:
        import jax

        from benchmark.harness import xplane

        self._bench_trace = {"dir": tempfile.mkdtemp(prefix="bench_trace_")}
        jax.profiler.start_trace(self._bench_trace["dir"],
                                 profiler_options=xplane.profile_options())
        self._bench_trace.update(stats0=self.engine.stats(),
                                 t0=time.perf_counter())
        return True

    def bench_trace_stop(self) -> Dict[str, Any]:
        import jax

        from benchmark.harness import xplane

        tr = self._bench_trace
        t1, stats1 = time.perf_counter(), self.engine.stats()
        jax.profiler.stop_trace()
        path = xplane.find_xplane(tr["dir"])
        reduced = xplane.reduce_trace(path, rehearsal=self._bench_rehearsal)
        reduced["window_s"] = t1 - tr["t0"]
        reduced["steps"] = stats1["steps"] - tr["stats0"]["steps"]
        shutil.rmtree(tr["dir"], ignore_errors=True)
        self._bench_trace = {}
        return reduced

    def bench_steps(self) -> List[float]:
        """Seconds of every ``_step`` since the last call."""
        out, self._bench_step_s = self._bench_step_s, []
        return out


def bind_app(cell: Dict[str, Any], *, seed: int, rehearsal: bool):
    """The application ``serve.run`` is given: one deployment of
    ``BenchGenerator`` over the family's served class, sized as
    ``llm.build_llama_app`` sizes its own. The class made here has no body:
    everything it runs is importable by name in the replica."""
    from ray_tpu.serve.deployment import Deployment

    eng = cell["engine"]
    served = type("BenchServed", (
        BenchGenerator, loader.load_family(cell["model"]).Served), {})
    dep = Deployment(
        served, "BenchGenerator", num_replicas=1,
        max_ongoing_requests=max(eng["max_ongoing_requests"],
                                 2 * eng["max_batch_size"]),
        max_queued_requests=eng["max_queued_requests"],
        ray_actor_options={} if rehearsal else {"num_tpus": cell["chips"]})
    return dep.bind(model=cell["model"], engine=eng, seed=seed,
                    rehearsal=rehearsal, chips=cell["chips"])


# ----------------------------------------------------------- harness side
def seq_buckets(cell: Dict[str, Any]) -> List[int]:
    """Every padded length the mix can reach: prompt plus generated
    tokens, rounded up to the engine's bucket."""
    mix, bucket = cell["traffic"], cell["engine"]["seq_bucket"]
    lo = mix["prompt_len"]["min"] + 1
    hi = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    return sorted({-(-n // bucket) * bucket for n in range(lo, hi + 1)})


def bring_up(cell: Dict[str, Any], ctx: Dict[str, Any]):
    """init, serve.run, device, warm-up of the cell's buckets, the check.
    Returns (handle, facts about the set-up)."""
    import ray_tpu
    from ray_tpu import serve

    say, rehearsal = ctx["say"], ctx["rehearsal"]
    if rehearsal:
        ray_tpu.init(num_cpus=4)
    else:
        ray_tpu.init()
        node_chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if node_chips < cell["chips"]:
            raise RuntimeError(f"the cell asks for {cell['chips']} TPU "
                               f"chip(s), the node has {node_chips}")
    t = time.time()
    handle = serve.run(bind_app(cell, seed=ctx["seed"], rehearsal=rehearsal),
                       name="bench", wait_timeout_s=300.0)
    facts: Dict[str, Any] = {"replica_ready_s": time.time() - t}

    def call(method: str, *args):
        return getattr(handle, method).remote(*args).result(
            timeout_s=HANDLE_TIMEOUT_S)

    facts["device"] = call("bench_device")
    facts["warm_s"] = {s: call("bench_warm", s) for s in seq_buckets(cell)}
    chk = cell["check"]
    import numpy as np

    prompt = np.random.default_rng([ctx["seed"], 7]).integers(
        2, cell["model"]["vocab_size"], size=chk["prompt_len"]).tolist()
    facts["check"] = call("bench_check", prompt)
    # one request down the whole streamed path before the clock starts
    first = list(handle.options(stream=True).remote(
        {"prompt": prompt[:cell["traffic"]["prompt_len"]["min"]],
         "max_new": cell["traffic"]["output_len"]["min"]}))
    facts["first_stream"] = first
    call("bench_steps")  # forget the warm-up's steps
    say("serve", replica_ready_s=round(facts["replica_ready_s"], 2),
        warm_s={k: round(v, 2) for k, v in facts["warm_s"].items()},
        check_s=round(facts["check"]["seconds"], 2),
        device=facts["device"]["kind"], count=facts["device"]["count"],
        attn_impl=facts["device"]["attn_impl"],
        param_dtypes=facts["device"]["param_dtypes"])
    return handle, call, facts


def offer(handle, call, schedule: List[Dict[str, Any]], *, seconds: float,
          client_threads: int, trace: Dict[str, Any] = None,
          drain_s: float = 60.0) -> Dict[str, Any]:
    """Send ``schedule`` on its clock whatever comes back (an open loop),
    and time every token at the client. Returns the raw observations of
    the window: per request due, sent, token times, tokens, error."""
    stream = handle.options(stream=True)
    results: List[Dict[str, Any]] = [None] * len(schedule)
    pool = concurrent.futures.ThreadPoolExecutor(
        max_workers=client_threads, thread_name_prefix="bench-client")

    def consume(i: int, gen, due: float, sent: float) -> None:
        times, toks, err = [], [], None
        try:
            for tok in gen:
                times.append(time.perf_counter())
                toks.append(tok)
        except Exception as e:  # noqa: BLE001: a failed request is a datum
            err = repr(e)
        results[i] = {"due": due, "sent": sent, "times": times,
                      "tokens": toks, "error": err,
                      "max_new": schedule[i]["max_new"]}

    traced: Dict[str, Any] = {}

    def tracer(t0: float) -> None:
        time.sleep(max(0.0, t0 + trace["start_s"] - time.perf_counter()))
        call("bench_trace_start")
        time.sleep(trace["seconds"])
        traced["reduced"] = call("bench_trace_stop")

    stats0 = call("engine_stats")
    t0 = time.perf_counter()
    trace_thread = None
    if trace:
        trace_thread = threading.Thread(target=tracer, args=(t0,),
                                        name="bench-tracer")
        trace_thread.start()
    futures = []
    for i, req in enumerate(schedule):
        due = t0 + req["due_s"]
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        sent = time.perf_counter()
        gen = stream.remote({"prompt": req["prompt"],
                             "max_new": req["max_new"]})
        futures.append(pool.submit(consume, i, gen, due, sent))
    delay = t0 + seconds - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    t_end = time.perf_counter()
    stats1 = call("engine_stats")
    _, not_done = concurrent.futures.wait(futures, timeout=drain_s)
    if trace_thread is not None:
        trace_thread.join(timeout=120.0)
    pool.shutdown(wait=False, cancel_futures=True)
    for i, r in enumerate(results):
        if r is None:  # still running after the drain: it failed
            results[i] = {"due": t0 + schedule[i]["due_s"], "sent": None,
                          "times": [], "tokens": [],
                          "error": "not finished after the drain",
                          "max_new": schedule[i]["max_new"]}
    return {"t0": t0, "t_end": t_end, "requests": results,
            "stats0": stats0, "stats1": stats1,
            "unfinished": len(not_done), "trace": traced.get("reduced")}


def reduce_window(win: Dict[str, Any], *, seconds: float,
                  vocab: int) -> Dict[str, Any]:
    """Client-side observations -> the numbers the metrics are read from."""
    t0, t_end = win["t0"], win["t_end"]
    ttft, gaps, late, bad = [], [], [], []
    tokens_in_window = 0
    for r in win["requests"]:
        ok = (r["error"] is None and len(r["tokens"]) == r["max_new"]
              and all(isinstance(t, int) and 0 <= t < vocab
                      for t in r["tokens"]))
        if not ok:
            bad.append(r["error"] or f"stream of {len(r['tokens'])} tokens, "
                       f"{r['max_new']} asked for, or an id outside the "
                       "vocabulary")
        # a failed or shed request enters at the window's length
        ttft.append(r["times"][0] - r["due"] if ok else seconds)
        if r["sent"] is not None:
            late.append(r["sent"] - r["due"])
        gaps.extend(b - a for a, b in zip(r["times"], r["times"][1:]))
        tokens_in_window += sum(1 for t in r["times"] if t <= t_end)
    s0, s1 = win["stats0"], win["stats1"]
    return {
        "requests": len(win["requests"]), "failed": len(bad),
        "failures": bad[:5], "ttft_s": ttft, "gap_s": gaps, "late_s": late,
        "tokens_in_window": tokens_in_window, "window_s": t_end - t0,
        "engine_steps": s1["steps"] - s0["steps"],
        "engine_emitted": s1["emitted"] - s0["emitted"],
        "engine_shed": s1["shed"] - s0["shed"],
        "engine_stats_end": s1,
    }


def run(cell: Dict[str, Any], ctx: Dict[str, Any]) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import serve

    say = ctx["say"]
    mix, m, chk = cell["traffic"], cell["model"], cell["check"]
    seconds = ctx["seconds"]
    handle = None
    try:
        handle, call, facts = bring_up(cell, ctx)
        schedule = ctx["traffic"].schedule(
            mix, seed=ctx["seed"], seconds=seconds, vocab=m["vocab_size"])
        trace = None
        if ctx["trace"]:
            trace = {"start_s": min(cell["trace"]["start_s"], seconds / 4),
                     "seconds": min(cell["trace"]["seconds"], seconds / 2)}
        compiles0 = call("bench_device")["compiles"]
        window_start_unix = time.time()
        win = offer(handle, call, schedule, seconds=seconds,
                    client_threads=cell["client_threads"], trace=trace)
        step_s = call("bench_steps")
        device = call("bench_device")
    finally:
        if handle is not None:
            serve.shutdown()
        ray_tpu.shutdown()
    w = reduce_window(win, seconds=seconds, vocab=m["vocab_size"])
    w.update(model_step_s=step_s, replica_ready_s=facts["replica_ready_s"],
             warm_s=facts["warm_s"], check=facts["check"],
             compiles_in_window=device["compiles"] - compiles0,
             forward_compiles=device["forward_compiles"])

    c = facts["check"]
    rel = c["max_abs_diff"] / max(c["reference_max_abs"], 1e-30)
    problems = []
    if rel > chk["logits_rel_tolerance"]:
        problems.append(f"last-position logits off by {c['max_abs_diff']} "
                        f"of {c['reference_max_abs']} ({rel:.4f}), tolerance "
                        f"{chk['logits_rel_tolerance']}")
    want = m["program"].get("param_dtype", "float32")
    if device["param_dtypes"] != [want]:
        problems.append(f"parameters are {device['param_dtypes']}, the "
                        f"configuration says {want}")
    if w["failed"]:
        problems.append(f"{w['failed']} of {w['requests']} requests failed: "
                        f"{w['failures']}")
    if w["compiles_in_window"]:
        problems.append(f"{w['compiles_in_window']} compilation(s) inside "
                        "the window")
    want_first = cell["traffic"]["output_len"]["min"]
    if len(facts["first_stream"]) != want_first:
        problems.append(f"the warm-up stream gave "
                        f"{len(facts['first_stream'])} tokens, not "
                        f"{want_first}")

    ok_gaps = w["gap_s"] or [seconds]
    e2e = {
        "setup_s": window_start_unix - ctx["process_start_unix"],
        "serve_gap_p95_ms": 1e3 * stats.percentile(ok_gaps, 95),
        "serve_tokens_per_s": w["tokens_in_window"] / w["window_s"],
    }
    say("serve", requests=w["requests"], failed=w["failed"],
        unfinished_after_drain=win["unfinished"],
        tokens_in_window=w["tokens_in_window"], gaps=len(w["gap_s"]),
        serve_ttft_p50_ms=round(1e3 * stats.median(w["ttft_s"]), 3),
        serve_gap_p50_ms=round(1e3 * stats.median(ok_gaps), 3),
        engine_steps=w["engine_steps"], engine_emitted=w["engine_emitted"],
        shed=w["engine_shed"], forward_compiles=w["forward_compiles"],
        compiles_in_window=w["compiles_in_window"])
    say("serve", ttft_ms={q: round(1e3 * stats.percentile(w["ttft_s"], q), 1)
                          for q in (25, 50, 75, 90, 100)},
        ttft_mean_ms=round(1e3 * sum(w["ttft_s"]) / len(w["ttft_s"]), 1),
        gap_ms={q: round(1e3 * stats.percentile(ok_gaps, q), 1)
                for q in (50, 90, 95, 99)},
        model_step_ms={q: round(1e3 * stats.percentile(step_s or [0.0], q), 1)
                       for q in (10, 50, 90)}, model_steps=len(step_s))
    say("check", max_abs_diff=c["max_abs_diff"],
        reference_max_abs=c["reference_max_abs"], rel=rel,
        tolerance=chk["logits_rel_tolerance"],
        argmax_agree=c["argmax_program"] == c["argmax_reference"])
    say("memory", per_device=device["memory"])
    return {
        "e2e": e2e, "obs": w,
        "device": {k: device[k] for k in (
            "platform", "kind", "count", "memory_peak_bytes")},
        "trace": win["trace"],
        "correct": not problems, "problems": problems,
        "attempted": w["requests"], "failed": w["failed"],
    }
