"""Training cells: ``JaxTrainer.fit`` with one worker that holds the cell's chips.

``run`` is the harness side and never touches jax. ``train_loop`` is the
benchmark's own loop and runs inside the worker, the only process that
holds the chip: it builds the model from the configuration file through
the file's family (``benchmark/families/``), checks the program's loss
against the family's float32 reference, warms the step up, runs
the measured window, traces a few steps of it when asked, reads the
device's memory, and sends everything back in its last ``train.report``.
"""

from __future__ import annotations

import functools
import math
import shutil
import tempfile
import time
from typing import Any, Dict


# ------------------------------------------------------------ worker side
def train_loop(config: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding

    from benchmark.harness import loader, onchip, xplane
    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_to_spec
    from ray_tpu.parallel.train_step import (
        create_train_state, make_eval_step, make_train_step)

    t_loop0 = time.time()
    cell, rehearsal = config["cell"], config["rehearsal"]
    m, tr, mix = cell["model"], cell["train"], cell["traffic"]
    seconds, do_trace = config["seconds"], config["trace"]

    devices = jax.devices()
    device = onchip.device_facts()
    if not rehearsal:
        onchip.require_chips(device, cell["chips"])
    compiles = onchip.count_compiles()

    model = loader.load_family(m).training(m)
    rows, seq = mix["rows_per_step"], mix["seq"]
    # the cell's own axes, or every chip on fsdp
    mesh = create_mesh(MeshConfig(
        **tr.get("mesh", {"data": 1, "fsdp": len(devices)})))
    tx = optax.adamw(tr["lr"])
    batch_sharding = NamedSharding(mesh, logical_to_spec(("batch", "seq")))
    loss_fn = model["loss"]
    out: Dict[str, Any] = {}
    with jax.set_mesh(mesh):
        t = time.time()
        state, shardings = create_train_state(
            model["init"], tx, mesh, model["logical_axes"],
            seed=config["seed"] % (2 ** 31))
        step = make_train_step(loss_fn, tx, mesh, shardings,
                               batch_logical_axes=("batch", "seq"))
        jax.block_until_ready(state)
        out["init_s"] = time.time() - t
        batches = train.get_dataset_shard("train").iter_jax_batches(
            batch_size=rows, dtypes={"inputs": jnp.int32,
                                     "targets": jnp.int32},
            sharding=batch_sharding, prefetch_batches=tr["prefetch_batches"])
        t = time.time()
        first = next(batches)
        out["first_batch_s"] = time.time() - t
        if first["inputs"].shape != (rows, seq):
            raise RuntimeError(f"batch shape {first['inputs'].shape}, the "
                               f"mix says {(rows, seq)}")

        # --- correct: the program's loss against the reference's, on one
        # seeded sequence (every row of the check batch is that sequence)
        t = time.time()
        dtypes = {str(x.dtype) for x in jax.tree.leaves(state.params)}
        want = str(jnp.dtype(m["program"].get("param_dtype", "float32")))
        one_in = np.asarray(first["inputs"])[:1]
        one_tg = np.asarray(first["targets"])[:1]
        check_batch = {
            "inputs": jax.device_put(np.repeat(one_in, rows, 0),
                                     batch_sharding),
            "targets": jax.device_put(np.repeat(one_tg, rows, 0),
                                      batch_sharding)}
        eval_step = make_eval_step(loss_fn, mesh, shardings,
                                   batch_logical_axes=("batch", "seq"))
        loss_program = float(eval_step(state.params, check_batch))
        loss_reference = float(loader.load_reference(m).loss(
            state.params, jnp.asarray(one_in[0]), jnp.asarray(one_tg[0]), m))
        out["check"] = {
            "loss_program": loss_program, "loss_reference": loss_reference,
            "abs_diff": abs(loss_program - loss_reference),
            "tolerance": tr["check"]["loss_abs_tolerance"],
            "param_dtypes": sorted(dtypes), "param_dtype_wanted": want,
            "seconds": time.time() - t}

        # --- warm-up: the one shape this cell uses, twice
        t = time.time()
        b = first
        for _ in range(2):
            state, metrics = step(state, b)
            b = next(batches)
        first_loss = float(metrics["loss"])
        jax.block_until_ready(state)
        out["warmup_s"] = time.time() - t
        out["compiles_before_window"] = len(compiles)
        out["compile_s_before_window"] = sum(compiles)
        train.report({"phase": "warm", "loss": first_loss})

        # --- the measured window
        report_every, max_steps = tr["report_every"], tr["max_steps"]
        trace_at = tr["trace_at_step"] if do_trace else -1
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") \
            if do_trace else None
        input_wait, losses, syncs = [], [], []
        traced: Dict[str, Any] = {}
        n = 0
        n_compiles0 = len(compiles)
        out["window_start_unix"] = time.time()
        t0 = time.perf_counter()
        syncs.append((0, t0))
        while n < max_steps and time.perf_counter() - t0 < seconds:
            if n == trace_at:
                jax.block_until_ready(state)
                jax.profiler.start_trace(
                    trace_dir, profiler_options=xplane.profile_options())
                traced = {"t0": time.perf_counter(), "step0": n}
            with jax.profiler.TraceAnnotation("bench:input_wait"):
                t_in = time.perf_counter()
                nxt = next(batches)
                input_wait.append(time.perf_counter() - t_in)
            with jax.profiler.TraceAnnotation("bench:step_dispatch"):
                state, metrics = step(state, b)
            b = nxt
            n += 1
            if n % report_every == 0:
                with jax.profiler.TraceAnnotation("bench:report"):
                    losses.append(float(metrics["loss"]))
                    train.report({"step": n, "loss": losses[-1]})
                syncs.append((n, time.perf_counter()))
            if traced and "t1" not in traced \
                    and n == traced["step0"] + tr["trace_steps"]:
                jax.block_until_ready(state)
                traced["t1"] = time.perf_counter()
                traced["steps"] = n - traced["step0"]
                jax.profiler.stop_trace()
        jax.block_until_ready(state)
        elapsed = time.perf_counter() - t0
        if traced and "t1" not in traced:  # the window closed inside it
            traced["t1"] = time.perf_counter()
            traced["steps"] = n - traced["step0"]
            jax.profiler.stop_trace()
        losses.append(float(metrics["loss"]))

    out.update({
        "steps": n, "elapsed_s": elapsed, "tokens_per_step": rows * seq,
        "first_loss": first_loss, "losses": losses,
        "input_wait_s": input_wait,
        "step_s_between_syncs": [
            (t_b - t_a) / (n_b - n_a)
            for (n_a, t_a), (n_b, t_b) in zip(syncs, syncs[1:])],
        "compiles_in_window": len(compiles) - n_compiles0,
        "step_cache_size": step._cache_size(),
        "device": onchip.device_facts(),
    })
    if traced:
        path = xplane.find_xplane(trace_dir)
        reduced = xplane.reduce_trace(path, rehearsal=rehearsal)
        reduced["window_s"] = traced["t1"] - traced["t0"]
        reduced["steps"] = traced["steps"]
        out["trace"] = reduced
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["loop_wall_s"] = time.time() - t_loop0
    train.report({"final": True, "bench": out})


# ----------------------------------------------------------- harness side
def run(cell: Dict[str, Any], ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Drive one training cell; returns the observations the readers and
    the last line are made from. ``ctx``: seed, seconds, trace, rehearsal,
    process_start_unix, traffic (the generator module), say."""
    import ray_tpu
    import ray_tpu.data as rdata
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    say, rehearsal = ctx["say"], ctx["rehearsal"]
    mix, tr, m = cell["traffic"], cell["train"], cell["model"]
    chips = cell["chips"]
    if rehearsal:
        ray_tpu.init(num_cpus=4)
    else:
        ray_tpu.init()
    storage = tempfile.mkdtemp(prefix="bench_fit_")
    try:
        node_chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not rehearsal and node_chips < chips:
            raise RuntimeError(
                f"the cell asks for {chips} TPU chip(s), the node has "
                f"{node_chips}")
        rows = mix["rows_per_step"]
        n_rows = rows * (tr["max_steps"] + 8)
        ds = rdata.range(n_rows, parallelism=tr["data_blocks"]).map_batches(
            functools.partial(ctx["traffic"].rows, params=mix,
                              seed=ctx["seed"], vocab=m["vocab_size"]),
            batch_size=rows)
        resources = {"CPU": 1} if rehearsal else {"TPU": chips, "CPU": 1}
        t_fit = time.time()
        result = JaxTrainer(
            train_loop,
            train_loop_config={
                "cell": cell, "seed": ctx["seed"],
                "seconds": ctx["seconds"], "trace": ctx["trace"],
                "rehearsal": rehearsal},
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=not rehearsal,
                resources_per_worker=resources),
            run_config=RunConfig(name="bench-" + cell["name"],
                                 storage_path=storage),
            datasets={"train": ds},
        ).fit()
        fit_wall = time.time() - t_fit
        if result.error is not None:
            raise result.error
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
    metrics = result.metrics
    if not metrics.get("final"):
        raise RuntimeError("the worker's last report is not the final one")
    w = metrics["bench"]
    w["fit_wall_s"] = fit_wall
    w["fit_start_unix"] = t_fit

    check, tol = w["check"], tr["check"]
    ln_vocab = math.log(m["vocab_size"])
    losses = [w["first_loss"]] + w["losses"]
    problems = []
    if check["abs_diff"] > tol["loss_abs_tolerance"]:
        problems.append(
            f"loss {check['loss_program']} against the reference's "
            f"{check['loss_reference']}: off by {check['abs_diff']}, "
            f"tolerance {tol['loss_abs_tolerance']}")
    if check["param_dtypes"] != [check["param_dtype_wanted"]]:
        problems.append(f"parameters are {check['param_dtypes']}, the "
                        f"configuration says {check['param_dtype_wanted']}")
    if not all(math.isfinite(x) for x in losses):
        problems.append(f"a loss is not finite: {losses}")
    if abs(w["first_loss"] - ln_vocab) > tol["first_loss_within_of_ln_vocab"]:
        problems.append(f"first loss {w['first_loss']} is not within "
                        f"{tol['first_loss_within_of_ln_vocab']} of ln(vocab) "
                        f"{ln_vocab}")
    if w["compiles_in_window"]:
        problems.append(f"{w['compiles_in_window']} compilation(s) inside "
                        "the window")
    if w["steps"] < 1:
        problems.append("no step finished in the window")

    chips_seen = w["device"]["count"]
    tokens = w["steps"] * w["tokens_per_step"]
    e2e = {
        "setup_s": w["window_start_unix"] - ctx["process_start_unix"],
        "train_tokens_per_s_per_chip": tokens / w["elapsed_s"] / chips_seen,
    }
    step_ms = sorted(1e3 * s for s in w["step_s_between_syncs"])
    say("train", steps=w["steps"], elapsed_s=round(w["elapsed_s"], 3),
        train_step_ms_p50=round(step_ms[len(step_ms) // 2], 3)
        if step_ms else None,
        first_loss=round(w["first_loss"], 4),
        last_loss=round(w["losses"][-1], 4), reports=len(w["losses"]))
    say("train", init_s=round(w["init_s"], 2),
        first_batch_s=round(w["first_batch_s"], 2),
        check_s=round(check["seconds"], 2), warmup_s=round(w["warmup_s"], 2),
        compiles_before_window=w["compiles_before_window"],
        compile_s=round(w["compile_s_before_window"], 2),
        compiles_in_window=w["compiles_in_window"],
        fit_wall_s=round(fit_wall, 2), loop_wall_s=round(w["loop_wall_s"], 2))
    say("check", loss_program=check["loss_program"],
        loss_reference=check["loss_reference"], abs_diff=check["abs_diff"],
        tolerance=tol["loss_abs_tolerance"],
        param_dtypes=check["param_dtypes"])
    say("memory", per_device=w["device"]["memory"])
    return {
        "e2e": e2e, "obs": w, "device": w["device"],
        "trace": w.get("trace"),
        "correct": not problems, "problems": problems,
        "attempted": w["steps"], "failed": 0,
    }
