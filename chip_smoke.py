"""chip_smoke.py — does the main path still start on the chip?

One driver process that never touches jax drives the two normal entry
points once, at Llama-2-7B width with the depth cut
(``LlamaConfig.llama2_7b_smoke``), on whatever TPU host it is run on:

  train   JaxTrainer.fit: one worker leased one chip, batches from a
          ray_tpu.data shard, a few AdamW steps on a repeated seeded batch
  train2  a second fit(): the first worker's chip was released, and the
          second process finds the first one's compiled step in the cache
  serve   serve.run(llm.build_llama_app(...)): streamed generations through
          the handle, base model and two LoRA adapters, one chip
  tasks   plain tasks: two chip leases one after the other get the chip in
          two processes, and a task without a lease stays on the CPU
  four    only where the node has >= 4 chips: the same loop in one worker
          over four chips (fsdp=4), then four one-chip actors side by side

Every phase checks what came out (device, shapes, finite falling loss, the
flash kernel against the float32 reference, the Mosaic custom call in the
compiled step, token streams) and any failure ends the run with a non-zero
exit code. Times are printed as information; nothing here is a benchmark.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
with the device as jax reported it from inside the workers.

``--cpu-rehearsal`` walks the same control flow on the CPU with the tiny
preset, for debugging the script where there is no chip. It says so in
every line, checks no device property, and is never a pass (exit code 3).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

PRESET = "llama2_7b_smoke"
BATCH, SEQ, STEPS = 4, 2048, 5
# serving: prompt + generated tokens stay under one 128 bucket, and the one
# allowed batch width keeps the forward to two compiles (base, adapted)
SERVE_BUCKET, SERVE_PROMPT_LEN, SERVE_NEW = 128, 96, 6
SERVE_BATCH = 4
LOSS_TOLERANCE_4_VS_1 = 0.05

_lines = []


def say(phase: str, **fields) -> None:
    line = f"[smoke] {phase}: " + " ".join(
        f"{k}={v}" for k, v in fields.items())
    _lines.append(line)
    print(line, flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _tokens_for_rows(ids, batch: int, seq: int, vocab: int) -> dict:
    """Row id -> tokens, by arithmetic from (id mod batch): every batch of
    the stream is the same seeded batch, so the loss must fall."""
    import numpy as np

    rows = (np.asarray(ids, dtype=np.int64) % batch).reshape(-1, 1)
    pos = np.arange(seq + 1, dtype=np.int64)[None, :]
    tok = ((rows * 1000003 + pos * 7919 + 17) % vocab).astype(np.int32)
    return {"inputs": tok[:, :-1], "targets": tok[:, 1:]}


# --------------------------------------------------------------- the workers
def _where_am_i() -> dict:
    """This process's devices as jax reports them, after a small sum on
    the default device. Runs in workers only."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "jax_platforms": os.environ.get("JAX_PLATFORMS"),
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "pid": os.getpid(),
        "sum": float(jnp.ones((8, 128)).sum()),
    }


def train_loop(config: dict) -> None:
    """Runs inside the JaxTrainer worker: the only place jax is touched."""
    import re

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding

    from ray_tpu import train
    from ray_tpu.models.llama import (
        LlamaConfig, init_llama, llama_logical_axes, llama_loss)
    from ray_tpu.ops.attention import attention, reference_attention
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import logical_to_spec
    from ray_tpu.parallel.train_step import (
        create_train_state, make_train_step)

    rehearsal = config["rehearsal"]
    devices = jax.devices()
    info = _where_am_i()
    chips = config["chips"]
    if not rehearsal:
        check(info["platform"] == "tpu", f"worker is on {info}")
        check(len(devices) == chips,
              f"leased {chips} chip(s), jax sees {len(devices)}: {info}")
        cache_before = set(os.listdir(info["compile_cache_dir"])) \
            if os.path.isdir(info["compile_cache_dir"]) else set()

    cfg = getattr(LlamaConfig, config["preset"])()
    batch, seq = config["batch"], config["seq"]
    info["model"] = (
        f"layers={cfg.num_layers} hidden={cfg.hidden} mlp={cfg.mlp_hidden} "
        f"heads={cfg.num_heads}x{cfg.head_dim} vocab={cfg.vocab_size} "
        f"params={cfg.num_params() / 1e6:.0f}M "
        f"act={jnp.dtype(cfg.dtype).name} attn_impl={cfg.attn_impl}")

    # the kernel against the repo's float32 reference, on a small input
    if config["kernel_check"]:
        ks = jax.random.split(jax.random.key(7), 3)
        shape_q = (1, 256, 4, cfg.head_dim)
        q, k, v = (jax.random.normal(kk, shape_q, jnp.float32).astype(
            cfg.dtype) for kk in ks)

        def summed(impl):
            return lambda q, k, v: attention(
                q, k, v, impl=impl).astype(jnp.float32).sum()

        def rel_err(got, want):
            want = want.astype(jnp.float32)
            return float(jnp.abs(got.astype(jnp.float32) - want).max()
                         / jnp.abs(want).max())

        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
        out_f = attention(q, k, v, impl="flash")
        g_f = jax.grad(summed("flash"), argnums=(0, 1, 2))(q, k, v)
        with jax.default_matmul_precision("highest"):
            out_r = reference_attention(q32, k32, v32)
            g_r = jax.grad(summed("reference"), argnums=(0, 1, 2))(
                q32, k32, v32)
        info["kernel_fwd_rel_err"] = rel_err(out_f, out_r)
        info["kernel_bwd_rel_err"] = max(
            rel_err(a, b) for a, b in zip(g_f, g_r))
        # the kernel's results are rounded to bf16: 2^-8 of the largest value
        check(info["kernel_fwd_rel_err"] < 2e-2
              and info["kernel_bwd_rel_err"] < 2e-2,
              f"flash kernel disagrees with the reference: {info}")

    mesh = create_mesh(MeshConfig(data=1, fsdp=len(devices)))
    tx = optax.adamw(config["lr"])
    batch_sharding = NamedSharding(
        mesh, logical_to_spec(("batch", "seq")))
    with jax.set_mesh(mesh):
        state, shardings = create_train_state(
            lambda key: init_llama(cfg, key), tx, mesh,
            llama_logical_axes(cfg), seed=config["seed"])
        step = make_train_step(
            lambda p, b: llama_loss(p, b, cfg), tx, mesh, shardings,
            batch_logical_axes=("batch", "seq"))
        batches = train.get_dataset_shard("train").iter_jax_batches(
            batch_size=batch,
            dtypes={"inputs": jnp.int32, "targets": jnp.int32},
            sharding=batch_sharding, prefetch_batches=2)
        first = next(batches)
        check(first["inputs"].shape == (batch, seq),
              f"batch shape {first['inputs'].shape}")

        # parameters: where they live, and how much of them on each device
        leaves = jax.tree.leaves(state.params)
        total_bytes = sum(x.nbytes for x in leaves)
        per_device = {d.id: 0 for d in devices}
        for x in leaves:
            for shard in x.addressable_shards:
                per_device[shard.device.id] += shard.data.nbytes
        info["param_bytes_total"] = total_bytes
        info["param_bytes_per_device"] = sorted(per_device.values())

        t0 = time.perf_counter()
        compiled = step.lower(state, first).compile()
        info["compile_s"] = round(time.perf_counter() - t0, 2)
        # Pallas TPU kernels appear in the compiled module as custom calls
        # to Mosaic; the first result of each says whose rows it works on
        mosaic_lines = [ln for ln in compiled.as_text().splitlines()
                        if 'custom_call_target="tpu_custom_call"' in ln]
        calls = [m.group(1) for m in (
            re.search(r"=\s*\(?\s*(\w+\[[\d,]+\])", ln)
            for ln in mosaic_lines) if m]
        info["mosaic_custom_calls"] = len(mosaic_lines)
        info["mosaic_first_results"] = sorted(set(calls))
        info["mosaic_sample"] = mosaic_lines[0].strip()[:240] \
            if mosaic_lines else None

        losses, step_s = [], []
        b = first
        for i in range(config["steps"]):
            t = time.perf_counter()
            state, metrics = compiled(state, b)
            losses.append(float(metrics["loss"]))  # D2H: closes the timing
            step_s.append(round(time.perf_counter() - t, 3))
            if i == 0:
                info["first_step_s"] = round(time.perf_counter() - t0, 2)
            train.report({"step": i, "loss": losses[-1]})
            if i + 1 < config["steps"]:
                b = next(batches)
    info["losses"] = [round(x, 4) for x in losses]
    info["step_s"] = step_s
    info["memory"] = [
        {k: (d.memory_stats() or {}).get(k)
         for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
        for d in devices]
    check(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    if config["steps"] > 1:
        check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
          f"first loss {losses[0]} is not near ln(vocab) for a random model")
    if not rehearsal:
        check(info["mosaic_custom_calls"] >= 3,
              "compiled step holds no Mosaic custom call (want the forward, "
              f"dq and dk/dv kernels): {info['mosaic_custom_calls']}")
        check(all(m["bytes_in_use"] for m in info["memory"]),
              f"a device holds nothing: {info['memory']}")
        quarter = total_bytes / len(devices)
        check(all(abs(n - quarter) < 0.02 * quarter
                  for n in per_device.values()),
              f"parameters are not split evenly: {info}")
        # the kernel ran on each device's own rows, not on the gathered batch
        lead = {int(c.split("[")[1].split(",")[0]) for c in calls}
        check(lead == {batch // len(devices)},
              f"flash custom calls see leading dims {lead}, want "
              f"{batch // len(devices)} (batch {batch} / {len(devices)}): "
              f"{info['mosaic_sample']}")
        cache_after = set(os.listdir(info["compile_cache_dir"]))
        check(bool(cache_after),
              f"nothing in the compile cache {info['compile_cache_dir']}")
        info["cache_entries"] = len(cache_after)
        info["cache_new_entries"] = len(cache_after - cache_before)
    train.report({"final": True, **info})


# ---------------------------------------------------------------- the driver
def run_fit(name: str, *, rehearsal: bool, chips: int, steps: int,
            kernel_check: bool, storage: str) -> dict:
    import ray_tpu.data as rdata
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    preset = "tiny" if rehearsal else PRESET
    batch, seq, vocab = (4, 64, 256) if rehearsal else (BATCH, SEQ, 32000)
    ds = rdata.range(batch * steps, parallelism=2).map_batches(
        lambda tbl: _tokens_for_rows(tbl["id"], batch, seq, vocab),
        batch_size=batch)
    resources = {"CPU": 1} if rehearsal else {"TPU": chips, "CPU": 1}
    t0 = time.time()
    result = JaxTrainer(
        train_loop,
        train_loop_config={
            "preset": preset, "batch": batch, "seq": seq, "steps": steps,
            "lr": 3e-4, "seed": 0, "chips": chips, "rehearsal": rehearsal,
            "kernel_check": kernel_check},
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=not rehearsal,
            resources_per_worker=resources),
        run_config=RunConfig(name=name, storage_path=storage),
        datasets={"train": ds},
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    check(bool(m.get("final")), f"{name}: last report is not the final one")
    say(name, platform=m["platform"], device_kind=repr(m["device_kind"]),
        devices=m["device_count"], TPU_VISIBLE_CHIPS=m["visible_chips"],
        JAX_PLATFORMS=m["jax_platforms"], pid=m["pid"])
    say(name, preset=preset, model=f"[{m['model']}]", batch=batch, seq=seq,
        steps=steps, losses=m["losses"])
    say(name, compile_s=m["compile_s"], first_step_s=m["first_step_s"],
        step_s=m["step_s"], fit_wall_s=round(time.time() - t0, 1),
        compile_cache_dir=m["compile_cache_dir"],
        cache_entries=m.get("cache_entries"),
        cache_new_entries=m.get("cache_new_entries"))
    say(name, mosaic_custom_calls=m["mosaic_custom_calls"],
        kernel_results=m["mosaic_first_results"],
        sample=repr(m["mosaic_sample"]),
        kernel_fwd_rel_err=m.get("kernel_fwd_rel_err"),
        kernel_bwd_rel_err=m.get("kernel_bwd_rel_err"))
    say(name, param_bytes_total=m["param_bytes_total"],
        param_bytes_per_device=m["param_bytes_per_device"],
        memory=json.dumps(m["memory"]))
    return m


def run_serve(*, rehearsal: bool) -> dict:
    from ray_tpu import serve
    from ray_tpu.serve import llm

    preset = "tiny" if rehearsal else PRESET
    vocab = 256 if rehearsal else 32000
    t0 = time.time()
    handle = serve.run(
        llm.build_llama_app(
            config=preset, lora_rank=4, max_batch_size=SERVE_BATCH,
            allowed_batch_sizes=(SERVE_BATCH,), max_new_tokens=SERVE_NEW,
            seq_bucket=SERVE_BUCKET,
            ray_actor_options={} if rehearsal else {"num_tpus": 1}),
        name="llama", wait_timeout_s=300.0)
    ready_s = round(time.time() - t0, 1)
    try:
        prompt = [(i * 7919 + 17) % vocab for i in range(SERVE_PROMPT_LEN)]
        adapters = ["", "", "a1", "a1", "a2", "a2"]
        t1 = time.time()
        streams = [
            handle.options(stream=True).remote(
                {"prompt": prompt, "max_new": SERVE_NEW, "adapter": a})
            for a in adapters]
        outs = [list(s) for s in streams]
        gen_s = round(time.time() - t1, 1)
        info = handle.device_info.remote().result(timeout_s=60)
        stats = handle.engine_stats.remote().result(timeout_s=60)
    finally:
        serve.shutdown()
    for a, toks in zip(adapters, outs):
        check(len(toks) == SERVE_NEW
              and all(isinstance(t, int) and 0 <= t < vocab for t in toks),
              f"adapter {a!r}: bad stream {toks}")
    # same prompt, same weights, greedy: the two streams of a pair agree
    for i in (0, 2, 4):
        check(outs[i] == outs[i + 1],
              f"adapter {adapters[i]!r}: {outs[i]} != {outs[i + 1]}")
    if not rehearsal:
        check(info["platform"] == "tpu" and info["device_count"] == 1,
              f"replica is on {info}")
        check(info["attn_impl"] == "flash", str(info))
        check(info["forward_compiles"] <= 2,
              f"forward compiled {info['forward_compiles']} times")
    say("serve", platform=info["platform"],
        device_kind=repr(info["device_kind"]), devices=info["device_count"],
        TPU_VISIBLE_CHIPS=info["visible_chips"], pid=info["pid"],
        preset=preset, layers=info["num_layers"], hidden=info["hidden"],
        attn_impl=info["attn_impl"])
    say("serve", ready_s=ready_s, generate_s=gen_s,
        requests=len(outs), tokens_each=SERVE_NEW,
        forward_compiles=info["forward_compiles"],
        compile_cache_dir=info["compile_cache_dir"],
        engine_steps=stats.get("steps"))
    # what the persistent cache did with this bring-up's programs
    say("serve", **{k: round(v, 2) if isinstance(v, float) else v
                    for k, v in info["compiles"].items() if k != "slowest"})
    say("serve", base=outs[0], a1=outs[2], a2=outs[4])
    return info


def run_tasks() -> None:
    import ray_tpu

    where = ray_tpu.remote(_where_am_i)
    first = ray_tpu.get(where.options(num_tpus=1).remote(), timeout=300)
    unleased = ray_tpu.get(where.remote(), timeout=300)
    time.sleep(1.0)  # past lease_idle_ttl_ms: the first lease is returned
    second = ray_tpu.get(where.options(num_tpus=1).remote(), timeout=300)
    say("tasks", first=json.dumps(first), unleased=json.dumps(unleased),
        second=json.dumps(second))
    for leased in (first, second):
        check(leased["platform"] == "tpu" and leased["device_count"] == 1
              and leased["sum"] == 1024.0, f"chip task: {leased}")
    check(first["pid"] != second["pid"], "a chip lease reused a process")
    check(unleased["platform"] == "cpu"
          and unleased["visible_chips"] is None,
          f"a task without a chip lease is on {unleased}")


def run_four_actors() -> None:
    import ray_tpu

    @ray_tpu.remote(num_tpus=1, num_cpus=1)
    class OneChip:
        def look(self):
            return _where_am_i()

    actors = [OneChip.remote() for _ in range(4)]
    try:
        seen = ray_tpu.get([a.look.remote() for a in actors], timeout=300)
    finally:
        for a in actors:
            ray_tpu.kill(a)
    say("four-actors", seen=json.dumps(seen))
    check(all(s["platform"] == "tpu" and s["device_count"] == 1
              and s["sum"] == 1024.0 for s in seen), str(seen))
    check(sorted(s["visible_chips"] for s in seen) == ["0", "1", "2", "3"],
          f"chips not distinct: {[s['visible_chips'] for s in seen]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="control flow only, tiny preset, never a pass")
    args = ap.parse_args()
    rehearsal = args.cpu_rehearsal

    import ray_tpu
    import ray_tpu.serve  # noqa: F401  (the driver-side imports stay off jax)
    import ray_tpu.train.jax  # noqa: F401

    if rehearsal:
        say("REHEARSAL", note="CPU control-flow rehearsal: no device "
            "property is checked and this run is not a pass")
        ray_tpu.init(num_cpus=4)
    else:
        ray_tpu.init()  # detected resources: what the node really holds
    device = None
    try:
        from ray_tpu._private import worker as worker_mod

        node_chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        say("node", TPU=node_chips, CPU=ray_tpu.cluster_resources()["CPU"],
            object_store=type(worker_mod.global_worker.store).__name__,
            JAX_COMPILATION_CACHE_DIR=os.environ.get(
                "JAX_COMPILATION_CACHE_DIR", "(unset: <checkout>/.jax_cache)"),
            driver_JAX_PLATFORMS=os.environ.get("JAX_PLATFORMS"))
        if not rehearsal and node_chips < 1:
            raise RuntimeError(
                "no TPU chip found on this node (looked for /dev/accel* and "
                "/dev/vfio/[0-9]*); chip_smoke.py only passes on the chip")
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as storage:
            one = run_fit("train", rehearsal=rehearsal, chips=1, steps=STEPS,
                          kernel_check=True, storage=storage)
            two = run_fit("train2", rehearsal=rehearsal, chips=1, steps=1,
                          kernel_check=False, storage=storage)
            check(two["pid"] != one["pid"], "second fit reused the process")
            check(abs(two["losses"][0] - one["losses"][0]) < 1e-3,
                  f"same seed, same batch, other first loss: {two['losses']}")
            if not rehearsal:
                # A first fit that wrote entries compiled cold, and the
                # second must then find them. One that wrote none found an
                # earlier run's entries itself: both were hits.
                cold = one["cache_new_entries"] > 0
                check(not cold or (
                    two["compile_s"] < 0.5 * one["compile_s"]
                    and two["first_step_s"] < one["first_step_s"]),
                    "second process missed the compile cache: compile "
                    f"{two['compile_s']}s after {one['compile_s']}s")
                say("train2", first_fit="compiled cold" if cold
                    else "found a warm cache",
                    first_step_s_first_fit=one["first_step_s"],
                    first_step_s_second_fit=two["first_step_s"],
                    compile_s_first_fit=one["compile_s"],
                    compile_s_second_fit=two["compile_s"],
                    second_fit_cache_hit=True)
            replica = run_serve(rehearsal=rehearsal)
            if not rehearsal:
                run_tasks()
            device = {"platform": one["platform"],
                      "kind": one["device_kind"],
                      "count": one["device_count"]}
            if not rehearsal and node_chips >= 4:
                four = run_fit("train-4chip", rehearsal=False, chips=4,
                               steps=2, kernel_check=False, storage=storage)
                diff = abs(four["losses"][0] - one["losses"][0])
                say("train-4chip", first_loss_1chip=one["losses"][0],
                    first_loss_4chip=four["losses"][0],
                    abs_diff=round(diff, 5))
                check(diff < LOSS_TOLERANCE_4_VS_1,
                      f"four-chip first loss off by {diff}")
                check(four["device_count"] == node_chips,
                      f"node advertises {node_chips} chips, an unmasked "
                      f"worker sees {four['device_count']}")
                run_four_actors()
                device["count"] = four["device_count"]
            elif not rehearsal:
                say("four", skipped=f"node advertises {node_chips} chip(s); "
                    "the four-chip phases need 4")
                check(one["device_count"] == node_chips,
                      f"node advertises {node_chips} chip(s), an unmasked "
                      f"worker sees {one['device_count']}")
            check(replica["platform"] == one["platform"], "replica platform")
    finally:
        ray_tpu.shutdown()
    check("jax" not in sys.modules, "the driver imported jax")
    say("driver", jax_in_sys_modules=False)
    # the workers' chatter is over: what mattered, once more, then the result
    print("== chip_smoke summary ==")
    print("\n".join(_lines))
    if rehearsal:
        print("REHEARSAL ONLY — not a pass", flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
