"""Headline benchmark: Llama train-step throughput on the local chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference has no TPU training numbers (BASELINE.md); the north-star is
≥40% MFU (SURVEY §6). ``vs_baseline`` is therefore MFU / 0.40 — ≥1.0 beats
the target. Runs the largest Llama decoder that fits one v5e chip's 16 GiB
HBM (a ~1B-param config with 7B-class head/mlp geometry, bf16 activations,
adafactor), falling back to smaller configs on OOM. It measures the chip:
without a TPU, or on a device whose peak is not in the table, it fails.
This process holds the chip; the runtime it starts for the data-fed series
keeps its children on the CPU.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# bf16 peak FLOP/s by jax device_kind (Google Cloud documentation, "TPU
# v5e": 197 TFLOP/s). A device that is not here is an error, not a default.
PEAK_FLOPS = {"TPU v5 lite": 197e12}


def _peak_flops() -> float:
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_FLOPS:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind {kind!r}; "
            f"known: {sorted(PEAK_FLOPS)}")
    return PEAK_FLOPS[kind]


def _tpu_configs():
    """Largest-first ladder; each entry is (cfg, batch, seq, steps)."""
    from ray_tpu.models.llama import LlamaConfig

    ladder = [
        # Llama-2-7B geometry, frozen-base + LoRA (the north-star workload:
        # BASELINE.md "Llama-2-7B fine-tune"; reference gates releases on LLM
        # fine-tunes, release/air_examples/gptj_deepspeed_finetuning). Base
        # in bf16 (13.5 GiB of 16) — only the adapters carry grads/opt state,
        # which is what makes 7B fit one v5e chip at all. Chunked lm-head CE
        # keeps peak logits memory at B*256*V.
        # remat_policy="full": the "dots" policy saves every matmul output
        # (batch-free dot dims), which at 7B geometry is ~1.3 GiB PER MLP
        # TENSOR per layer — full recompute keeps activations ~0.6 GiB so
        # base(13.5) + adapters + workspace fit the 15.75 GiB chip
        ("lora", LlamaConfig(
            vocab_size=32000, hidden=4096, mlp_hidden=11008, num_layers=32,
            num_heads=32, num_kv_heads=32, head_dim=128, max_seq_len=2048,
            remat=True, remat_policy="full", param_dtype=jnp.bfloat16,
            loss_chunk=256, attn_impl="auto"), 1, 2048, 8),
        # ~1.005B: Llama-2-7B geometry at half width/depth, head_dim 128.
        # Sized to v5e HBM: fp32 params + adafactor factored stats + fp32
        # grads peak at ~15.2 of 15.75 GiB (18 layers exceeds it by 16 MiB).
        ("full", LlamaConfig(
            vocab_size=32000, hidden=2048, mlp_hidden=5632, num_layers=17,
            num_heads=16, num_kv_heads=16, head_dim=128, max_seq_len=2048,
            remat=True, attn_impl="auto"), 4, 2048, 8),
        # ~271M fallback (round-1 headline config).
        ("full", LlamaConfig(
            vocab_size=32000, hidden=1024, mlp_hidden=2816, num_layers=16,
            num_heads=8, num_kv_heads=8, head_dim=128, max_seq_len=2048,
            remat=True, attn_impl="auto"), 8, 2048, 10),
    ]
    return ladder


def _time_steps(step, state, b, steps):
    state, m = step(state, b)          # compile
    jax.block_until_ready((state, m))
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, b)
    jax.block_until_ready((state, m))
    return time.perf_counter() - t0


def _run_one(kind, cfg, batch, seq, steps):
    import optax

    from ray_tpu.models.llama import (
        LoraConfig, init_llama, init_lora, llama_logical_axes, llama_loss,
        llama_lora_loss, lora_logical_axes)
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.sharding import param_shardings
    from ray_tpu.parallel.train_step import (
        create_train_state, make_train_step)

    mesh = create_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    tok = rng.integers(0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32)
    b = {"inputs": tok[:, :-1], "targets": tok[:, 1:]}

    if kind == "lora":
        lcfg = LoraConfig(rank=16)
        tx = optax.adamw(1e-4)
        with jax.set_mesh(mesh):
            base = jax.jit(
                lambda k: init_llama(cfg, k),
                out_shardings=param_shardings(llama_logical_axes(cfg), mesh),
            )(jax.random.key(0))
            state, shardings = create_train_state(
                lambda k: init_lora(cfg, lcfg, k), tx, mesh,
                lora_logical_axes(cfg, lcfg), seed=1)
            step = make_train_step(
                lambda lo, bb, fz: llama_lora_loss(fz, lo, bb, cfg, lcfg),
                tx, mesh, shardings, batch_logical_axes=("batch", "seq"),
                frozen=base, frozen_logical_axes=llama_logical_axes(cfg))
            dt = _time_steps(step, state, b, steps)
        flops_tok = cfg.flops_per_token_frozen(lcfg.num_params(cfg), seq)
    else:
        # adafactor (factored second moment, the T5X/PaLM TPU standard):
        # adam's fp32 mu+nu alone would put the 1B config past 16 GiB HBM
        tx = optax.adafactor(1e-3)
        with jax.set_mesh(mesh):
            state, shardings = create_train_state(
                lambda k: init_llama(cfg, k), tx, mesh,
                llama_logical_axes(cfg))
            step = make_train_step(
                lambda p, bb: llama_loss(p, bb, cfg), tx, mesh, shardings,
                batch_logical_axes=("batch", "seq"))
            dt = _time_steps(step, state, b, steps)
        flops_tok = cfg.flops_per_token(seq)

    tok_s = batch * seq * steps / dt
    return tok_s, tok_s * flops_tok / _peak_flops()


def _tokenize_rows(ids: np.ndarray, seq: int, vocab: int) -> dict:
    """Deterministic arithmetic 'tokenizer': row id -> (seq+1) tokens.
    Stands in for a tokenized corpus while remaining reproducible and
    dependency-free; the point of the data-fed series is the PIPELINE
    (streaming executor, backpressure, device feed), not the text."""
    ids = np.asarray(ids, dtype=np.int64).reshape(-1, 1)
    pos = np.arange(seq + 1, dtype=np.int64)[None, :]
    tok = ((ids * 1000003 + pos * 7919 + 17) % vocab).astype(np.int32)
    return {"inputs": tok[:, :-1], "targets": tok[:, 1:]}


def _run_dense_datafed(cfg, batch, seq, steps):
    """The dense train step fed by Dataset.streaming_split /
    iter_jax_batches — real blocks through the streaming executor with
    backpressure — instead of one resident synthetic batch (VERDICT r4
    #6; reference: train/_internal/data_config.py per-worker split +
    dataset.iter_torch_batches under the train loop). Returns (tokens/s,
    timed steps); what the rate is worth is the caller's to say."""
    import optax

    import ray_tpu
    from ray_tpu import data as rdata
    from ray_tpu.models.llama import (
        init_llama, llama_logical_axes, llama_loss)
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.parallel.train_step import (
        create_train_state, make_train_step)

    owns_runtime = not ray_tpu.is_initialized()
    if owns_runtime:
        ray_tpu.init(num_cpus=2)
    try:
        total_rows = batch * (steps + 2)
        vocab = cfg.vocab_size
        ds = rdata.range(total_rows, parallelism=2).map_batches(
            lambda tbl: _tokenize_rows(tbl["id"], seq, vocab),
            batch_size=batch)
        it = ds.streaming_split(1)[0]

        mesh = create_mesh(MeshConfig(data=-1), devices=jax.devices()[:1])
        tx = optax.adafactor(1e-3)
        with jax.set_mesh(mesh):
            state, shardings = create_train_state(
                lambda k: init_llama(cfg, k), tx, mesh,
                llama_logical_axes(cfg))
            step = make_train_step(
                lambda p, bb: llama_loss(p, bb, cfg), tx, mesh, shardings,
                batch_logical_axes=("batch", "seq"))
            batches = it.iter_jax_batches(
                batch_size=batch,
                dtypes={"inputs": jnp.int32, "targets": jnp.int32},
                prefetch_batches=2)
            first = next(batches)
            state, m = step(state, first)   # compile
            jax.block_until_ready((state, m))
            n = 0
            t0 = time.perf_counter()
            for bb in batches:
                state, m = step(state, bb)
                n += 1
                if n >= steps:
                    break
            jax.block_until_ready((state, m))
            dt = time.perf_counter() - t0
        if n == 0:
            raise RuntimeError("dataset yielded no timed batches")
        return batch * seq * n / dt, n
    finally:
        if owns_runtime:
            try:
                ray_tpu.shutdown()
            except Exception:
                pass


def _hw_util(kind, cfg, mfu, seq) -> float:
    """Executed-FLOPs utilization: model MFU counts USEFUL flops (4N for a
    frozen base, 6N dense), but the chip also executes the full-remat
    forward recompute (+2N) the 16 GiB HBM forces at 7B. This rescales
    model-MFU by executed/useful so the two series are comparable — it is
    the number that says whether the MXU pipeline itself is healthy."""
    n = cfg.num_params()
    attn = 12.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim * seq
    if kind == "lora":
        useful = 4.0 * n + attn          # adapters negligible here
        executed = useful + (2.0 * n + 0.5 * attn
                             if cfg.remat_policy == "full" else 0.0)
    else:
        useful = 6.0 * n + attn
        executed = useful                # dots remat recomputes ~no matmuls
    return mfu * executed / useful


def main() -> list:
    """Run the ladder and print the one result line. Returns the names of
    the series that failed; the caller turns them into the exit code."""
    import gc

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip and found platform {platform!r}")
    _peak_flops()  # an unknown device fails before anything is timed
    ladder = _tpu_configs()

    failed_series = []
    last_err = None
    for idx, (kind, cfg, batch, seq, steps) in enumerate(ladder):
        try:
            tok_s, mfu = _run_one(kind, cfg, batch, seq, steps)
        except Exception as e:  # OOM on smaller chips: walk down the ladder
            oom = any(t in str(e) for t in
                      ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory"))
            if oom:
                # drop the traceback: its frames pin the failed attempt's
                # device buffers, which would OOM the smaller fallback too
                try:
                    last_err = type(e)(str(e))
                except Exception:
                    last_err = RuntimeError(str(e))
                e.__traceback__ = None
                del e
                gc.collect()
                continue
            raise
        tag = "lora ft, " if kind == "lora" else ""
        result = {
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": round(tok_s, 1),
            "unit": f"tokens/s ({cfg.num_params()/1e6:.0f}M params, {tag}"
                    f"{platform}, mfu={mfu:.3f}, "
                    f"hw_util={_hw_util(kind, cfg, mfu, seq):.3f})",
            "vs_baseline": round(mfu / 0.40, 3),
        }
        # second recorded series (VERDICT r3 #5): the dense config runs
        # every round alongside the LoRA headline so an MFU regression is
        # attributable to a specific series, not a workload switch
        if kind == "lora":
            gc.collect()
            for kind2, cfg2, batch2, seq2, steps2 in ladder[idx + 1:]:
                if kind2 != "full":
                    continue
                try:
                    tok2, mfu2 = _run_one(kind2, cfg2, batch2, seq2, steps2)
                    result["series_1b_dense"] = {
                        "tokens_per_sec": round(tok2, 1),
                        "params_m": round(cfg2.num_params() / 1e6),
                        "mfu": round(mfu2, 4),
                        "hw_util": round(
                            _hw_util(kind2, cfg2, mfu2, seq2), 4),
                    }
                    # data-fed twin (VERDICT r4 #6): same step, batches
                    # from the streaming executor; vs_synthetic ≈ 1.0
                    # proves the feed path keeps the chip busy
                    gc.collect()
                    try:
                        tok3, n3 = _run_dense_datafed(
                            cfg2, batch2, seq2, steps2)
                        mfu3 = (tok3 * cfg2.flops_per_token(seq2)
                                / _peak_flops())
                        result["series_1b_dense_datafed"] = {
                            "tokens_per_sec": round(tok3, 1),
                            "mfu": round(mfu3, 4),
                            "steps": n3,
                            "vs_synthetic": round(mfu3 / mfu2, 4),
                        }
                    except Exception as e:
                        result["series_1b_dense_datafed"] = {
                            "error": str(e)[:200]}
                        failed_series.append("series_1b_dense_datafed")
                except Exception as e:
                    result["series_1b_dense"] = {"error": str(e)[:200]}
                    failed_series.append("series_1b_dense")
                break
        print(json.dumps(result))
        return failed_series
    raise last_err or RuntimeError("no config ran")


def _reap_on_exit() -> None:
    """Leak gate (ISSUE 1): the benchmark must never poison the next run.
    Shut down any runtime this process still holds, then GC stale session
    dirs/daemons through the same lifecycle reaper the tests use."""
    try:
        ray_tpu = sys.modules.get("ray_tpu")
        if ray_tpu is not None and ray_tpu.is_initialized():
            ray_tpu.shutdown()
    except Exception:
        pass
    try:
        from ray_tpu._private import lifecycle

        lifecycle.gc_stale_sessions()
    except Exception:
        pass


if __name__ == "__main__":
    try:
        failed = main()
    except Exception as e:  # always emit one line
        print(json.dumps({
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": 0.0, "unit": f"tokens/s (failed: {type(e).__name__}: {e})",
            "vs_baseline": 0.0}))
        _reap_on_exit()
        sys.exit(1)
    _reap_on_exit()
    sys.exit(1 if failed else 0)
