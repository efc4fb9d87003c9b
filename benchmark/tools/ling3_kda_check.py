"""Kimi delta attention's kernel against the recurrence ON THE CHIP, at
``serve_ling3_repoctx``'s longest step: 8 rows of 3072 positions, 32 heads
of 128 keys against 128 values, chunks of 128, bf16 in and out, so that the
state, carried in VMEM from one grid step to the next, crosses 23 chunk
edges a row.

    chiprun -- python3 benchmark/tools/ling3_kda_check.py \
        --out chiprun_out/ling3_kda_check.json

The cell's own check (``harness/tokengap.py`` over served tokens) reads
the whole model; this tool holds the kernel's own mechanism where it can be
told apart from everything else, at the kernel's outputs, as
``tools/granite_scan_check.py`` does for the state-space scan:

- ``sound``: ``ops/pallas/kda_chunk.py::kda_chunked`` over the whole length
  against ``ops/kda.py::reference_kda`` (the recurrence position by
  position in float32) over the same bf16 numbers; the largest difference
  of an output as a share of the largest output, and the final state's
  likewise, must lie UNDER ``TOLERANCE`` (the kernel rounds its outputs and
  its matmuls' operands to bf16, 8 bits).
- ``state_dropped``: the same kernel run a chunk at a time, each from
  zeros, as a kernel that lost its state between grid steps would compute.
  It must agree up to the first edge and lie OVER ``DROPPED_OVER`` after
  it.

Both limits lie between the two readings (my chip runs, PR 52: the module's
``READINGS``). The operands are drawn as a layer of the model makes them
(``models/llama.py::_kda`` under ``init_llama``'s gate): ``q`` and ``k`` of
unit length a head, ``q`` times ``128 ** -0.5``, ``v`` of unit scale,
``beta`` a sigmoid, a channel's memory log-uniform in 10 to 1000 positions
and moved by the data, so its decay a position lies in about 0.9 to 0.999.
``--times`` also times the kernel alone at each of ``--chunks`` (the median
of 5 calls), which is how the chunk was chosen. One JSON line a seed
(``--seeds`` of them, each drawn anew); exit 0 where both hold at every
seed, 1 where either does not. ``--rehearsal`` walks it on the CPU at the
rehearsal's sizes with the kernel interpreted: never a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve_ling3_repoctx"
TOLERANCE = 0.03
DROPPED_OVER = 0.15
# what set the two limits (my chip runs, PR 52, one v5e chip, 4 seeds at
# 8 x 3072): the largest sound reading and the smallest dropped one
READINGS = {"sound_outputs_off_max": 0.0051, "sound_state_off_max": 0.0032,
            "dropped_after_it_off_min": 0.82}


def drawn(key, rows, length, heads, head_dim, dtype):
    """A layer's operands at the rule: ``q``, ``k``, ``v``, ``g``,
    ``beta``."""
    import math

    import jax
    import jax.numpy as jnp

    k = jax.random.split(key, 6)
    shape = (rows, length, heads, head_dim)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = (unit(jax.random.normal(k[0], shape)) * head_dim ** -0.5
         ).astype(dtype)
    kk = unit(jax.random.normal(k[1], shape)).astype(dtype)
    v = jax.random.normal(k[2], shape).astype(dtype)
    memory = jnp.exp(jax.random.uniform(
        k[3], (heads, head_dim), jnp.float32, math.log(10.0),
        math.log(1000.0)))
    at_rest = -jnp.log(5.0 * memory - 1.0)
    g = -5.0 * jax.nn.sigmoid(at_rest + 0.3 * jax.random.normal(k[4], shape))
    beta = jax.nn.sigmoid(jax.random.normal(k[5], shape[:3]))
    return q, kk, v, g, beta


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3100000007)
    ap.add_argument("--out", default=None, help="the lines again, in a file")
    ap.add_argument("--times", action="store_true")
    ap.add_argument("--chunks", default="", help="chunks to time beside the "
                    "cell's, with commas")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO_ROOT)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import time

    import jax
    import jax.numpy as jnp

    from benchmark.drivers.serve import seq_buckets
    from benchmark.harness import loader
    from ray_tpu.ops.kda import reference_kda
    from ray_tpu.ops.pallas.kda_chunk import (
        KDA_CHUNK_TRACE_NAME, kda_chunked)

    platform = jax.devices()[0].platform
    if not args.rehearsal and platform != "tpu":
        raise SystemExit(f"no chip: {jax.devices()}")
    cell = loader.load_cell(CELL, rehearsal=args.rehearsal)
    m = cell["model"]
    rows, length = cell["engine"]["max_batch_size"], seq_buckets(cell)[-1]
    heads, head_dim = m["num_attention_heads"], m["head_dim"]
    chunk = m["kda_chunk_size"]
    dtype = jnp.dtype(m["program"]["dtype"])
    f32, tol = jnp.float32, TOLERANCE
    zeros = jnp.zeros((rows, heads, head_dim, head_dim), f32)
    recurrence = jax.jit(reference_kda)

    def kernel_at(c):
        return jax.jit(lambda q, k, v, g, beta: kda_chunked(
            q, k, v, g, beta,
            jnp.zeros((q.shape[0], heads, head_dim, head_dim), f32), c))

    kernel = kernel_at(chunk)

    def off(got, want):
        return float(jnp.abs(got.astype(f32) - want).max())

    def rms_off(got, want):
        return float(jnp.sqrt(jnp.mean((got.astype(f32) - want) ** 2)
                              / jnp.mean(want ** 2)))

    lines = []
    for i in range(args.seeds):
        seed = args.first_seed + 100003 * i
        q, k, v, g, beta = drawn(jax.random.key(seed % 2 ** 31), rows,
                                 length, heads, head_dim, dtype)
        want_o, want_s = recurrence(q.astype(f32), k.astype(f32),
                                    v.astype(f32), g, beta, zeros)
        o, s = kernel(q, k, v, g, beta)
        # a chunk at a time, each from zeros: the state dropped at every
        # edge (all the chunks as rows of one call)
        def as_chunks(a):
            return a.reshape((rows * (length // chunk), chunk) + a.shape[2:])

        cut = kernel(*(as_chunks(a) for a in (q, k, v, g, beta))
                     )[0].reshape(o.shape)
        size = float(jnp.abs(want_o).max())
        size_s = float(jnp.abs(want_s).max())
        line = {
            "tool": "ling3_kda_check", "platform": platform,
            "device": jax.devices()[0].device_kind, "seed": seed,
            "kernel": KDA_CHUNK_TRACE_NAME, "rows": rows, "length": length,
            "heads": heads, "head_dim": head_dim, "chunk": chunk,
            "edges_a_row": length // chunk - 1, "dtype": str(dtype),
            "tolerance": tol, "dropped_over": DROPPED_OVER,
            "largest_output": size, "largest_state": size_s,
            "sound": {"outputs_off": off(o, want_o) / size,
                      "outputs_rms_off": rms_off(o, want_o),
                      "final_state_off": off(s, want_s) / size_s},
            "state_dropped": {
                "up_to_the_first_edge_off": off(
                    cut[:, :chunk], want_o[:, :chunk]) / size,
                "after_it_off": off(cut[:, chunk:],
                                    want_o[:, chunk:]) / size,
                "after_it_rms_off": rms_off(cut[:, chunk:],
                                            want_o[:, chunk:])},
        }
        sound, dropped = line["sound"], line["state_dropped"]
        line["ok"] = bool(
            length > chunk
            and sound["outputs_off"] < tol and sound["final_state_off"] < tol
            and dropped["up_to_the_first_edge_off"] < tol
            and dropped["after_it_off"] > DROPPED_OVER)
        if args.times and i == 0:
            line["kernel_ms"] = {}
            for c in [chunk] + [int(x) for x in args.chunks.split(",") if x]:
                fn = kernel_at(c)
                jax.block_until_ready(fn(q, k, v, g, beta))
                took = []
                for _ in range(5):
                    t = time.perf_counter()
                    jax.block_until_ready(fn(q, k, v, g, beta))
                    took.append(1e3 * (time.perf_counter() - t))
                line["kernel_ms"][str(c)] = sorted(took)[2]
        if args.rehearsal:
            line["rehearsal"] = True
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.writelines(json.dumps(line) + "\n" for line in lines)
    return 0 if lines and all(line["ok"] for line in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
