"""The equal-width flash forward under a window against the masked softmax
ON THE CHIP, at ``serve_mellum2_projctx``'s longest step: 4 rows of 8192
positions, 32 query heads on 4 key/value heads of 128, a window of 1024,
bf16 in and out, the queries and keys normed a head as the model's are (so
that the scores have the model's spread), tiles of 1024 x 1024, so that
every query block's window crosses a block's edge.

    chiprun -- python3 benchmark/tools/mellum2_window_check.py \
        --out chiprun_out/mellum2_window_check.json

The cell's own check (``harness/tokengap.py`` over served tokens) does NOT
tell a window that is one key off: the reference with ``q - k <
sliding_window - 1`` puts the same tokens first as the sound one (a
``gap_mean`` of 0.0 to 2.6e-7 over 90 tokens, under every sound run's: the
workload file's ``check.why``), because one key of 1024 moves attention by
a thousandth, under bf16's own error in the logits. This tool holds that
convention where it can be told, at the kernel's outputs:

- ``sound``: ``ops/pallas/flash_attention.py::flash_attention_window`` at
  the window against ``reference/mellum.py::masked_attention`` (float32,
  highest matmul precision, the window and the causal mask as booleans over
  all the keys) over the same bf16 numbers; the mean absolute difference of
  an output as a share of the mean absolute output must lie UNDER
  ``TOLERANCE``.
- ``one_key_short`` and ``one_key_long``: the same kernel told ``window -
  1`` and ``window + 1``, against the same reference at ``window``: each
  must lie OVER ``OFF_OVER``. ``window_ignored``: the causal kernel
  (``flash_attention``), far over it.

The limits and the readings between which they lie are in PERF.md section
6, PR 55. One JSON line a seed (``--seeds`` of them, each drawn anew); exit
0 where all hold at every seed, 1 where one does not. ``--rehearsal`` walks
it on the CPU at the rehearsal's sizes with the kernel interpreted: never a
measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve_mellum2_projctx"
TOLERANCE = 0.004
OFF_OVER = 0.008


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3100000007)
    ap.add_argument("--out", default=None, help="the lines again, in a file")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO_ROOT)
    if args.rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp

    from benchmark.drivers.serve import seq_buckets
    from benchmark.harness import loader
    from benchmark.reference import mellum as reference
    from ray_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_window)

    platform = jax.devices()[0].platform
    if not args.rehearsal and platform != "tpu":
        raise SystemExit(f"no chip: {jax.devices()}")
    cell = loader.load_cell(CELL, rehearsal=args.rehearsal)
    m = cell["model"]
    rows, length = cell["engine"]["max_batch_size"], seq_buckets(cell)[-1]
    heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    d, window = m["head_dim"], m["sliding_window"]
    dtype = jnp.dtype(m["program"]["dtype"])
    f32 = jnp.float32

    def unit(x):  # an RMSNorm over each head at weight 1, as the model's
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))

    def off(got, want):
        """Mean absolute difference over the mean absolute output."""
        return float(jnp.mean(jnp.abs(got.astype(f32) - want))
                     / jnp.mean(jnp.abs(want)))

    kernels = {
        "sound": jax.jit(lambda q, k, v: flash_attention_window(
            q, k, v, window)),
        "one_key_short": jax.jit(lambda q, k, v: flash_attention_window(
            q, k, v, window - 1)),
        "one_key_long": jax.jit(lambda q, k, v: flash_attention_window(
            q, k, v, window + 1)),
        "window_ignored": jax.jit(lambda q, k, v: flash_attention(
            q, k, v, True)),
    }
    lines = []
    for n in range(args.seeds):
        seed = args.first_seed + 100003 * n
        kq, kk, kv = jax.random.split(jax.random.key(seed % 2 ** 31), 3)
        q = unit(jax.random.normal(kq, (rows, length, heads, d), f32)
                 ).astype(dtype)
        k = unit(jax.random.normal(kk, (rows, length, kv_heads, d), f32)
                 ).astype(dtype)
        v = jax.random.normal(kv, (rows, length, kv_heads, d), f32
                              ).astype(dtype)
        with jax.default_matmul_precision("highest"):
            want = jnp.stack([reference.masked_attention(
                q[b].astype(f32), k[b].astype(f32), v[b].astype(f32),
                window=window) for b in range(rows)])
        read = {name: off(fn(q, k, v), want) for name, fn in kernels.items()}
        line = {
            "tool": "mellum2_window_check", "platform": platform,
            "device": jax.devices()[0].device_kind, "seed": seed,
            "rows": rows, "length": length, "heads": heads,
            "kv_heads": kv_heads, "head_dim": d, "window": window,
            "dtype": str(dtype), "tolerance": TOLERANCE,
            "off_over": OFF_OVER, **read,
            "sound_ok": read["sound"] < TOLERANCE,
            "faults_told": all(read[name] > OFF_OVER for name in (
                "one_key_short", "one_key_long", "window_ignored")),
            "rehearsal": bool(args.rehearsal)}
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)) or ".",
                    exist_ok=True)
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0 if all(ln["sound_ok"] and ln["faults_told"]
                    for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
