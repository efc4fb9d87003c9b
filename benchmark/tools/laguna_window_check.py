"""The equal-width flash forward under a window NARROWER than its tile
against the masked softmax ON THE CHIP, at ``serve_laguna_agentturns``'s
longest step: 4 rows of 6144 positions, 64 query heads on 8 key/value heads
of 128 (groups of 8), a window of 512, bf16 in and out, the queries and keys
normed a head (so that the scores have a model's spread), tiles of 1024 x
1024, so that a query block's window reaches two key blocks of which at
most 512 keys a query are inside; and the causal forward at the full
layers' 48 heads on 8 (groups of 6), each told the rows' lengths and not.

    chiprun -- python3 benchmark/tools/laguna_window_check.py \
        --out chiprun_out/pr60/laguna_window_check.jsonl

The cell's own check (``harness/tokengap.py`` over served tokens) does not
tell a window that is one key off (the workload file's ``check.why``): one
key of 512 moves attention by a five-hundredth, under bf16's own error in
the logits. This tool holds that convention where it can be told, at the
kernel's outputs:

- ``sound``: ``ops/pallas/flash_attention.py::flash_attention_window`` at
  the window against ``reference/laguna.py::masked_attention`` (float32,
  highest matmul precision, the window and the causal mask as booleans over
  all the keys) over the same bf16 numbers; the mean absolute difference of
  an output as a share of the mean absolute output must lie UNDER
  ``TOLERANCE``. ``sound_told``: the same call told the rows' lengths
  (``LENGTHS``: one whole row, one that ends inside a block, one under the
  window, one empty), read over the rows' own positions. ``full`` and ``full_told``: the causal forward
  (``flash_attention``) at the full layers' head count against the same
  reference with no window.
- ``one_key_short`` and ``one_key_long``: the same kernel told ``window -
  1`` and ``window + 1``, against the same reference at ``window``: each
  must lie OVER ``OFF_OVER``. ``window_ignored``: the causal kernel
  (``flash_attention``), far over it. ``group_of_8_for_6``: the full
  layers' queries against key/value heads read in groups of 8
  (``reference``'s ``group=8``), far over it too.

The limits and the readings between which they lie are in PERF.md section
6, PR 60. One JSON line a seed (``--seeds`` of them, each drawn anew); exit
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
CELL = "serve_laguna_agentturns"
TOLERANCE = 0.004
OFF_OVER = 0.012
# the told calls' rows, as shares of the length: whole, ending inside a
# block, under the window, empty
LENGTHS = (1.0, 0.55, 0.05, 0.0)


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
    from benchmark.reference import laguna as reference
    from ray_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_window)

    platform = jax.devices()[0].platform
    if not args.rehearsal and platform != "tpu":
        raise SystemExit(f"no chip: {jax.devices()}")
    cell = loader.load_cell(CELL, rehearsal=args.rehearsal)
    m = cell["model"]
    rows, length = cell["engine"]["max_batch_size"], seq_buckets(cell)[-1]
    heads = max(m["num_attention_heads_per_layer"])  # the sliding layers'
    full_heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    d, window = m["head_dim"], m["sliding_window"]
    dtype = jnp.dtype(m["program"]["dtype"])
    f32 = jnp.float32

    def unit(x):  # an RMSNorm over each head at weight 1, as the model's
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))

    lengths = jnp.asarray([int(share * length) for share in LENGTHS][:rows]
                          + [length] * max(0, rows - len(LENGTHS)), jnp.int32)
    own = (jnp.arange(length)[None, :] < lengths[:, None])[:, :, None, None]

    def off(got, want, told=False):
        """Mean absolute difference over the mean absolute output; of a
        told call over the rows' own positions (what lies past a row's end
        is nobody's to read)."""
        got = got.astype(f32)
        if not told:
            return float(jnp.mean(jnp.abs(got - want))
                         / jnp.mean(jnp.abs(want)))
        return float(jnp.sum(jnp.abs(jnp.where(own, got - want, 0.0)))
                     / jnp.sum(jnp.abs(jnp.where(own, want, 0.0))))

    kernels = {
        "sound": jax.jit(lambda q, k, v: flash_attention_window(
            q, k, v, window)),
        "sound_told": jax.jit(lambda q, k, v: flash_attention_window(
            q, k, v, window, lengths)),
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
        def masked(q, **how):
            with jax.default_matmul_precision("highest"):
                return jnp.stack([reference.masked_attention(
                    q[b].astype(f32), k[b].astype(f32), v[b].astype(f32),
                    **how) for b in range(rows)])

        want = masked(q, window=window)
        read = {name: off(fn(q, k, v), want, name.endswith("_told"))
                for name, fn in kernels.items()}
        # the full layers' forward: their head count, groups of 6
        qf = q[:, :, :full_heads]
        want = masked(qf, window=None)
        read["full"] = off(jax.jit(lambda q, k, v: flash_attention(
            q, k, v, True))(qf, k, v), want)
        read["full_told"] = off(jax.jit(lambda q, k, v: flash_attention(
            q, k, v, True, lengths))(qf, k, v), want, True)
        read["group_of_8_for_6"] = off(
            masked(qf, window=None, group=heads // kv_heads), want)
        line = {
            "tool": "laguna_window_check", "platform": platform,
            "device": jax.devices()[0].device_kind, "seed": seed,
            "rows": rows, "length": length, "heads": heads,
            "full_heads": full_heads, "lengths": lengths.tolist(),
            "kv_heads": kv_heads, "head_dim": d, "window": window,
            "dtype": str(dtype), "tolerance": TOLERANCE,
            "off_over": OFF_OVER, **read,
            "sound_ok": all(read[name] < TOLERANCE for name in (
                "sound", "sound_told", "full", "full_told")),
            "faults_told": all(read[name] > OFF_OVER for name in (
                "one_key_short", "one_key_long", "window_ignored",
                "group_of_8_for_6")),
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
