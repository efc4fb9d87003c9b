"""The indexer's three kernels ON THE CHIP at ``serve_keye_clipqa``'s
longest step: 4 rows of 8192 positions, 16 index heads of 64 against one
key a position, a choice of 2048 keys a query, 32 query heads on 4
key/value heads of 128 (groups of 8), bf16 in and out, the queries and keys
normed a head (so that the scores have a model's spread).

    chiprun -- python3 benchmark/tools/keye_select_check.py \
        --out chiprun_out/pr62/keye_select_check.jsonl

The cell's own check (``harness/tokengap.py`` over served tokens) cannot
tell every fault of the choice at random weights (the workload file's
``check.why`` says which). This tool holds them where they can be told, at
the kernels' outputs:

- ``scores``: ``ops/pallas/index_scores.py::index_scores_causal`` at 16
  heads of 64 against the einsums (float32, highest matmul precision, a
  block of queries at a time) over the same bf16 numbers, over the causal
  pairs: the mean absolute difference as a share of the mean absolute
  score, UNDER ``TOLERANCE``.
- ``choice_wrong``: the (query, key) pairs on which
  ``models/llama.py::_chosen_keys`` over those scores differs from
  ``lax.top_k``'s choice, over the queries whose ``topk``-th score ties
  with no other (``choice_ties`` counts the others): 0.
- ``sound``, ``sound_told``: ``ops/pallas/flash_attention.py::
  flash_attention_selected`` handed that choice against
  ``reference/keye.py::masked_attention`` (float32, highest precision, the
  causal mask and the choice as booleans over all the keys), told the
  rows' lengths (``LENGTHS``: one whole row, one that ends inside a block,
  one shorter than ``topk``, one empty) and not; the mean absolute
  difference of an output as a share of the mean absolute output, UNDER
  ``TOLERANCE``.
- ``one_key_fewer``, ``one_key_more``: the same kernel handed the choice
  of ``topk - 1`` and ``topk + 1`` keys against the reference at ``topk``:
  each OVER ``OFF_OVER``. ``choice_ignored``: the causal kernel
  (``flash_attention``), far over it. ``groups_of_4``: the reference with
  query head ``n`` reading key/value head ``n // 4 % 4``, far over it too.

The limits and the readings between which they lie are in PERF.md section
6, PR 62. One JSON line a seed (``--seeds`` of them, each drawn anew); exit
0 where all hold at every seed, 1 where one does not. ``--rehearsal`` walks
it on the CPU at the rehearsal's sizes with the kernels interpreted: never
a measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "serve_keye_clipqa"
TOLERANCE = 0.004
OFF_OVER = 0.006
# the told calls' rows, as shares of the length: whole, ending inside a
# block, shorter than topk, empty
LENGTHS = (1.0, 0.55, 0.15, 0.0)
QUERY_BLOCK = 256


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
    from benchmark.reference import keye as reference
    from ray_tpu.models.llama import _chosen_keys
    from ray_tpu.ops.attention import index_scores
    from ray_tpu.ops.pallas.flash_attention import (
        flash_attention, flash_attention_selected)

    platform = jax.devices()[0].platform
    if not args.rehearsal and platform != "tpu":
        raise SystemExit(f"no chip: {jax.devices()}")
    cell = loader.load_cell(CELL, rehearsal=args.rehearsal)
    m, sa = cell["model"], cell["model"]["sa_config"]
    rows, length = cell["engine"]["max_batch_size"], seq_buckets(cell)[-1]
    heads, kv_heads = m["num_attention_heads"], m["num_key_value_heads"]
    d, topk = m["head_dim"], sa["topk"]
    ih, ihd = sa["indexer_num_heads"], sa["indexer_head_dim"]
    dtype = jnp.dtype(m["program"]["dtype"])
    f32 = jnp.float32
    block = min(QUERY_BLOCK, length)

    def unit(x):  # an RMSNorm over each head at weight 1, as the model's
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True))

    lengths = jnp.asarray([int(share * length) for share in LENGTHS][:rows]
                          + [length] * max(0, rows - len(LENGTHS)), jnp.int32)
    own = (jnp.arange(length)[None, :] < lengths[:, None])[:, :, None, None]
    at = jnp.arange(length)
    causal = at[:, None] >= at[None, :]

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

    @jax.jit
    def einsum_scores(q_i, k_i, w):
        """One block of queries of one row: q_i [J, Q, D], k_i [S, D], w
        [Q, J] -> [Q, S] float32."""
        with jax.default_matmul_precision("highest"):
            products = jnp.einsum("jqd,sd->jqs", q_i.astype(f32),
                                  k_i.astype(f32))
            return jnp.einsum("jqs,qj->qs", jax.nn.relu(products), w)

    @jax.jit
    def top_k_choice(scores, start):
        """lax.top_k's choice of a block of queries ``start`` on, and
        whether a query's ``topk``-th score ties with its next."""
        q_pos = start + jnp.arange(scores.shape[0])
        seen = q_pos[:, None] >= at[None, :]
        masked = jnp.where(seen, scores, -jnp.inf)
        values, idx = jax.lax.top_k(masked, min(topk + 1, length))
        picked = jnp.zeros(scores.shape, bool).at[
            jnp.arange(scores.shape[0])[:, None], idx[:, :topk]].set(True)
        tied = (values[:, topk - 1] == values[:, -1]) & (
            values[:, -1] > -jnp.inf) if length > topk else jnp.zeros(
                scores.shape[0], bool)
        return picked & seen, tied

    choose = jax.jit(lambda s, k: _chosen_keys(s, causal, k),
                     static_argnums=1)
    selected = jax.jit(lambda q, k, v, keep: flash_attention_selected(
        q, k, v, keep.astype(jnp.int8)))
    selected_told = jax.jit(lambda q, k, v, keep: flash_attention_selected(
        q, k, v, keep.astype(jnp.int8), lengths))
    lines = []
    for n in range(args.seeds):
        seed = args.first_seed + 100003 * n
        kq, kk, kv, kiq, kik, kiw = jax.random.split(
            jax.random.key(seed % 2 ** 31), 6)
        q = unit(jax.random.normal(kq, (rows, length, heads, d), f32)
                 ).astype(dtype)
        k = unit(jax.random.normal(kk, (rows, length, kv_heads, d), f32)
                 ).astype(dtype)
        v = jax.random.normal(kv, (rows, length, kv_heads, d), f32
                              ).astype(dtype)
        q_i = jax.random.normal(kiq, (rows, ih, length, ihd), f32
                                ).astype(dtype)
        k_i = unit(jax.random.normal(kik, (rows, length, ihd), f32)
                   ).astype(dtype)
        w = jax.random.normal(kiw, (rows, length, ih), f32) * ih ** -0.5

        # the score kernel against the einsums, a block of queries a time
        scores = index_scores(q_i, k_i, w, impl="flash")
        wrong = total = choice_wrong = choice_ties = 0.0
        keep = choose(scores, topk)
        for b in range(rows):
            for start in range(0, length, block):
                want = einsum_scores(q_i[b, :, start:start + block], k_i[b],
                                     w[b, start:start + block])
                seen = causal[start:start + block]
                got = scores[b, start:start + block]
                wrong += float(jnp.sum(jnp.abs(jnp.where(
                    seen, got - want, 0.0))))
                total += float(jnp.sum(jnp.abs(jnp.where(seen, want, 0.0))))
                picked, tied = top_k_choice(got, start)
                differs = (picked != keep[b, start:start + block]
                           ) & ~tied[:, None]
                choice_wrong += float(jnp.sum(differs))
                choice_ties += float(jnp.sum(tied))

        def masked(allowed_of, **how):
            with jax.default_matmul_precision("highest"):
                return jnp.stack([reference.masked_attention(
                    q[b].astype(f32), k[b].astype(f32), v[b].astype(f32),
                    allowed_of(b), **how) for b in range(rows)])

        def rows_of(keep):
            return lambda b: lambda start, n: keep[b, start:start + n]

        want = masked(rows_of(keep))
        read = {
            "scores": wrong / total, "choice_wrong": choice_wrong,
            "choice_ties": choice_ties,
            "kept_share": float(jnp.sum(keep) / (rows * jnp.sum(causal))),
            "sound": off(selected(q, k, v, keep), want),
            "sound_told": off(selected_told(q, k, v, keep), want, True),
            "one_key_fewer": off(selected(q, k, v, choose(scores, topk - 1)),
                                 want),
            "one_key_more": off(selected(q, k, v, choose(scores, topk + 1)),
                                want),
            "choice_ignored": off(jax.jit(lambda q, k, v: flash_attention(
                q, k, v, True))(q, k, v), want),
            "groups_of_4": off(masked(rows_of(keep),
                                      group=heads // kv_heads // 2), want)}
        line = {
            "tool": "keye_select_check", "platform": platform,
            "device": jax.devices()[0].device_kind, "seed": seed,
            "rows": rows, "length": length, "heads": heads,
            "kv_heads": kv_heads, "head_dim": d, "index_heads": ih,
            "index_head_dim": ihd, "topk": topk,
            "lengths": lengths.tolist(), "dtype": str(dtype),
            "tolerance": TOLERANCE, "off_over": OFF_OVER, **read,
            "sound_ok": (all(read[name] < TOLERANCE for name in (
                "scores", "sound", "sound_told"))
                and read["choice_wrong"] == 0),
            "faults_told": all(read[name] > OFF_OVER for name in (
                "one_key_fewer", "one_key_more", "choice_ignored",
                "groups_of_4")),
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
