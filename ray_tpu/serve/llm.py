"""LoRA-adapter llama generation on the continuous batching engine.

This is the serving shape the repo's ``models/`` path is meant to run at
production RPS (ROADMAP S3 and S2b; reference: Ray Serve LLM deployments —
multiplexed LoRA adapters over a shared base model, iteration-level
batching): one frozen base model per replica, per-request LoRA adapters
multiplexed by model id, greedy decode driven step-by-step by
:class:`~ray_tpu.serve._private.engine.ContinuousBatchingEngine` so
mixed-length generations share the compiled batch.

TPU notes: the per-step program is jitted per (batch bucket, padded seq)
shape pair — the engine's ``allowed_batch_sizes`` snapping plus a seq-pad
bucket keep the compile-cache menu finite. It ends in the next token of
each row (``models.llama.llama_next_token``: the head meets only each
row's last position, the argmax runs on the device), so a step brings
``bucket`` int32s to the host (and, from a model with experts, two
float32 a routed layer of its routers' load, three where the replica
holds a share of the experts) and the ``[bucket, S, vocab]``
logits are never made. Every model is told which positions are the rows'
own. Routed experts multiply those alone: the padding's pairs are sorted
past the last expert's and get no visit (``models/moe.py::expert_ffn``;
``expert_pairs_skipped`` counts them). Kimi delta attention's kernel runs
no chunk past a row's end (``ops/pallas/kda_chunk.py``;
``kda_chunks_skipped`` counts them), nor does the state-space scan's
(``ops/pallas/ssd_scan.py``; ``ssm_chunks_skipped``), and the flash
forwards compute no block past one
(``ops/pallas/flash_attention.py``): latent attention's
two-width forward (``flash_blocks_skipped``) and grouped-query
attention's equal-width one, full or under its window
(``attn_blocks_skipped``).
``LlamaGenerator._fwd`` is the step's function with no mask (every
position computed: another program than ``_step``'s for every model),
followed by the head over every position, for callers that want the
logits themselves. What a benchmark asks of a served class is
``warm_step_programs``, ``compiled_step_programs``, ``engine_stats()``
with ``positions_computed`` and ``positions_live`` (and ``experts_met``,
``attention_pairs`` and ``attention_keys`` once a program counts them:
PERF.md section 7), and the stream it serves.
Decoding still recomputes the full prefix each step (ROADMAP S3: a cached
decode changes what ``_step`` computes, and waits for the ``benchmark``
issue that PERF.md section 7 briefs).

Usage::

    from ray_tpu.serve import llm
    app = llm.build_llama_app(config="debug_1l", adapters=("a1", "a2"))
    handle = serve.run(app, name="llama")
    toks = list(handle.options(stream=True).remote(
        {"prompt": [3, 5, 7], "max_new": 8, "adapter": "a1"}))
"""

from __future__ import annotations

import collections
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Sequence

from ray_tpu._private import compile_cache, events
from ray_tpu.serve._private.engine import ContinuousBatchingEngine
from ray_tpu.serve.deployment import Application, Deployment


class _FullLogits:
    """``LlamaGenerator._fwd``: ``(params, tokens [B, S], lora)`` →
    logits ``[B, S, V]`` float32, as ``jit(llama_forward)`` gave them,
    from the step's own program and a second small one for the head."""

    def __init__(self, step_fn, head_fn):
        self._step_fn = step_fn
        self._head_fn = head_fn

    def __call__(self, params, tokens, lora):
        import numpy as np

        _, hidden, _ = self._step_fn(
            params, tokens, lora, np.zeros(tokens.shape[0], np.int32), None)
        return self._head_fn(params, hidden)

    def _cache_size(self) -> int:
        """Compiled (shape, adapter structure) variants of the step."""
        return self._step_fn._cache_size()


class LlamaGenerator:
    """Deployment callable: streaming greedy generation with multiplexed
    LoRA adapters, continuously batched."""

    # what `_step` counts (`engine_stats`): bytes of device results brought
    # to the host, positions computed (rows x padded length) and live among
    # them, and over live positions the (position, expert) pairs of the
    # fullest and of the mean expert, summed over steps and layers; of a
    # replica that holds a share of each layer's experts those two are over
    # the held experts, `expert_pairs_here` is all of theirs and
    # `expert_pairs_all` the pairs its routers made over every expert;
    # `expert_pairs_skipped` the padding's pairs, which no expert multiplied;
    # `expert_rows_moved` the sorted pairs' rows the routed layers gathered
    # (a layer's kept pairs, covered to the pass) of `expert_rows_all`;
    # `step_device_s` the seconds (`time.perf_counter()`) from the call of
    # the step's program until its results are on the host, the span
    # `llm.device`; `index_keys_seen` the (query, key) pairs the indexed
    # operators' live queries could have attended (the causal ones),
    # `index_keys_kept` those their indexers' choice kept (counted on the
    # device), `window_keys_kept` the pairs inside the window operators'
    # windows (latent attention's `window`, grouped-query attention's
    # `sliding`) and `window_keys_seen` the causal pairs of the same
    # queries, each summed over steps and those layers; `ssm_chunks_run` the
    # chunks of the state-space layers' scans' grids (layers x rows x chunks
    # of the padded length), `ssm_chunks_live` those among them that hold
    # one of a row's own positions and `ssm_chunks_skipped` the chunks of
    # those grids that the kernel, told the rows' lengths, did not run: the
    # difference of the two; `kda_chunks_run`, `kda_chunks_live` and
    # `kda_chunks_skipped` the same three over the Kimi delta attention
    # layers' grids;
    # `flash_blocks_run` the grid steps at or under the diagonal of the
    # latent operators' two-width flash forwards (layers x rows x heads x
    # the padded length's), `flash_blocks_live` those among them whose
    # query and key block both hold one of a row's own positions, and
    # `flash_blocks_skipped` the rest, which the kernel, told the rows'
    # lengths, did not compute; `attn_blocks_run`, `attn_blocks_live` and
    # `attn_blocks_skipped` the same three over the grouped-query attention
    # layers' equal-width flash forwards, full, under the window or under
    # an indexer's choice (which skips no block of its own);
    # `step_compiles` and `step_compile_s` the back-end compilations, and
    # their seconds, that began and ended inside a step's `llm.device`: a
    # step that met a shape nobody warmed
    STEP_COUNTERS = ("host_bytes", "positions_computed", "positions_live",
                     "expert_pairs_fullest", "expert_pairs_mean",
                     "expert_pairs_here", "expert_pairs_all",
                     "expert_pairs_skipped", "expert_rows_moved",
                     "expert_rows_all", "step_device_s", "index_keys_kept",
                     "index_keys_seen", "window_keys_kept",
                     "window_keys_seen", "ssm_chunks_run",
                     "ssm_chunks_live", "ssm_chunks_skipped",
                     "kda_chunks_run", "kda_chunks_live",
                     "kda_chunks_skipped", "flash_blocks_run",
                     "flash_blocks_live", "flash_blocks_skipped",
                     "attn_blocks_run", "attn_blocks_live",
                     "attn_blocks_skipped", "step_compiles",
                     "step_compile_s")

    def __init__(self, config: str = "tiny", lora_rank: int = 4,
                 max_batch_size: int = 4,
                 allowed_batch_sizes: Optional[Sequence[int]] = (1, 2, 4),
                 max_new_tokens: int = 16, seq_bucket: int = 32,
                 max_adapters: int = 4, seed: int = 0):
        # start-up's phases are spans that fill `events.startup_stats()`:
        # the process's first `import jax` (0.0 where somebody had it
        # already), then the client of the device coming up
        events.time_first_import("jax", "import_jax")
        import jax

        compile_cache.watch_compiles()
        with events.startup_span("devices") as up:
            up.extra = {"devices": len(jax.devices())}

        from ray_tpu.models.llama import (
            LATENT_OPERATORS, LlamaConfig, LoraConfig, init_llama)
        from ray_tpu.models.moe import held_experts

        self._cfg = getattr(LlamaConfig, config)() \
            if isinstance(config, str) else config
        # how many of a routed layer's experts this replica holds
        self._experts_held = (held_experts(self._cfg)[1]
                              if self._cfg.num_experts else 0)
        # the (position, expert) pairs a position makes over the routed
        # layers: 0 for a dense model
        self._pairs_a_position = self._cfg.experts_per_token * sum(
            n for kind, n in self._cfg.kind_counts().items()
            if kind.endswith("_routed"))
        def layers_of(operator):
            return sum(n for kind, n in self._cfg.kind_counts().items()
                       if kind.startswith(operator + "_"))

        # the layers whose query attends an indexer's choice of its keys,
        # those whose operator carries a state over the sequence in chunks
        # (the state-space scan's, the delta rule's), and those whose query
        # sees a window of its keys (latent or grouped-query attention)
        (self._ssm_layers, self._kda_layers) = map(layers_of,
                                                   ("mamba", "kda"))
        # an indexer on latent or on grouped-query attention
        self._indexed_layers = layers_of("indexed") + layers_of("chosen")
        self._window_layers = layers_of("window") + layers_of("sliding")
        # the latent operators, whose prefill is the two-width flash
        # forward's: (layers, their widths) each
        self._flash_layers = [
            (layers, self._cfg.latent_widths(operator))
            for operator in LATENT_OPERATORS
            if (layers := layers_of(operator))]
        # grouped-query attention, whose prefill is the equal-width flash
        # forward's: (layers, the kind's query heads, the window or None)
        # each
        self._attn_layers = [
            (layers, self._cfg.attention_heads(operator), window)
            for operator, window in (
                ("attention", None), ("sliding", self._cfg.sliding_window),
                # under a choice the forward skips no block but those past
                # a row's end: the full layers' walk
                ("chosen", None))
            if (layers := layers_of(operator))]
        # adapt only the attention q/v projections: the cheap standard
        # LoRA target set, and enough for adapters to produce distinct
        # generations; the stacks are over the attention layers alone in a
        # model that has other operators (``init_lora``)
        self._lcfg = LoraConfig(rank=lora_rank, targets=("wq", "wv"))
        # one jitted init: op-by-op it is a compile per parameter leaf.
        # The span ends when the call returns; the draw goes on beside the
        # rest of the start-up, and a thread that waits for it notes when
        # the parameters were on the device
        with events.startup_span("weights") as draw:
            self._params = jax.jit(lambda k: init_llama(self._cfg, k))(
                jax.random.PRNGKey(seed))
        threading.Thread(target=self._note_weights_ready, args=(draw,),
                         name="weights-ready", daemon=True).start()
        self.max_new_tokens = max_new_tokens
        self.seq_bucket = max(8, int(seq_bucket))
        self._max_adapters = max_adapters
        self._adapters: "collections.OrderedDict[str, Any]" = \
            collections.OrderedDict()
        self._adapter_lock = threading.Lock()

        cfg, lcfg = self._cfg, self._lcfg

        def step_fn(params, tokens, lora, last, live):
            from ray_tpu.models.llama import llama_next_token

            return llama_next_token(params, tokens, last, cfg,
                                    lora=lora, lora_cfg=lcfg, live=live)

        def head_fn(params, hidden):
            from ray_tpu.models.llama import llama_head

            return llama_head(params, hidden, cfg)

        # one jit; the trace cache keys on (shape, adapter-pytree
        # structure), so base (lora=None) and adapted calls coexist. The
        # weights are an ARGUMENT: closed over, they are lowered as
        # constants (2.67 GB at 7B width and 2 layers) and the replica
        # sits in the lowering long enough to miss its health probe
        self._step_fn = jax.jit(step_fn)
        self._head_fn = jax.jit(head_fn)
        self._fwd = _FullLogits(self._step_fn, self._head_fn)
        self._counts = dict.fromkeys(self.STEP_COUNTERS, 0)
        self.engine = ContinuousBatchingEngine(
            self._step, prefill_fn=self._prefill,
            max_batch_size=max_batch_size,
            allowed_batch_sizes=allowed_batch_sizes,
            name="llama")

    def _note_weights_ready(self, draw: "events.startup_span") -> None:
        """``weights_ready_s`` of the start-up book: from the dispatch of
        the init until the parameters are on the device."""
        import jax

        jax.block_until_ready(self._params)
        ready_s = time.perf_counter() - draw.t0
        events.startup_stats()["weights_ready_s"] = ready_s
        events.startup_record("startup.weights_ready", draw.at, ready_s)

    # ------------------------------------------------------------- adapters
    def _adapter(self, model_id: str):
        """Deterministic per-id LoRA pytree, LRU-cached (the sync-path
        analog of ``@serve.multiplexed`` — loads happen in the stepper
        thread, so the cache is lock-guarded, not loop-bound)."""
        if not model_id:
            return None
        with self._adapter_lock:
            if model_id in self._adapters:
                self._adapters.move_to_end(model_id)
                return self._adapters[model_id]
        import jax

        from ray_tpu.models.llama import init_lora

        key = jax.random.PRNGKey(zlib.crc32(model_id.encode()) & 0x7FFFFFFF)
        lora = init_lora(self._cfg, self._lcfg, key)
        # B starts at 0 in real LoRA (adapted == base); nudge it so
        # distinct adapters actually generate distinct tokens in demos
        k2 = jax.random.split(key, 1)[0]
        lora = jax.tree_util.tree_map_with_path(
            lambda path, leaf: leaf if path[-1].key == "a" else
            jax.random.normal(k2, leaf.shape, leaf.dtype) * 0.02, lora)
        with self._adapter_lock:
            self._adapters[model_id] = lora
            while len(self._adapters) > self._max_adapters:
                self._adapters.popitem(last=False)
        return lora

    # -------------------------------------------------------------- serving
    @staticmethod
    def _normalize(payload: Any) -> Dict[str, Any]:
        if isinstance(payload, dict):
            return payload
        return {"prompt": list(payload)}

    def _prefill(self, payload: Any, model_id: str) -> Dict[str, Any]:
        p = self._normalize(payload)
        prompt = [int(t) for t in p.get("prompt", [0])] or [0]
        vocab = self._cfg.vocab_size
        prompt = [t % vocab for t in prompt]
        return {
            "tokens": prompt,
            "prompt_len": len(prompt),
            "max_new": min(int(p.get("max_new", self.max_new_tokens)),
                           self.max_new_tokens),
        }

    def _padded_len(self, states: List[Optional[Dict]]) -> int:
        """The length a step pads these rows to: the longest row's, up to
        the next multiple of ``seq_bucket``."""
        max_len = max(len(s["tokens"]) for s in states if s is not None)
        pad_len = -(-max_len // self.seq_bucket) * self.seq_bucket
        return min(pad_len, self._cfg.max_seq_len)

    def _run_step(self, tokens, last, mask, lora=None):
        """The step's one jitted program on numpy ``tokens [B, S]``, ``last
        [B]`` and ``mask [B, S]`` (the rows' own tokens, which every model
        is told: for its routers' load, for what its indexers' choices
        kept, and for the rows' lengths, the marks' row sums, past which
        the delta rule's kernel and the state-space scan's run no chunk
        and the flash forwards, at two widths and at equal ones, compute
        no block) -> (ids, hidden, load)."""
        import jax.numpy as jnp

        return self._step_fn(self._params, jnp.asarray(tokens), lora, last,
                             mask)

    def _step(self, model_id: str, states: List[Optional[Dict]]) -> List:
        """One decode iteration for one adapter group: pad the live rows
        to (bucket, seq_bucket-multiple) and run the step's one jitted
        program, which re-runs every row's whole prefix and returns the
        greedy next token of each row; ``bucket`` int32s come to the
        host, and the routers' load with them where the model has
        experts, and an int32 an indexed operator of what its indexer
        kept. Nothing else runs on the device here (an op-by-op ``jnp``
        call would compile a program of its own per shape), and ``last``
        goes in as numpy, as ``_fwd`` passes it. Three spans tile it where
        the device takes over (``events.span``): ``llm.prepare`` (the
        numpy rows, the adapter's lookup), ``llm.device`` (from the call of
        the program until its results are on the host, summed in
        ``step_device_s``) and ``llm.finish`` (the counters, the tokens
        appended, the result list)."""
        import numpy as np

        from ray_tpu.models.moe import moved_chunk
        from ray_tpu.ops.pallas import flash_attention as fa

        with events.span("llm.prepare", "serve"):
            live = [(i, s) for i, s in enumerate(states) if s is not None]
            bucket = len(states)
            pad_len = self._padded_len(states)
            tokens = np.zeros((bucket, pad_len), np.int32)
            # index of each row's newest token; 0 for a padded row
            last = np.zeros(bucket, np.int32)
            # a row's own tokens, as against its padding and the padded
            # rows (which all carry token 0 and route alike): what a
            # router's load is counted over
            mask = np.zeros((bucket, pad_len), bool)
            for row, (_, s) in enumerate(live):
                ts = s["tokens"][-pad_len:]
                tokens[row, :len(ts)] = ts
                last[row] = len(ts) - 1
                mask[row, :len(ts)] = True
            lora = self._adapter(model_id)
        # dispatch, transfer in, the program, transfer out: until every
        # result the host reads is on the host
        compiled = compile_cache.compile_stats()
        with events.span("llm.device", "serve") as device:
            ids, _, load = self._run_step(tokens, last, mask, lora)
            ids = np.asarray(ids)
            if load is not None:
                load = {k: np.asarray(v) for k, v in load.items()}
        compiled_now = compile_cache.compile_stats()
        with events.span("llm.finish", "serve"):
            counts = self._counts
            if compiled_now is not compiled:  # an event replaces the book
                counts["step_compiles"] += (compiled_now["programs"]
                                            - compiled["programs"])
                counts["step_compile_s"] += (compiled_now["backend_s"]
                                             - compiled["backend_s"])
            counts["step_device_s"] += device.t1 - device.t0
            counts["host_bytes"] += ids.nbytes
            counts["positions_computed"] += bucket * pad_len
            live_positions = int(mask.sum())
            counts["positions_live"] += live_positions
            # reckoned here, from the mask the step handed the program: no
            # device result says it
            counts["expert_pairs_skipped"] += (
                (bucket * pad_len - live_positions)
                * self._pairs_a_position)
            if self._indexed_layers or self._window_layers:
                # a live query at position t of its row has t + 1 causal
                # keys, and a window leaves it min(t + 1, window) of them
                n = mask.sum(axis=1).astype(np.int64)
                w = self._cfg.sliding_window
                inside = np.where(n >= w, n * w - w * (w - 1) // 2,
                                  n * (n + 1) // 2)
                causal = int((n * (n + 1) // 2).sum())
                counts["index_keys_seen"] += causal * self._indexed_layers
                counts["window_keys_kept"] += (int(inside.sum())
                                               * self._window_layers)
                counts["window_keys_seen"] += causal * self._window_layers
            # the scans' and the delta rule's grids are every row of the
            # batch over the whole padded length; a chunk is live while its
            # first position is one of its row's own, and both kernels are
            # told the rows' lengths (`_run_step`) and run no other:
            # reckoned here, from the mask the step handed the program, as
            # the padding's pairs are
            for name, layers, chunk in (
                    ("ssm", self._ssm_layers, self._cfg.mamba_chunk),
                    ("kda", self._kda_layers, self._cfg.kda_chunk)):
                if layers:
                    run = layers * bucket * -(-pad_len // chunk)
                    own = layers * int((-(-mask.sum(axis=1) // chunk)).sum())
                    counts[name + "_chunks_run"] += run
                    counts[name + "_chunks_live"] += own
                    counts[name + "_chunks_skipped"] += run - own
            if pad_len % 128 == 0:
                # the flash forwards are told the same lengths and compute
                # no block past a row's end; a length off the kernels' 128
                # grid is the reference's, which has no blocks
                lengths = mask.sum(axis=1)
                blocks = [("flash", layers * w.heads, fa.shared_rope_blocks(
                    pad_len, lengths, head_dim=w.nope, rope_dim=w.rope,
                    value_dim=w.v, window=w.window or None))
                    for layers, w in self._flash_layers]
                blocks += [("attn", layers * heads, fa.equal_width_blocks(
                    pad_len, lengths, head_dim=self._cfg.head_dim,
                    window=window))
                    for layers, heads, window in self._attn_layers]
                for name, heads, (run, own) in blocks:
                    counts[name + "_blocks_run"] += heads * run
                    counts[name + "_blocks_live"] += heads * own
                    counts[name + "_blocks_skipped"] += heads * (run - own)
            if load is not None and "index_kept" in load:
                kept = load["index_kept"]
                counts["host_bytes"] += kept.nbytes
                counts["index_keys_kept"] += int(kept.sum())
            if load is not None and "fullest" in load:
                fullest, mean = load["fullest"], load["mean"]
                counts["host_bytes"] += fullest.nbytes + mean.nbytes
                counts["expert_pairs_fullest"] += float(fullest.sum())
                counts["expert_pairs_mean"] += float(mean.sum())
                pairs_here = float(mean.sum()) * self._experts_held
                pairs_all = pairs_here
                if "all" in load:  # a share: the router's pairs everywhere
                    everywhere = load["all"]
                    counts["host_bytes"] += everywhere.nbytes
                    pairs_all = float(everywhere.sum())
                counts["expert_pairs_here"] += pairs_here
                counts["expert_pairs_all"] += pairs_all
                # a layer's kept pairs are its held experts' over the live
                # positions: what the dispatch's passes cover of them
                rows = bucket * pad_len * self._cfg.experts_per_token
                a_pass = moved_chunk(rows)
                kept = np.rint(mean * self._experts_held)
                counts["expert_rows_moved"] += int(np.minimum(
                    np.ceil(kept / a_pass) * a_pass, rows).sum())
                counts["expert_rows_all"] += rows * len(mean)
            results: List[Optional[tuple]] = [None] * len(states)
            for row, (idx, s) in enumerate(live):
                nxt = int(ids[row])
                s["tokens"].append(nxt)
                done = len(s["tokens"]) - s["prompt_len"] >= s["max_new"]
                results[idx] = (nxt, done)
        return results

    def __call__(self, payload: Any):
        """Streaming endpoint: yields generated token ids one at a time
        (sync generator → the replica's streaming path relays each token
        as it is produced)."""
        from ray_tpu.serve.multiplex import get_multiplexed_model_id

        p = self._normalize(payload)
        model_id = get_multiplexed_model_id() or str(p.get("adapter", ""))
        yield from self.engine.submit(p, model_id)

    def engine_stats(self) -> Dict[str, Any]:
        """The engine's counters, and ``_step``'s own: ``host_bytes`` (the
        bytes of device results brought to the host: 4 a row a step, and
        8 a routed layer from a model with experts), ``positions_computed`` (rows
        x padded length) and ``positions_live`` (the rows' own tokens
        among them), ``expert_pairs_fullest`` and ``expert_pairs_mean``
        (over live positions, the (position, expert) pairs of the fullest
        and of the mean expert, summed over steps and over the layers that
        have routed experts; 0 for a model without experts),
        ``expert_pairs_here`` and ``expert_pairs_all`` (the pairs on the
        experts this replica holds and the pairs its routers made over
        every expert: the same unless ``experts_held`` is a share),
        ``expert_pairs_skipped`` (the pairs of a step's padding, which the
        step's mask keeps off the routed experts: (``positions_computed`` -
        ``positions_live``) x ``experts_per_token`` x routed layers, counted
        on the host; 0 for a model without experts); ``expert_rows_moved``
        and ``expert_rows_all`` (of the ``positions_computed`` x
        ``experts_per_token`` sorted rows of a routed layer, the ones its
        dispatch gathered: the layer's kept pairs covered to the pass,
        ``models/moe.py::moved_chunk``, reckoned on the host from the
        routers' load; how far the kept pairs' rows alone move);
        ``step_device_s``
        (seconds of ``time.perf_counter()`` from the call of a step's
        program until its results are on the host: dispatch, transfer in,
        the program, transfer out; beside the engine's ``active_s`` it says
        how long an iteration the host works while the device has nothing
        to do); ``index_keys_seen`` and ``index_keys_kept`` (over the live
        queries of the layers whose operator has an indexer, ``indexed``
        on latent or ``chosen`` on grouped-query attention: the (query,
        key) pairs they could have attended, each query's causal keys,
        reckoned on the host from the rows' lengths, and the pairs their
        indexers' choice kept, counted on the device from the choice
        itself and brought to the host with the step's tokens; both summed
        over steps and indexed layers, 0 for a model without an indexer)
        and ``window_keys_kept`` (over the live queries of the ``window``
        and the ``sliding`` layers, the pairs inside the window: ``min(t +
        1, sliding_window)`` for the query at position ``t`` of its row,
        reckoned on the host; summed over steps and those layers) beside
        ``window_keys_seen`` (the causal pairs of the same queries, ``t +
        1`` each: what the window leaves of them is the ratio);
        ``ssm_chunks_run`` and ``ssm_chunks_live`` (over the layers whose
        operator is ``mamba``: the chunks of ``mamba_chunk`` positions of
        their scans' grid, rows of the batch x chunks of the padded length,
        and those among them that hold at least one of a row's own
        positions, a row of ``n`` tokens having ``ceil(n / mamba_chunk)``;
        both reckoned on the host from the rows' lengths and summed over
        steps and those layers, 0 for a model without them) and
        ``ssm_chunks_skipped`` (the chunks of that grid past a row's end,
        which the kernel is told and does not run: layers x the sum over
        rows of the padded length's chunks less ``ceil(n / mamba_chunk)``,
        the difference of the two, reckoned on the host from the mask the
        program was handed); ``kda_chunks_run``, ``kda_chunks_live`` and
        ``kda_chunks_skipped`` (the same three over the layers whose
        operator is ``kda`` and their chunks of ``kda_chunk`` positions,
        told and reckoned the same way); ``flash_blocks_run``,
        ``flash_blocks_live`` and ``flash_blocks_skipped`` (over the layers
        whose operator is latent attention, plain, windowed or indexed, at a padded length on
        the two-width flash forward's 128 grid: the (query block, key
        block) steps of its grid at or under the diagonal, rows x heads x
        the padded length's by ``flash_tiles``, under a window the
        window's; those among them whose two blocks both hold a position
        of their row's own; and the rest, which the kernel is told and
        does not compute: ``ops/pallas/flash_attention.py::
        shared_rope_blocks``, reckoned on the host from the mask the
        program was handed, summed over steps and those layers);
        ``attn_blocks_run``, ``attn_blocks_live`` and
        ``attn_blocks_skipped`` (the same three over the layers whose
        operator is grouped-query attention, ``attention``, ``sliding``
        or ``chosen`` (whose forward walks as a full layer's: a choice
        skips no block), and their equal-width flash forward's grid, rows
        x the kind's own query heads (``LlamaConfig.attention_heads``) x
        the padded length's steps at or under the diagonal, under
        ``sliding_window`` the window's walk, or its query blocks where the
        window runs one step a block: ``equal_width_blocks`` of the same
        module, by the kernel's own rule for ``head_dim`` and the kind's
        window); and
        ``layer_kinds``, how many layers of each kind this replica serves
        (``LlamaConfig.kind_counts``: ``attention_dense`` alone for a dense
        decoder). Two books of the process ride along, each a reference to
        a dict that exists already: ``startup`` (``events.startup_stats()``:
        seconds by ``startup.*`` phase, ``weights_ready_s``, ``warm_s`` by
        length) and ``compiles`` (``compile_cache.compile_stats()``: every
        compilation by phase and by what the persistent cache did with
        it); ``step_compiles`` and ``step_compile_s`` are the compilations
        of that book that fell inside a step's ``llm.device``, which no
        ``warm_step_programs`` covered."""
        return {**self.engine.stats(), **self._counts,
                "layer_kinds": self._cfg.kind_counts(),
                "startup": events.startup_stats(),
                "compiles": compile_cache.compile_stats()}

    # ------------------------------------------- what a benchmark asks for
    def warm_step_programs(self, seq_len: int) -> None:
        """Compile (or find in the cache) and run every device program a
        step runs at one padded length and the engine's batch: the one
        program of ``_step``, its results brought to the host."""
        import jax
        import numpy as np

        rows = self.engine.max_batch_size
        with events.startup_span("warm", {"seq_len": seq_len}) as warm:
            ids, _, load = self._run_step(
                np.zeros((rows, seq_len), np.int32),
                np.zeros(rows, np.int32), np.zeros((rows, seq_len), bool))
            jax.tree.map(np.asarray, (ids, load))
        by_length = events.startup_stats().setdefault("warm_s", {})
        by_length[seq_len] = by_length.get(seq_len, 0.0) + warm.t1 - warm.t0

    def logits_after_prompt(self, prompt: List[int]):
        """``[vocab]`` float32 after the prompt's last token, from the
        weights this replica serves, through the step's program at the
        engine's batch; the head meets that one position."""
        import numpy as np

        rows, n = self.engine.max_batch_size, len(prompt)
        tokens = np.zeros((rows, n), np.int32)
        tokens[0] = prompt
        mask = np.zeros((rows, n), bool)
        mask[0] = True
        _, hidden, _ = self._run_step(tokens, np.zeros(rows, np.int32), mask)
        return np.asarray(self._head_fn(self._params, hidden[0, n - 1]))

    def compiled_step_programs(self) -> int:
        """Compiled (shape, adapter structure) variants of the step."""
        return self._step_fn._cache_size()

    def device_info(self) -> Dict[str, Any]:
        """Where this replica's model lives, as jax reports it."""
        import os

        import jax

        devices = jax.devices()
        return {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "attn_impl": self._cfg.attn_impl,
            "num_layers": self._cfg.num_layers,
            "hidden": self._cfg.hidden,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "forward_compiles": self._fwd._cache_size(),
            "compiles": compile_cache.compile_stats(),
            "pid": os.getpid(),
        }


def build_llama_app(*, config: str = "tiny", lora_rank: int = 4,
                    max_batch_size: int = 4,
                    allowed_batch_sizes: Optional[Sequence[int]] = (1, 2, 4),
                    max_new_tokens: int = 16, seq_bucket: int = 32,
                    num_replicas: int = 1,
                    max_ongoing_requests: int = 16,
                    max_queued_requests: int = 32,
                    autoscaling_config: Optional[Dict] = None,
                    ray_actor_options: Optional[Dict] = None) -> Application:
    """Bind a continuously-batched LoRA llama generator deployment.

    ``max_ongoing_requests`` must exceed the engine batch width: each
    in-flight generation holds a replica admission slot while the engine
    multiplexes them onto the compiled batch.
    """
    dep = Deployment(
        LlamaGenerator, "LlamaGenerator",
        num_replicas=num_replicas,
        max_ongoing_requests=max(max_ongoing_requests, 2 * max_batch_size),
        max_queued_requests=max_queued_requests,
        autoscaling_config=autoscaling_config,
        ray_actor_options=ray_actor_options or {},
    )
    return dep.bind(config=config, lora_rank=lora_rank,
                    max_batch_size=max_batch_size,
                    allowed_batch_sizes=allowed_batch_sizes,
                    max_new_tokens=max_new_tokens, seq_bucket=seq_bucket)
