"""The rows' lengths reach the delta rule from a serving step (PR 53):
``llama_next_token`` of a model with Kimi delta attention takes them off
the mask it is handed anyway, ``_hidden_and_books`` -> ``_layer`` ->
``_kda`` carry them, and ``ops/pallas/kda_chunk.py`` runs no chunk past a
row's end. Here, on a Ling-shaped tiny model (delta attention beside
latent attention, routed experts; the kernel interpreted): the tokens and
the rows' own hidden states are, to the bit, those of the same step with
the lengths withheld from the kernel; and ``LlamaGenerator._step`` counts
the chunks the kernel was told to skip, ``kda_chunks_skipped``, as the
grid's less the live ones, and none for a model without the operator. The
two-width flash forward of the model's latent layer is told the same
lengths (PR 54; the kernel's own tests are ``tests/test_flash_lengths.py``)
and ``flash_blocks_skipped`` counts the blocks it did not compute; the
equal-width forward of grouped-query attention, full or ``sliding``, is
told them too (PR 56) and ``attn_blocks_skipped`` counts its blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, init_llama, llama_next_token
from ray_tpu.ops.pallas import kda_chunk
from ray_tpu.serve.llm import LlamaGenerator

CHUNK, BUCKET = 64, 256


def ling_shaped(**over):
    """Four layers as Ling-3.0-flash orders them (delta attention with a
    dense feed-forward, then routed: delta, latent attention, delta), 2
    heads of 128, chunks of 64, 8 experts of which 2 a token, in float32
    through the kernels."""
    kwargs = dict(
        vocab_size=256, hidden=64, mlp_hidden=32, num_layers=4, num_heads=2,
        num_kv_heads=2, head_dim=128, max_seq_len=BUCKET, rms_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="flash",
        num_experts=8, experts_per_token=2, norm_topk_prob=True,
        router_scores="sigmoid", router_bias=True, router_norm_eps=1e-20,
        routed_scaling_factor=2.5,
        layer_types=("kda", "kda", "latent_attention", "kda"),
        num_dense_layers=1, dense_mlp_hidden=96, kv_lora_rank=32,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_shared_experts=1, head_gate=True, kda_heads=2, kda_head_dim=128,
        kda_chunk=CHUNK)
    kwargs.update(over)
    return LlamaConfig(**kwargs)


def test_a_step_told_its_rows_lengths_is_the_step_that_was_not(monkeypatch):
    """Rows of no token, one, a chunk and one, and the whole bucket, padded
    on the right as ``_step`` pads them."""
    cfg = ling_shaped()
    params = init_llama(cfg, jax.random.key(3))
    lengths = np.asarray([0, 1, CHUNK + 1, 150, BUCKET])
    live = np.arange(BUCKET)[None, :] < lengths[:, None]
    tokens = np.where(live, np.asarray(jax.random.randint(
        jax.random.key(4), live.shape, 1, cfg.vocab_size)), 0)
    last = np.maximum(lengths - 1, 0).astype(np.int32)
    handed = []
    sound = kda_chunk.kda_chunked

    def step(withheld):
        def kernel(*args):
            handed.append(args[8])
            return sound(*args[:8], None if withheld else args[8])

        monkeypatch.setattr(kda_chunk, "kda_chunked", kernel)
        ids, hidden, _ = jax.jit(lambda p, t, i, on: llama_next_token(
            p, t, i, cfg, live=on))(params, tokens, last, live)
        return np.asarray(ids), np.asarray(hidden)

    ids, hidden = step(withheld=False)
    # a kernel a run of like layers, each handed the rows' lengths
    assert len(handed) == 3 and all(
        n is not None and n.shape == (5,) and n.dtype == jnp.int32
        for n in handed)
    want_ids, want_hidden = step(withheld=True)
    np.testing.assert_array_equal(ids[lengths > 0], want_ids[lengths > 0])
    np.testing.assert_array_equal(hidden[live], want_hidden[live])
    assert np.isfinite(hidden).all()
    # and the lengths did something: the padding's hidden states moved
    assert not np.array_equal(hidden[~live], want_hidden[~live])
    # without a mask no length is made: every position is wanted
    del handed[:]
    monkeypatch.setattr(kda_chunk, "kda_chunked", lambda *args: (
        handed.append(args[8]), sound(*args))[1])
    llama_next_token(params, jnp.asarray(tokens), jnp.asarray(last), cfg)
    assert handed == [None] * 3


@pytest.fixture(scope="module")
def generator():
    made = []

    def make(cfg):
        made.append(LlamaGenerator(
            config=cfg, max_batch_size=4, allowed_batch_sizes=[4],
            max_new_tokens=4, seq_bucket=128))
        return made[-1]

    yield make
    for gen in made:
        gen.engine.shutdown()


def test_the_step_counts_the_chunks_the_kernel_skipped(generator):
    """Steps with a long row, a short one and empty ones in a batch of 4:
    the counter is the grid's chunks less the live ones, whatever the
    rows."""
    gen = generator(ling_shaped())
    assert "kda_chunks_skipped" in gen.STEP_COUNTERS
    assert "kda_chunks_skipped" in LlamaGenerator.engine_stats.__doc__
    states = [gen._prefill({"prompt": list(range(1, n + 1)), "max_new": 2},
                           "") for n in (130, 5)] + [None, None]
    gen._step("", states)
    stats = gen.engine_stats()
    # a bucket of 256: 3 layers x 4 rows x 4 chunks, of which the long row
    # has three and the short one one
    assert stats["kda_chunks_run"] == 3 * 4 * 4
    assert stats["kda_chunks_live"] == 3 * (3 + 1)
    assert stats["kda_chunks_skipped"] == 3 * (1 + 3 + 4 + 4)
    # the short row alone, at a bucket of 128: two chunks a row
    gen._step("", [None, states[1], None, None])
    # and three whole rows of 128 beside it: nothing of theirs to skip
    whole = [gen._prefill({"prompt": [7] * 128, "max_new": 2}, "")
             for _ in range(3)]
    gen._step("", whole + [None])
    stats = gen.engine_stats()
    assert stats["kda_chunks_skipped"] == 3 * (12 + 7 + 2)
    assert stats["kda_chunks_skipped"] == (stats["kda_chunks_run"]
                                           - stats["kda_chunks_live"])


def test_the_step_counts_the_blocks_the_flash_forward_skipped(
        generator, monkeypatch):
    """The same steps over the model's one latent layer (2 heads), at
    tiles of 128: a bucket of 256 is 2 x 2 blocks a (row, head), 3 of them
    at or under the diagonal, and a bucket of 128 is ONE, where an empty
    row's alone is skipped."""
    from ray_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "flash_tiles", lambda *a, **kw: (128, 128))
    gen = generator(ling_shaped())
    for name in ("flash_blocks_run", "flash_blocks_live",
                 "flash_blocks_skipped"):
        assert name in gen.STEP_COUNTERS
        assert name in LlamaGenerator.engine_stats.__doc__
    states = [gen._prefill({"prompt": list(range(1, n + 1)), "max_new": 2},
                           "") for n in (130, 5)] + [None, None]
    gen._step("", states)
    stats = gen.engine_stats()
    # the long row's blocks are all live, the short row's first alone
    assert stats["flash_blocks_run"] == 2 * 4 * 3
    assert stats["flash_blocks_live"] == 2 * (3 + 1)
    assert stats["flash_blocks_skipped"] == 2 * (0 + 2 + 3 + 3)
    # the short row alone, at a bucket of 128: the three empty rows' block
    gen._step("", [None, states[1], None, None])
    assert gen.engine_stats()["flash_blocks_skipped"] == 2 * (8 + 3)
    # three whole rows of 128 and an empty one: the empty one's
    whole = [gen._prefill({"prompt": [7] * 128, "max_new": 2}, "")
             for _ in range(3)]
    gen._step("", whole + [None])
    stats = gen.engine_stats()
    assert stats["flash_blocks_run"] == 2 * 4 * (3 + 1 + 1)
    assert stats["flash_blocks_skipped"] == 2 * (8 + 3 + 1)
    assert stats["flash_blocks_skipped"] == (stats["flash_blocks_run"]
                                             - stats["flash_blocks_live"])
    # the model has no grouped-query attention layer
    assert stats["attn_blocks_run"] == 0 == stats["attn_blocks_skipped"]


def attention_shaped(sliding):
    """Grouped-query attention, 4 heads on 2 of 32: two dense layers, or
    Mellum2's pattern cut to three (two under a window of 65, then a full
    one) with 4 experts of which 2 a token."""
    kwargs = dict(
        vocab_size=256, hidden=64, mlp_hidden=96, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=32, max_seq_len=384, rms_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32)
    if sliding:
        kwargs.update(
            mlp_hidden=32, num_layers=3, num_experts=4, experts_per_token=2,
            norm_topk_prob=True, sliding_window=65,
            layer_types=("sliding_attention", "sliding_attention",
                         "full_attention"))
    return LlamaConfig(**kwargs)


def blocks_by_hand(pad_len, lengths, window=None, block=128):
    """``(run, live)`` a head over the rows: the kernel's rule, block by
    block. A step is run if its key block is at or under the diagonal and,
    under a window, holds a key that some query of the block sees."""
    run = live = 0
    for n in lengths:
        for iq in range(pad_len // block):
            for at in range(iq + 1):
                if window and (at + 1) * block - 1 < iq * block - window + 1:
                    continue
                run += 1
                live += iq * block < n and at * block < n
    return run, live


@pytest.mark.parametrize("sliding", [False, True], ids=["dense", "sliding"])
def test_the_step_counts_the_blocks_the_equal_width_forward_skipped(
        sliding, generator, monkeypatch):
    """The same kind of steps through grouped-query attention alone, at
    tiles of 128: a long row, a short one and two empty at a bucket of 384
    (3 x 3 blocks a (row, head), 6 at or under the diagonal, 5 of them
    inside a window of 65), then the short row alone at a bucket of 128,
    then three whole rows of 128 and an empty one."""
    from ray_tpu.ops.pallas import flash_attention as fa

    monkeypatch.setattr(fa, "flash_tiles", lambda *a, **kw: (128, 128))
    gen = generator(attention_shaped(sliding))
    for name in ("attn_blocks_run", "attn_blocks_live",
                 "attn_blocks_skipped"):
        assert name in gen.STEP_COUNTERS
        assert name in LlamaGenerator.engine_stats.__doc__
    heads = 4
    # (layers, window) of each operator
    operators = [(2, 65), (1, None)] if sliding else [(2, None)]
    states = [gen._prefill({"prompt": list(range(1, n + 1)), "max_new": 2},
                           "") for n in (260, 5)] + [None, None]
    gen._step("", states)
    stats = gen.engine_stats()
    if sliding:
        assert stats["attn_blocks_run"] == heads * 4 * (2 * 5 + 6)
        # the long row's blocks are all live, the short row's first alone
        assert stats["attn_blocks_live"] == heads * (2 * (5 + 1) + (6 + 1))
    else:
        assert stats["attn_blocks_run"] == heads * 4 * 2 * 6
        assert stats["attn_blocks_live"] == heads * 2 * (6 + 1)
    gen._step("", [None, states[1], None, None])
    whole = [gen._prefill({"prompt": [7] * 128, "max_new": 2}, "")
             for _ in range(3)]
    gen._step("", whole + [None])
    stats = gen.engine_stats()
    want_run = want_live = 0
    for pad_len, lengths in ((384, (260, 5, 0, 0)), (128, (6, 0, 0, 0)),
                             (128, (128, 128, 128, 0))):
        for layers, window in operators:
            run, live = blocks_by_hand(pad_len, lengths, window)
            assert (run, live) == fa.causal_blocks(pad_len, lengths,
                                                   (128, 128), window)
            want_run += layers * heads * run
            want_live += layers * heads * live
    assert stats["attn_blocks_run"] == want_run
    assert stats["attn_blocks_live"] == want_live
    assert 0 < stats["attn_blocks_skipped"] == want_run - want_live
    # `flash_blocks_*` are the two-width forward's alone
    assert stats["flash_blocks_run"] == 0 == stats["flash_blocks_skipped"]


def test_the_step_counts_the_windows_one_step_a_query_block(generator):
    """The counters count the blocks the kernel runs (PR 61): at a bucket
    of 2048 the sliding layers' forward, told a window of 65, runs one step
    a query block of 512 over its own keys and the 128 before them
    (``window_step``: four steps a (row, head)), and the full layer's walks
    the plain rule's 1024 x 1024 (three). A long row, a short one and two
    empty."""
    from ray_tpu.ops.pallas import flash_attention as fa

    cfg = attention_shaped(sliding=True)
    gen = generator(LlamaConfig(**{**cfg.__dict__, "max_seq_len": 2048}))
    heads, lengths = 4, np.asarray((2000, 5, 0, 0))
    states = [gen._prefill({"prompt": [1 + n % 200 for n in range(length)],
                            "max_new": 2}, "") for length in lengths[:2]]
    gen._step("", states + [None, None])
    stats = gen.engine_stats()
    assert stats["positions_computed"] == 4 * 2048
    assert fa.window_step(2048, 65, head_dim=cfg.head_dim) == (512, 128)
    assert fa.flash_tiles(2048, 2048, head_dim=cfg.head_dim) == (1024, 1024)
    # every block of the long row's, the short row's first alone
    sliding = fa.equal_width_blocks(2048, lengths, head_dim=cfg.head_dim,
                                    window=65)
    full = fa.equal_width_blocks(2048, lengths, head_dim=cfg.head_dim)
    assert sliding == (4 * 4, 4 + 1) and full == (4 * 3, 3 + 1)
    assert stats["attn_blocks_run"] == heads * (2 * 4 * 4 + 4 * 3)
    assert stats["attn_blocks_live"] == heads * (2 * 5 + 4)
    assert stats["attn_blocks_skipped"] == (stats["attn_blocks_run"]
                                            - stats["attn_blocks_live"])
    # the walk it replaces would count other blocks: three a (row, head)
    # of a sliding layer, of twice the query rows and 1024 keys each
    assert fa.causal_blocks(2048, lengths, (1024, 1024), 65) == (4 * 3, 4)
    # kept pairs over computed pairs in the sliding layers, a head, says
    # how well a step fits the window: 2.6 times the walk's share
    kept = stats["window_keys_kept"]
    assert kept == 2 * sum(n * 65 - 65 * 64 // 2 if n >= 65
                           else n * (n + 1) // 2 for n in lengths)
    computed = 2 * sliding[1] * 512 * (512 + 128)
    walked = 2 * 4 * 1024 * 1024
    assert 0.078 < kept / computed < 0.079 and computed / walked < 0.4


def test_a_model_without_the_operator_skips_no_chunk(generator):
    gen = generator(LlamaConfig.debug_1l())
    gen._step("", [gen._prefill({"prompt": [1, 2, 3], "max_new": 2}, ""),
                   None, None, None])
    stats = gen.engine_stats()
    assert stats["positions_computed"] == 4 * 128
    assert stats["kda_chunks_skipped"] == 0 == stats["kda_chunks_run"]
    assert stats["ssm_chunks_skipped"] == 0 == stats["ssm_chunks_run"]
    assert stats["flash_blocks_skipped"] == 0 == stats["flash_blocks_run"]
    # its one attention layer (2 heads) at a bucket of 128: one block a
    # (row, head), the three empty rows' skipped
    assert (stats["attn_blocks_run"], stats["attn_blocks_live"],
            stats["attn_blocks_skipped"]) == (4 * 2, 2, 3 * 2)
