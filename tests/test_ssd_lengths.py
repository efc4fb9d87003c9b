"""The rows' lengths reach the state-space scan from a serving step (PR
59), the way they reach the delta rule (``tests/test_kda_lengths.py``):
``llama_next_token`` takes them off the mask it is handed anyway,
``_hidden_and_books`` -> ``_layer`` -> ``_mamba`` -> ``ops.ssm.ssd_scan``
carry them, and ``ops/pallas/ssd_scan.py`` runs no chunk past a row's end.
Here, on a granite-shaped tiny model (Mamba-2 mixers beside one attention
layer, no positions; the kernel interpreted): the tokens and the rows' own
hidden states are, to the bit, those of the same step with the lengths
withheld from the scan; and ``LlamaGenerator._step`` counts the chunks the
kernel was told to skip, ``ssm_chunks_skipped``, as the grid's less the
live ones (none for a model without the operator:
``tests/test_kda_lengths.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, init_llama, llama_next_token
from ray_tpu.ops import ssm
from ray_tpu.serve.llm import LlamaGenerator

CHUNK, BUCKET = 128, 512


def granite_shaped(**over):
    """Four layers as granite-4.0-h orders them (mixers round one attention
    layer), 4 heads of 32 over a state of 16, chunks of 128, in float32
    through the kernels."""
    kwargs = dict(
        vocab_size=256, hidden=64, mlp_hidden=96, num_layers=4, num_heads=2,
        num_kv_heads=1, head_dim=128, max_seq_len=BUCKET, rms_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="flash",
        layer_types=("mamba", "mamba", "full_attention", "mamba"),
        mamba_heads=4, mamba_head_dim=32, mamba_state=16, mamba_chunk=CHUNK,
        use_rope=False)
    kwargs.update(over)
    return LlamaConfig(**kwargs)


def test_a_step_told_its_rows_lengths_is_the_step_that_was_not(monkeypatch):
    """Rows of no token, one, a chunk and one, 300 and the whole bucket,
    padded on the right as ``_step`` pads them."""
    cfg = granite_shaped()
    params = init_llama(cfg, jax.random.key(3))
    lengths = np.asarray([0, 1, CHUNK + 1, 300, BUCKET])
    live = np.arange(BUCKET)[None, :] < lengths[:, None]
    tokens = np.where(live, np.asarray(jax.random.randint(
        jax.random.key(4), live.shape, 1, cfg.vocab_size)), 0)
    last = np.maximum(lengths - 1, 0).astype(np.int32)
    handed = []
    sound = ssm.ssd_scan

    def step(withheld):
        def scan(*args, lengths=None, **kwargs):
            handed.append(lengths)
            return sound(*args, lengths=None if withheld else lengths,
                         **kwargs)

        monkeypatch.setattr(ssm, "ssd_scan", scan)
        ids, hidden, _ = jax.jit(lambda p, t, i, on: llama_next_token(
            p, t, i, cfg, live=on))(params, tokens, last, live)
        return np.asarray(ids), np.asarray(hidden)

    ids, hidden = step(withheld=False)
    # a scan a run of like layers, each handed the rows' lengths
    assert len(handed) == 2 and all(
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
    llama_next_token(params, jnp.asarray(tokens), jnp.asarray(last), cfg)
    assert handed == [None] * 2


@pytest.fixture(scope="module")
def generator():
    gen = LlamaGenerator(
        config=granite_shaped(), max_batch_size=4, allowed_batch_sizes=[4],
        max_new_tokens=4, seq_bucket=128)
    yield gen
    gen.engine.shutdown()


def test_the_step_counts_the_chunks_the_scan_skipped(generator):
    """Steps with a long row, a short one and empty ones in a batch of 4:
    the counter is the grid's chunks less the live ones, whatever the
    rows, and ``ssm_chunks_run`` and ``ssm_chunks_live`` mean what they
    meant."""
    gen = generator
    assert "ssm_chunks_skipped" in gen.STEP_COUNTERS
    assert "ssm_chunks_skipped" in LlamaGenerator.engine_stats.__doc__
    states = [gen._prefill({"prompt": list(range(1, n + 1)), "max_new": 2},
                           "") for n in (260, 5)] + [None, None]
    gen._step("", states)
    stats = gen.engine_stats()
    # a bucket of 384: 3 mixers x 4 rows x 3 chunks, of which the long row
    # has three and the short one one
    assert stats["positions_computed"] == 4 * 384
    assert stats["ssm_chunks_run"] == 3 * 4 * 3
    assert stats["ssm_chunks_live"] == 3 * (3 + 1)
    assert stats["ssm_chunks_skipped"] == 3 * (0 + 2 + 3 + 3)
    # the short row alone, at a bucket of 128: one chunk a row
    gen._step("", [None, states[1], None, None])
    # and three whole rows of 128 beside it: the empty row's alone to skip
    whole = [gen._prefill({"prompt": [7] * 128, "max_new": 2}, "")
             for _ in range(3)]
    gen._step("", whole + [None])
    stats = gen.engine_stats()
    assert stats["ssm_chunks_skipped"] == 3 * (8 + 3 + 1)
    assert stats["ssm_chunks_skipped"] == (stats["ssm_chunks_run"]
                                           - stats["ssm_chunks_live"])
    assert stats["kda_chunks_skipped"] == 0 == stats["kda_chunks_run"]
