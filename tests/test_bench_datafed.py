"""Data→Train feed proof at test scale (VERDICT r4 #6): the dense bench
step fed by Dataset.streaming_split/iter_jax_batches must train on real
blocks flowing through the streaming executor (reference:
train/_internal/data_config.py per-worker split)."""

import numpy as np
import pytest

import ray_tpu


def test_datafed_dense_step_runs():
    import bench
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.tiny()
    _, n = bench._run_dense_datafed(cfg, batch=4, seq=64, steps=3)
    assert n == 3
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()


def test_no_device_metric_off_the_chip():
    """A CPU run has no peak on record: utilization is an error there,
    never a number under the device metric's name."""
    import bench

    with pytest.raises(RuntimeError, match="no peak FLOP/s on record"):
        bench._peak_flops()
    with pytest.raises(RuntimeError, match="measures the chip"):
        bench.main()


def test_tokenize_rows_deterministic():
    import bench

    a = bench._tokenize_rows(np.arange(4), seq=8, vocab=128)
    b = bench._tokenize_rows(np.arange(4), seq=8, vocab=128)
    assert a["inputs"].shape == (4, 8) and a["targets"].shape == (4, 8)
    np.testing.assert_array_equal(a["inputs"], b["inputs"])
    # causal pairing: targets are inputs shifted by one position
    np.testing.assert_array_equal(a["inputs"][:, 1:], a["targets"][:, :-1])
    assert a["inputs"].min() >= 0 and a["inputs"].max() < 128
