"""Kernel correctness: flash attention (interpret mode) and ring attention
against the XLA reference path, on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import reference_attention


def _rand_qkv(key, B=2, S=256, H=4, KVH=2, D=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, KVH, D), dtype)
    v = jax.random.normal(kv, (B, S, KVH, D), dtype)
    return q, k, v


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _rand_qkv(jax.random.key(0))
        out = flash_attention(q, k, v, causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa_grouping(self):
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _rand_qkv(jax.random.key(1), H=8, KVH=2)
        out = flash_attention(q, k, v, True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_grad_flows(self):
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _rand_qkv(jax.random.key(2), S=128)

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, True) ** 2)

        g = jax.grad(loss)(q, k, v)
        gref = jax.grad(
            lambda q, k, v: jnp.sum(
                reference_attention(q, k, v, causal=True) ** 2))(q, k, v)
        np.testing.assert_allclose(g, gref, atol=1e-4, rtol=1e-4)


def _old_block(n):
    """What the flash kernels tried before PR 29: 512, 256, then 128."""
    return next(b for b in (512, 256, 128) if n % b == 0 or b == 128)


class TestFlashTiles:
    """``flash_tiles`` is a pure function of the lengths: no chip needed."""

    @pytest.mark.parametrize("backward", [False, True])
    @pytest.mark.parametrize("seq", range(128, 4097, 128))
    def test_every_multiple_of_128(self, seq, backward):
        from ray_tpu.ops.pallas.flash_attention import (
            VMEM_LIMIT_BYTES, flash_tiles, tile_vmem_bytes)

        bq, bk = flash_tiles(seq, seq, backward=backward)
        assert seq % bq == 0 and seq % bk == 0
        assert bq % 128 == 0 and bk % 128 == 0
        assert (seq // bq) * (seq // bk) <= (seq // _old_block(seq)) ** 2
        assert tile_vmem_bytes(bq, bk, backward=backward) <= VMEM_LIMIT_BYTES
        if (bq, bk) == (128, 128):
            # left at 128 x 128 only where nothing coarser is reckoned to fit
            divisors = [b for b in range(128, seq + 1, 128) if seq % b == 0]
            assert not [
                (q, k) for q in divisors for k in divisors
                if (q, k) != (128, 128) and tile_vmem_bytes(
                    q, k, backward=backward) <= VMEM_LIMIT_BYTES]

    @pytest.mark.parametrize("backward", [False, True])
    @pytest.mark.parametrize("seq, tile", [
        (128, (128, 128)), (256, (256, 256)), (512, (512, 512)),
        (1024, (1024, 1024)), (2048, (1024, 1024)), (4096, (1024, 1024))])
    def test_one_rule_cuts_the_lengths_512_divides_too(self, seq, tile,
                                                       backward):
        from ray_tpu.ops.pallas.flash_attention import flash_tiles

        assert flash_tiles(seq, seq, backward=backward) == tile

    @pytest.mark.parametrize("seq, tile", [
        (384, (384, 384)), (640, (640, 640)), (768, (768, 768)),
        (896, (896, 896)), (1152, (1152, 1152)), (1408, (128, 1408))])
    def test_the_serving_buckets_run_as_one_block(self, seq, tile):
        from ray_tpu.ops.pallas.flash_attention import flash_tiles

        assert flash_tiles(seq, seq) == tile

    def test_each_length_is_cut_by_itself(self):
        from ray_tpu.ops.pallas.flash_attention import flash_tiles

        assert flash_tiles(384, 1024) == (384, 1024)
        assert flash_tiles(2048, 640) == (1024, 640)

    @pytest.mark.parametrize("seq", [64, 192, 1000])
    def test_a_length_128_does_not_divide_is_refused(self, seq):
        from ray_tpu.ops.pallas.flash_attention import (
            flash_attention, flash_tiles)

        with pytest.raises(ValueError, match="divide by 128"):
            flash_tiles(seq, seq)
        q, k, v = _rand_qkv(jax.random.key(0), B=1, S=seq, H=2, KVH=1)
        with pytest.raises(ValueError, match="divide by 128"):
            flash_attention(q, k, v, True)


class TestWindowStep:
    """``window_step``: where the equal-width forward under a window runs
    one grid step a query block, and at which block and tail. Pure: no chip
    needed."""

    # Laguna-XS.2's window at its buckets of several key blocks: a query
    # block of 512 and the 512 keys before it, 1024 keys a query where the
    # walk's two blocks of 1024 were 2048
    @pytest.mark.parametrize("seq", range(2048, 6145, 1024))
    def test_lagunas_window_runs_a_step_a_query_block_of_512(self, seq):
        from ray_tpu.ops.pallas.flash_attention import (
            causal_blocks, equal_width_blocks, flash_tiles, window_step)

        assert window_step(seq, 512) == (512, 512)
        whole, short = [seq, seq // 3, 0], seq // 3
        run, live = equal_width_blocks(seq, whole, head_dim=128, window=512)
        assert run == 3 * (seq // 512)
        assert live == seq // 512 + -(-short // 512)
        # the walk it replaces: two blocks of 1024 a block of 1024 queries
        walk, _ = causal_blocks(seq, whole, flash_tiles(seq, seq), 512)
        assert walk == 3 * (2 * (seq // 1024) - 1)
        # two thirds of its scores at 2048, 6 in 11 at 6144
        assert 3 * run * 512 * (512 + 512) <= 2 * walk * 1024 * 1024
        # and no window: the causal walk, as ever
        assert equal_width_blocks(seq, whole, head_dim=128) == (
            causal_blocks(seq, whole, flash_tiles(seq, seq)))

    # Mellum2's window at its five buckets and at the 3072 its warm-up
    # stream compiles: its tail of 1024 beside a block of 1024 is past
    # VMEM's reckoning, so the walk; Laguna's at 1024, the 1152 of nine
    # 128s and 1408: the plain rule's keys are one block there, and a walk
    # of one step is the step
    @pytest.mark.parametrize("window, seq", [
        *((1024, seq) for seq in range(3072, 8193, 1024)), (512, 1024),
        (512, 1152), (512, 1408), (24, 256), (24, 128), (4000, 8192),
        (1025, 4096), (700, 5120)])
    def test_where_the_window_walks(self, window, seq):
        from ray_tpu.ops.pallas.flash_attention import (
            causal_blocks, equal_width_blocks, flash_tiles, window_step)

        assert window_step(seq, window) is None
        lengths = [seq, 5]
        assert equal_width_blocks(
            seq, lengths, head_dim=128, window=window) == causal_blocks(
                seq, lengths, flash_tiles(seq, seq), window)

    # the tail is the shortest divisor of the block that holds the window
    # less one key (a block's first query sees so many keys before the
    # block); the block is the shortest from 512 up that has such a divisor
    @pytest.mark.parametrize("window, seq, step", [
        (1, 2048, (512, 128)), (24, 2048, (512, 128)),
        (129, 2048, (512, 128)), (130, 2048, (512, 256)),
        (300, 5120, (512, 512)), (513, 5120, (512, 512)),
        (514, 5120, (640, 640)), (514, 6144, (768, 768)),
        (700, 6144, (768, 768)), (512, 1536, (512, 512)),
        (512, 1920, (640, 640))])
    def test_the_block_and_the_tail(self, window, seq, step):
        from ray_tpu.ops.pallas.flash_attention import (
            VMEM_LIMIT_BYTES, tile_vmem_bytes, window_step)

        block_q, tail = step
        assert window_step(seq, window) == step
        assert seq % block_q == 0 == block_q % tail and tail >= window - 1
        assert tile_vmem_bytes(block_q, block_q + tail) <= VMEM_LIMIT_BYTES

    def test_a_head_of_64_takes_the_same_step(self):
        from ray_tpu.ops.pallas.flash_attention import window_step

        assert window_step(6144, 512, head_dim=64) == (512, 512)
        # one block at 1408 for a head of 64: the walk
        assert window_step(1408, 512, head_dim=64) is None


class TestFlashAtTheRulesTiles:
    """Interpret mode, grouped heads (2 query heads on 1 key/value head),
    D 128, B 1: the serving buckets the rule before PR 29 left at block
    128; 1024 as one block in both passes; 1408 for a forward with more
    than one key block; and 2048, the shortest length 512 divides that the
    one rule cuts into more than one block in every pass (1024 x 1024, the
    tile training's 4096 runs: the running maximum and sum folded across
    key blocks, dq and dk/dv summed across blocks, the causal skip)."""

    @pytest.mark.parametrize("seq", [384, 640, 896, 1152, 1024, 1408, 2048])
    def test_forward_matches_reference(self, seq):
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _rand_qkv(jax.random.key(seq), B=1, S=seq, H=2, KVH=1,
                            D=128)
        out = flash_attention(q, k, v, True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("seq", [384, 640, 1024, 2048])
    def test_gradient_matches_reference(self, seq):
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _rand_qkv(jax.random.key(seq), B=1, S=seq, H=2, KVH=1,
                            D=128)
        g = jax.random.normal(jax.random.key(seq + 1), q.shape, q.dtype)
        gr = jax.grad(lambda *a: (reference_attention(
            *a, causal=True) * g).sum(), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(lambda *a: (flash_attention(*a, True) * g).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gr, gf, "qkv"):
            assert float(jnp.abs(a - b).max()) < 1e-4, n


class TestFlashHeadDim64:
    """A head of 64, half the lane width (LFM2-24B-A2B's: 4 query heads on
    each key/value head), interpreted: the block's last dim is the whole
    head. What Mosaic makes of it is in ``test_flash_tiles_v5e.py``."""

    @pytest.mark.parametrize("seq", [384, 1024, 1408])
    def test_forward_matches_reference(self, seq):
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _rand_qkv(jax.random.key(seq), B=1, S=seq, H=4, KVH=1,
                            D=64)
        out = flash_attention(q, k, v, True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gradient_matches_reference(self):
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        q, k, v = _rand_qkv(jax.random.key(7), B=1, S=384, H=4, KVH=1, D=64)
        g = jax.random.normal(jax.random.key(8), q.shape, q.dtype)
        gr = jax.grad(lambda *a: (reference_attention(
            *a, causal=True) * g).sum(), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(lambda *a: (flash_attention(*a, True) * g).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gr, gf, "qkv"):
            assert float(jnp.abs(a - b).max()) < 1e-4, n

    def test_the_tile_and_the_dims_the_kernel_takes(self):
        from ray_tpu.ops.pallas.flash_attention import (
            flash_tiles, takes_head_dim)

        assert [d for d in (32, 64, 96, 128, 192, 256)
                if takes_head_dim(d)] == [64, 128, 256]
        # a narrower head leaves room for one block of the longest
        # serving bucket, which a head of 128 cuts into (128, 1408)
        assert flash_tiles(1408, 1408, head_dim=64) == (1408, 1408)
        assert flash_tiles(1408, 1408, head_dim=128) == (128, 1408)
        for seq in range(128, 1153, 128):
            assert flash_tiles(seq, seq, head_dim=64) == flash_tiles(seq, seq)


class TestRingAttention:
    def test_matches_reference(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from ray_tpu.ops.ring_attention import ring_attention

        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs.reshape(4), ("seq",))
        q, k, v = _rand_qkv(jax.random.key(3), S=64, H=4, KVH=4, D=16)
        spec = P(None, "seq", None, None)
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="seq"),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))
        out = fn(q, k, v)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa_noncausal(self):
        from jax.sharding import Mesh, PartitionSpec as P
        from ray_tpu.ops.ring_attention import ring_attention

        devs = np.array(jax.devices()[:2])
        mesh = Mesh(devs.reshape(2), ("seq",))
        q, k, v = _rand_qkv(jax.random.key(4), S=32, H=4, KVH=2, D=8)
        spec = P(None, "seq", None, None)
        fn = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="seq",
                                           causal=False),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False))
        out = fn(q, k, v)
        ref = reference_attention(q, k, v, causal=False)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


class TestFlashBackward:
    def test_pallas_bwd_matches_reference(self):
        """The custom dq/dkv kernels (interpret mode on CPU) must produce
        reference gradients — the training-path correctness gate."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.attention import reference_attention
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        k1, k2, k3, k4 = jax.random.split(jax.random.key(2), 4)
        B, S, H, D = 1, 256, 2, 128
        q = jax.random.normal(k1, (B, S, H, D), jnp.float32)
        k = jax.random.normal(k2, (B, S, H, D), jnp.float32)
        v = jax.random.normal(k3, (B, S, H, D), jnp.float32)
        g = jax.random.normal(k4, (B, S, H, D), jnp.float32)
        gr = jax.grad(lambda *a: (reference_attention(
            *a, causal=True) * g).sum(), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(lambda *a: (flash_attention(*a, True) * g).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b, n in zip(gr, gf, "qkv"):
            assert float(jnp.abs(a - b).max()) < 5e-5, n

    def test_gqa_backward_native(self):
        """n_rep > 1 runs the native Pallas dk/dv kernel (grid walks each
        kv head's query group; VERDICT r2 item 6) — gradients must match
        reference attention."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.attention import reference_attention
        from ray_tpu.ops.pallas.flash_attention import flash_attention

        k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
        q = jax.random.normal(k1, (1, 256, 4, 128), jnp.float32)
        k = jax.random.normal(k2, (1, 256, 2, 128), jnp.float32)
        v = jax.random.normal(k3, (1, 256, 2, 128), jnp.float32)
        gr = jax.grad(lambda *a: reference_attention(
            *a, causal=True).sum(), argnums=(0, 1, 2))(q, k, v)
        gf = jax.grad(lambda *a: flash_attention(*a, True).sum(),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gr, gf):
            assert float(jnp.abs(a - b).max()) < 1e-3
