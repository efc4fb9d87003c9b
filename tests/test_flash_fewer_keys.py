"""Attention over fewer keys than the causal ones, interpreted on the CPU:
the two-width flash forward under a window and under a choice of keys
against ``reference_attention``, the equal-width forward under a window
and under a choice (grouped-query heads, 8 query heads a key head), and
the indexer's score kernel against its einsums. The lengths are several blocks long and the
window and the choice are shorter than they are, so blocks are skipped and
keys masked."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import (
    attention, index_scores, reference_attention, reference_index_scores)
from ray_tpu.ops.pallas import flash_attention as fa
from ray_tpu.ops.pallas import index_scores as ix


def operands(seq, heads=2, d=32, r=16, dv=32, batch=2, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (batch, seq, heads, d)),
            jax.random.normal(ks[1], (batch, seq, heads, d)),
            jax.random.normal(ks[2], (batch, seq, heads, dv)),
            dict(q_rope=jax.random.normal(ks[3], (batch, seq, heads, r)),
                 k_rope=jax.random.normal(ks[4], (batch, seq, r))))


def some_keys(seq, k, batch=2, seed=1):
    """A choice of at most ``k`` causal keys a query, at random."""
    scores = jax.random.normal(jax.random.key(seed), (batch, seq, seq))
    at = jnp.arange(seq)
    causal = at[:, None] >= at[None, :]
    kth = jnp.sort(jnp.where(causal, scores, -jnp.inf), axis=-1)[..., -k]
    return causal & (scores >= kth[..., None])


@pytest.mark.parametrize("window", [1, 37, 128, 129, 513, 4000])
@pytest.mark.parametrize("seq", [128, 384, 1024])
def test_the_window_against_the_reference(seq, window):
    q, k, v, rope = operands(seq)
    got = attention(q, k, v, impl="flash", window=window, **rope)
    want = reference_attention(q, k, v, window=window, **rope)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # and the window is what it says: query t sees keys t - window + 1 .. t
    at = jnp.arange(seq)
    inside = (at[:, None] >= at[None, :]) & (
        at[:, None] - at[None, :] < window)
    same = reference_attention(q, k, v, keep=jnp.broadcast_to(
        inside, (2, seq, seq)), **rope)
    np.testing.assert_allclose(want, same, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kept", [1, 40, 300])
@pytest.mark.parametrize("seq", [128, 384, 1024])
def test_a_choice_of_keys_against_the_reference(seq, kept):
    q, k, v, rope = operands(seq, seed=2)
    keep = some_keys(seq, kept)
    got = attention(q, k, v, impl="flash", keep=keep, **rope)
    want = reference_attention(q, k, v, keep=keep, **rope)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # both at once: the choice inside a window
    both = attention(q, k, v, impl="flash", keep=keep, window=200, **rope)
    np.testing.assert_allclose(
        both, reference_attention(q, k, v, keep=keep, window=200, **rope),
        rtol=2e-5, atol=2e-5)


def test_neither_is_the_forward_it_always_was():
    q, k, v, rope = operands(256, seed=3)
    plain = attention(q, k, v, impl="flash", **rope)
    np.testing.assert_array_equal(
        plain, attention(q, k, v, impl="flash", window=None, keep=None,
                         **rope))
    # a window as long as the sequence, and a choice of every key
    np.testing.assert_allclose(
        plain, attention(q, k, v, impl="flash", window=256, **rope),
        rtol=1e-6, atol=1e-6)
    everything = jnp.ones((2, 256, 256), bool)
    np.testing.assert_allclose(
        plain, attention(q, k, v, impl="flash", keep=everything, **rope),
        rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("seq, block_q, block_k, window, blocks", [
    (5120, 640, 640, 513, 2), (3584, 512, 512, 513, 2),
    (4608, 768, 768, 513, 2), (1024, 128, 128, 513, 5),
    (1024, 512, 128, 130, 6), (1024, 1024, 1024, 513, 1),
    (2048, 512, 512, 4000, 4),
    # a window narrower than the tile: two key blocks, never three
    (6144, 1024, 1024, 512, 2), (6144, 1024, 1024, 511, 2),
    (6144, 1024, 1024, 513, 2), (1024, 1024, 1024, 512, 1),
    (6144, 1024, 1024, 1, 1), (6144, 1024, 1024, 1025, 2),
    (6144, 1024, 1024, 1026, 3)])
def test_the_windows_walk_is_as_long_as_its_widest_reach(
        seq, block_q, block_k, window, blocks):
    assert fa._window_key_blocks(seq, block_q, block_k, window) == blocks
    for iq in range(seq // block_q):
        first = int(fa._first_key_block(jnp.int32(iq), block_q, block_k,
                                        window))
        assert first == max(iq * block_q - window + 1, 0) // block_k
        last = (iq * block_q + block_q - 1) // block_k
        assert last - first + 1 <= blocks


def grouped(seq, heads=8, kv_heads=1, d=32, batch=2, seed=5):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (batch, seq, heads, d)),
            jax.random.normal(ks[1], (batch, seq, kv_heads, d)),
            jax.random.normal(ks[2], (batch, seq, kv_heads, d)))


# blocks of 128 (forced: `flash_tiles` gives one block up to 1152) and the
# rule's own; a window smaller than, equal to and larger than a block, one
# key, and longer than the sequence
@pytest.mark.parametrize("window", [1, 37, 127, 128, 129, 300, 4000])
@pytest.mark.parametrize("seq, tile", [(128, None), (512, 128), (512, None),
                                       (768, 256)])
def test_the_equal_width_window_against_the_reference(seq, tile, window,
                                                      monkeypatch):
    if tile:
        monkeypatch.setattr(fa, "flash_tiles",
                            lambda sq, skv, **kw: (tile, tile))
    q, k, v = grouped(seq)
    got = attention(q, k, v, impl="flash", window=window)
    want = reference_attention(q, k, v, window=window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    at = jnp.arange(seq)
    inside = (at[:, None] >= at[None, :]) & (
        at[:, None] - at[None, :] < window)
    same = reference_attention(q, k, v, keep=jnp.broadcast_to(
        inside, (2, seq, seq)))
    np.testing.assert_allclose(want, same, rtol=1e-6, atol=1e-6)
    if tile:  # the walk is the window's blocks and no other
        assert fa._window_key_blocks(seq, tile, tile, window) == min(
            seq // tile, -(-(window - 1) // tile) + 1)


# Laguna-XS.2's shapes of the same forward: groups of 6 (48 query heads on
# 8) and of 8 at 64 heads in one model, and a window NARROWER than the tile
# (512 inside blocks of 1024: the walk visited two key blocks a query block
# of whose 2048 keys at most 512 are inside; since PR 61 a step a query block
# of 512 over 1024), one key either side of it, and no window; told the rows' lengths (a whole row and one that ends inside a
# block) and not
# (interpreted, a head of a row costs a second: the model's 64 and 48 heads
# once each, told; the windows' edges and the untold call at 16 and 12 heads
# on 2, the same groups)
@pytest.mark.parametrize("heads, kv_heads, window, told", [
    (64, 8, 512, True), (48, 8, None, True),
    (16, 2, 511, False), (16, 2, 511, True), (16, 2, 512, False),
    (16, 2, 513, False), (16, 2, 513, True), (12, 2, None, False),
    (12, 2, 512, True)])
def test_a_window_narrower_than_the_tile_and_groups_of_six(
        heads, kv_heads, window, told):
    seq = 2048
    # the plain tile, which the walk would visit two blocks of; since PR 61
    # these windows run one step a query block of 512 (`window_step`)
    assert fa.flash_tiles(seq, seq, head_dim=128) == (1024, 1024)
    if window:
        assert fa._window_key_blocks(seq, 1024, 1024, window) == 2
        assert fa.window_step(seq, window, head_dim=128) == (512, 512)
    q, k, v = grouped(seq, heads=heads, kv_heads=kv_heads, d=128, batch=2)
    lengths = jnp.asarray([seq, 1300], jnp.int32) if told else None
    got = attention(q, k, v, impl="flash", window=window, lengths=lengths)
    want = reference_attention(q, k, v, window=window)
    own = (jnp.arange(seq)[None, :] < (
        lengths if told else jnp.full((2,), seq))[:, None])
    np.testing.assert_allclose(jnp.where(own[:, :, None, None], got, 0.0),
                               jnp.where(own[:, :, None, None], want, 0.0),
                               rtol=2e-5, atol=2e-5)
    if window:  # one key more or fewer is another result
        other = reference_attention(q, k, v, window=window + 1)
        assert float(jnp.abs(jnp.where(own[:, :, None, None],
                                       got - other, 0.0)).max()) > 1e-3
    if heads // kv_heads == 6:  # query head n reads key head n // 6
        wrong = reference_attention(
            q, jnp.repeat(k, 8, axis=2)[:, :, :heads],
            jnp.repeat(v, 8, axis=2)[:, :, :heads], window=window)
        if kv_heads > 1:
            assert float(jnp.abs(got - wrong).max()) > 1e-2


# one grid step a query block (PR 61, ``window_step``): a length the plain
# rule cuts into two blocks of 1024 runs, under a window whose tail fits
# beside a query block, at query blocks of 512 over their own keys and the
# tail before them (512 keys at a window of 300 to 513, 128 at one of 65),
# with no key dim in the grid and no running softmax; a window of 514 at
# 2048 (no block of 768), one of 1024 (its tail past VMEM) and keys in one
# block walk as they did. Told the rows' lengths (a whole row, one that ends
# inside a block, one under the window, an empty one) and not
@pytest.mark.parametrize("told", [False, True])
@pytest.mark.parametrize("seq, window, step", [
    (2048, 512, (512, 512)), (2048, 300, (512, 512)),
    (2048, 513, (512, 512)), (2048, 65, (512, 128)), (2048, 1, (512, 128)),
    (1536, 514, (768, 768)), (2048, 514, None), (2048, 1024, None),
    (1024, 512, None)])
def test_the_windows_one_step_against_the_masked_softmax(
        seq, window, step, told):
    assert fa.window_step(seq, window, head_dim=128) == step
    q, k, v = grouped(seq, heads=4, kv_heads=2, d=128, batch=4)
    lengths = jnp.asarray([seq, seq - 400, 200, 0], jnp.int32)
    got = attention(q, k, v, impl="flash", window=window,
                    lengths=lengths if told else None)
    want = reference_attention(q, k, v, window=window)
    own = (jnp.arange(seq)[None, :] < (
        lengths if told else jnp.full((4,), seq))[:, None])[:, :, None, None]
    np.testing.assert_allclose(jnp.where(own, got, 0.0),
                               jnp.where(own, want, 0.0),
                               rtol=2e-5, atol=2e-5)
    # one key more or fewer is another result
    for other in {max(window - 1, 1), window + 1} - {window}:
        off = reference_attention(q, k, v, window=other)
        assert float(jnp.abs(jnp.where(own, got - off, 0.0)).max()) > 1e-3
    block_q = step[0] if step else fa.flash_tiles(seq, seq, head_dim=128)[0]
    if told:  # past a row's end a whole block is zeros, an empty row all
        assert not np.asarray(got[3]).any()
        assert not np.asarray(got[2, block_q:]).any()
    # and the call is the grid the rule says: a step a query block and two
    # operands of keys, or the walk's key dim
    text = str(jax.make_jaxpr(lambda q, k, v: attention(
        q, k, v, impl="flash", window=window))(q, k, v))
    if step:
        assert f"grid=(4, 4, {seq // block_q})" in text
    else:
        walk = fa._window_key_blocks(seq, block_q, block_q, window)
        assert f"grid=(4, 4, {seq // block_q}, {walk})" in text


def test_the_equal_width_window_is_a_forward_alone_and_none_is_the_old_call():
    q, k, v = grouped(256, heads=4, kv_heads=2)
    plain = attention(q, k, v, impl="flash")
    # a window as long as the sequence
    np.testing.assert_allclose(
        plain, attention(q, k, v, impl="flash", window=256),
        rtol=1e-6, atol=1e-6)
    # no window compiles what it did: `_flash_fwd` told `window=None` is
    # the call without the argument, to the character
    text = lambda **kw: str(jax.make_jaxpr(  # noqa: E731
        lambda q, k, v: fa._flash_fwd(q, k, v, causal=True, **kw))(
            *(jnp.swapaxes(a, 1, 2) for a in (q, k, v))))
    assert text() == text(window=None)
    assert "flash_fwd_sliding" not in text() and "window" not in text()
    assert text(window=100) != text()
    # the windowed call under a function and a scope of its own
    call = lambda q, k, v: attention(  # noqa: E731
        q, k, v, impl="flash", window=100)
    assert "name=flash_attention_window" in str(
        jax.make_jaxpr(call)(q, k, v))
    assert fa.EQUAL_WINDOW_TRACE_NAME in jax.jit(call).lower(
        q, k, v).as_text(debug_info=True)
    assert fa.EQUAL_WINDOW_TRACE_NAME not in jax.jit(
        lambda q, k, v: attention(q, k, v, impl="flash")).lower(
            q, k, v).as_text(debug_info=True)
    with pytest.raises(NotImplementedError, match="has no backward"):
        jax.grad(lambda q: attention(q, k, v, impl="flash",
                                     window=8).sum())(q)
    with pytest.raises(ValueError, match="a prefill's"):
        fa._flash_fwd(*(jnp.swapaxes(a, 1, 2) for a in (q, k, v)),
                      causal=False, window=8)


@pytest.mark.parametrize("told", [False, True])
@pytest.mark.parametrize("heads, kv_heads", [(2, 2), (8, 2), (8, 1)])
@pytest.mark.parametrize("seq, tile, kept", [
    (128, None, 40), (512, 128, 40), (512, 128, 200), (512, None, 1)])
def test_the_equal_width_choice_against_the_reference(
        seq, tile, kept, heads, kv_heads, told, monkeypatch):
    """The equal-width forward under ``keep`` (Keye-VL-2.0-30B-A3B's
    layers): groups of 1, 4 and 8 query heads a key/value head, one block
    and four a side, told the rows' lengths and not; the last key block of
    each query's row holds NO chosen key (a whole block the choice left
    empty, after blocks that held some), and a told row of 30 is shorter
    than ``kept``."""
    if tile:
        monkeypatch.setattr(fa, "flash_tiles",
                            lambda sq, skv, **kw: (tile, tile))
    ks = jax.random.split(jax.random.key(7), 3)
    q = jax.random.normal(ks[0], (2, seq, heads, 32))
    k = jax.random.normal(ks[1], (2, seq, kv_heads, 32))
    v = jax.random.normal(ks[2], (2, seq, kv_heads, 32))
    at = jnp.arange(seq)
    # no key of a query's own block of 128 but the first query's own
    early = (at[None, :] < at[:, None] // 128 * 128) | (at[:, None] < 128)
    keep = some_keys(seq, kept, seed=3) & early
    keep = keep | (~keep.any(-1, keepdims=True) & (at[None, :] == 0))
    lengths = jnp.asarray([seq - 70, 30], jnp.int32) if told else None
    got = attention(q, k, v, impl="flash", keep=keep, lengths=lengths)
    want = reference_attention(q, k, v, keep=keep)
    assert got.dtype == q.dtype and got.shape == q.shape
    for b, n in enumerate(lengths.tolist() if told else [seq, seq]):
        np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=2e-5,
                                   atol=2e-5)
    if told and tile:  # past a row's last live block: zeros
        assert not np.asarray(got[1, 128:]).any()


def test_the_equal_width_choice_is_a_forward_alone_and_none_is_the_old_call():
    q, k, v, _ = operands(256, seed=5)
    keep = some_keys(256, 30)
    with pytest.raises(NotImplementedError, match="attn_impl='reference'"):
        jax.grad(lambda q: attention(q, k, v, impl="flash",
                                     keep=keep).sum())(q)
    text = str(jax.make_jaxpr(lambda q, k, v, keep: attention(
        q, k, v, impl="flash", keep=keep))(q, k, v, keep))
    assert fa.EQUAL_SELECTED_TRACE_NAME != fa.SELECTED_TRACE_NAME
    assert "flash_attention_selected" in text and "i8[2,256,256]" in text
    # the call without a choice is the one it was: no operand, no name
    plain = str(jax.make_jaxpr(lambda q, k, v: attention(
        q, k, v, impl="flash"))(q, k, v))
    assert "selected" not in plain and "i8[" not in plain
    np.testing.assert_allclose(
        attention(q, k, v, impl="flash",
                  keep=jnp.ones((2, 256, 256), bool)),
        attention(q, k, v, impl="flash"), rtol=2e-6, atol=2e-6)


def test_what_the_kernels_do_not_take():
    q, k, v, rope = operands(128)
    with pytest.raises(NotImplementedError, match="not both"):
        attention(q, k, v, impl="flash", window=8,
                  keep=jnp.ones((2, 128, 128), bool))
    with pytest.raises(ValueError, match="comes without a window"):
        fa._flash_fwd(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                      jnp.swapaxes(v, 1, 2), causal=True,
                      keep=jnp.ones((2, 128, 64), jnp.int8))
    with pytest.raises(ValueError, match="of the causal keys"):
        attention(q, k, v, impl="flash", causal=False, window=8)
    with pytest.raises(ValueError, match="a prefill's"):
        attention(q, k, v, impl="flash", causal=False, window=8, **rope)
    with pytest.raises(ValueError, match="causal keys' last"):
        reference_attention(q, k, v, causal=False, window=8)


@pytest.mark.parametrize("seq, heads, width, tol", [
    (128, 3, 32, 2e-5), (384, 8, 32, 2e-5), (1024, 4, 32, 2e-5),
    # Keye-VL-2.0-30B-A3B's indexer: 16 heads of 64, half a lane tile. A
    # score is a float32 sum over 16 heads of dots over 64 dims where the
    # cases above sum 3 to 8 heads of 32: scores of 25 to 30 in absolute
    # value, summed in another order than the einsums', so the tolerance
    # is the wider by the sum's length
    (512, 16, 64, 5e-5)])
def test_the_index_scores_against_the_einsums(seq, heads, width, tol):
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (2, heads, seq, width))
    k = jax.random.normal(ks[1], (2, seq, width))
    w = jax.random.normal(ks[2], (2, seq, heads))
    got = index_scores(q, k, w, impl="flash")
    want = reference_index_scores(q, k, w)
    assert got.dtype == jnp.float32 and got.shape == (2, seq, seq)
    at = np.arange(seq)
    causal = at[:, None] >= at[None, :]
    np.testing.assert_allclose(np.where(causal, got, 0),
                               np.where(causal, want, 0),
                               rtol=tol, atol=tol)
    # the blocks wholly above the diagonal are zeros, not products
    block_q, block_k = ix.index_tiles(seq)
    above = (at[None, :] // block_k * block_k
             > at[:, None] // block_q * block_q + block_q - 1)
    assert not np.asarray(got)[:, above].any()
    np.testing.assert_array_equal(index_scores(q, k, w, impl="auto"), want)
    with pytest.raises(ValueError, match="divide by 128"):
        ix.index_tiles(200)
