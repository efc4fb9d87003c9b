"""Kimi delta attention's chunked kernel (``ops/pallas/kda_chunk.py``),
interpreted, against the recurrence itself (``ops.kda.reference_kda``):
chunks SHORTER than the lengths, so that the state crosses edges, lengths
that are not whole chunks, so that a ragged last chunk bites, an entering
state, right-padded rows, the decay at its bound for whole chunks, and the
controls that say the tolerance can tell a fault: the state dropped at the
chunks' edges, the delta term left out. Then the kernel told its rows'
lengths (PR 53): a row's own outputs to the bit, zeros past its end, the
state after its last chunk that ran, and nothing of the chunks it skips
read.

Both sides compute in float32 here and differ by the order of sums alone
(the kernel's sums run by chunk and its solve by block): some 1e-6 of
outputs of order 0.1 to 1.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import kda
from ray_tpu.ops.pallas import kda_chunk as kernel

TIGHT = dict(rtol=2e-5, atol=2e-5)


def inputs(B=2, S=200, H=4, D=128, dtype=jnp.float32, seed=0, slowest=-0.001,
           fastest=-0.3):
    """Operands as a layer makes them: ``q`` and ``k`` of unit length a
    head, ``q`` times ``D ** -0.5``, ``beta`` a sigmoid, and a log-decay a
    channel between ``fastest`` and ``slowest`` a position, so that a
    channel's memory runs from a few positions to many chunks."""
    k = jax.random.split(jax.random.key(seed), 6)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    q = (unit(jax.random.normal(k[0], (B, S, H, D))) * D ** -0.5
         ).astype(dtype)
    kk = unit(jax.random.normal(k[1], (B, S, H, D))).astype(dtype)
    v = jax.random.normal(k[2], (B, S, H, D)).astype(dtype)
    g = -jnp.exp(jax.random.uniform(
        k[3], (B, S, H, D), minval=np.log(-slowest), maxval=np.log(-fastest)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (B, S, H)))
    s0 = jax.random.normal(k[5], (B, H, D, D))
    return q, kk, v, g, beta, s0


# lengths of whole chunks and not, one chunk and several, a single position
@pytest.mark.parametrize("S, chunk", [(200, 64), (256, 64), (256, 128),
                                      (70, 64), (1, 64), (300, 128)])
def test_the_kernel_is_the_recurrence(S, chunk):
    q, k, v, g, beta, s0 = inputs(S=S)
    want_o, want_s = kda.reference_kda(q, k, v, g, beta, s0)
    o, s = kda.kda(q, k, v, g, beta, s0, chunk, impl="flash")
    assert o.shape == q.shape and o.dtype == q.dtype
    assert s.shape == s0.shape and s.dtype == jnp.float32
    np.testing.assert_allclose(o, want_o, **TIGHT)
    np.testing.assert_allclose(s, want_s, **TIGHT)


def test_a_sequence_starts_from_zeros_without_a_state():
    q, k, v, g, beta, _ = inputs(S=200)
    want = kda.reference_kda(q, k, v, g, beta)
    got = kda.kda(q, k, v, g, beta, None, 64, impl="flash")
    zeros = kda.kda(q, k, v, g, beta, jnp.zeros((2, 4, 128, 128)), 64,
                    impl="flash")
    for a, z, w in zip(got, zeros, want):
        np.testing.assert_allclose(a, w, **TIGHT)
        np.testing.assert_array_equal(a, z)


def test_the_state_crosses_the_chunks_edges():
    """The control: each chunk run from zeros, as a kernel that lost its
    state between grid steps would. The tolerance tells it by three orders
    of magnitude."""
    q, k, v, g, beta, _ = inputs(S=192)
    want, _ = kda.reference_kda(q, k, v, g, beta)
    cut = jnp.concatenate([
        kda.kda(*(a[:, s:s + 64] for a in (q, k, v, g, beta)), None, 64,
                impl="flash")[0] for s in (0, 64, 128)], axis=1)
    np.testing.assert_allclose(cut[:, :64], want[:, :64], **TIGHT)
    assert float(jnp.abs(cut[:, 64:] - want[:, 64:]).max()) > 1e3 * 2e-5


def test_the_delta_term_is_in_it():
    """The control: what the state already answers to ``k_t`` left on
    ``v_t`` (plain gated linear attention) moves the outputs by far more
    than the tolerance."""
    q, k, v, g, beta, s0 = inputs()
    o, _ = kda.kda(q, k, v, g, beta, s0, 64, impl="flash")

    def no_delta(s, at):
        q_t, k_t, v_t, g_t, b_t = at
        s = jnp.exp(g_t)[..., None] * s
        s = s + (b_t[..., None] * k_t)[..., None] * v_t[:, :, None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, without = jax.lax.scan(no_delta, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    assert float(jnp.abs(o - jnp.moveaxis(without, 0, 1)).max()) > 1e3 * 2e-5


def test_a_row_shorter_than_its_bucket():
    """Right-padded rows, as a serving step pads them (one token repeated
    past a row's end): what follows a position does not reach it, so a
    row's own outputs are those of the row alone."""
    q, k, v, g, beta, _ = inputs(S=256)
    n = 150
    alone, state = kda.kda(*(a[:1, :n] for a in (q, k, v, g, beta)), None,
                           64, impl="flash")
    padded = [jnp.concatenate(
        [a[:1, :n], jnp.broadcast_to(a[:1, n:n + 1], (1, 256 - n)
                                     + a.shape[2:])], axis=1)
        for a in (q, k, v, g, beta)]
    o, _ = kda.kda(*padded, None, 64, impl="flash")
    np.testing.assert_allclose(o[:, :n], alone, **TIGHT)
    want, want_state = kda.reference_kda(
        *(a[:1, :n] for a in (q, k, v, g, beta)))
    np.testing.assert_allclose(alone, want, **TIGHT)
    np.testing.assert_allclose(state, want_state, **TIGHT)


@pytest.mark.parametrize("chunk", [64, 128])
def test_the_decay_at_its_bound_for_whole_chunks_does_not_overflow(chunk):
    """``g`` at -5 in every channel of every position: a chunk's running
    sum reaches -5 x chunk, ``exp`` of its negative is far outside float32,
    and the kernel never forms it: everything is finite and the
    recurrence's. Then -5 in the first chunk only, a slow decay after it,
    so that what the fast chunk leaves in the state is read."""
    q, k, v, g, beta, s0 = inputs(S=2 * chunk)
    for bound in (jnp.full_like(g, kda.G_LOWER_BOUND),
                  g.at[:, :chunk].set(kda.G_LOWER_BOUND)):
        want_o, want_s = kda.reference_kda(q, k, v, bound, beta, s0)
        o, s = kda.kda(q, k, v, bound, beta, s0, chunk, impl="flash")
        assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
        np.testing.assert_allclose(o, want_o, **TIGHT)
        np.testing.assert_allclose(s, want_s, **TIGHT)


def test_no_decay_and_a_full_beta():
    """The other end: ``g`` 0 and ``beta`` 1 with keys that repeat, the
    case in which the triangular system is as far from the identity as it
    gets (every ``A_kk`` entry of a repeated key is 1)."""
    q, k, v, g, beta, s0 = inputs(S=128)
    k = jnp.tile(k[:, :4], (1, 32, 1, 1))
    want_o, want_s = kda.reference_kda(q, k, v, 0 * g, beta ** 0, s0)
    o, s = kda.kda(q, k, v, 0 * g, beta ** 0, s0, 64, impl="flash")
    np.testing.assert_allclose(o, want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(s, want_s, rtol=1e-4, atol=1e-4)


def test_the_heads_are_brought_to_unit_length_inside():
    """``l2_norm``: the kernel is handed ``q`` and ``k`` at any length and
    norms each head's itself, ``q`` then times ``D ** -0.5``: the same
    numbers as norming them first, in the kernel and in the recurrence; a
    padded position's zeros stay zeros."""
    q, k, v, g, beta, s0 = inputs(S=150)
    raw_q, raw_k = 3.0 * q * 128 ** 0.5, 0.2 * k

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    want_o, want_s = kda.reference_kda(unit(raw_q) * 128 ** -0.5, unit(raw_k),
                                       v, g, beta, s0)
    for impl in ("flash", "reference"):
        o, s = kda.kda(raw_q, raw_k, v, g, beta, s0, 64, impl=impl,
                       l2_norm=True)
        assert bool(jnp.isfinite(o).all())
        np.testing.assert_allclose(o, want_o, **TIGHT)
        np.testing.assert_allclose(s, want_s, **TIGHT)
    plain, _ = kda.kda(raw_q, raw_k, v, g, beta, s0, 64, impl="flash")
    assert float(jnp.abs(plain - want_o).max()) > 1e3 * 2e-5


def test_bf16_operands_round_as_the_chip_rounds_them():
    """bf16 in: the kernel hands the MXU bf16 operands and rounds its
    outputs to bf16, so it lies within bf16's 8 bits of the recurrence
    over the same numbers, and far outside the float32 tolerance."""
    q, k, v, g, beta, s0 = inputs(dtype=jnp.bfloat16)
    want_o, want_s = kda.reference_kda(q, k, v, g, beta, s0)
    o, s = kda.kda(q, k, v, g, beta, s0, 64, impl="flash")
    assert o.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    size = float(jnp.abs(want_o.astype(jnp.float32)).max())
    off = float(jnp.abs(o.astype(jnp.float32)
                        - want_o.astype(jnp.float32)).max())
    assert 2e-5 * size < off < 0.03 * size
    np.testing.assert_allclose(s, want_s, rtol=0.03, atol=0.03)


def test_the_dispatcher():
    q, k, v, g, beta, s0 = inputs(S=128)
    # on the CPU `auto` is the recurrence
    auto = kda.kda(q, k, v, g, beta, s0, 64)
    ref = kda.kda(q, k, v, g, beta, s0, 64, impl="reference")
    for a, r in zip(auto, ref):
        np.testing.assert_array_equal(a, r)
    with pytest.raises(ValueError, match="unknown kda impl"):
        kda.kda(q, k, v, g, beta, s0, 64, impl="xla")
    with pytest.raises(ValueError, match="not whole chunks"):
        kernel.kda_chunked(q[:, :100], k[:, :100], v[:, :100], g[:, :100],
                           beta[:, :100], s0, 64)
    with pytest.raises(ValueError, match="kda_chunked: q"):
        kernel.kda_chunked(q, k, v, g, beta[..., :2], s0, 64)
    assert kernel.kda_heads_a_step(32) == 4
    assert kernel.kda_heads_a_step(2) == 2


# ---------------------------------------------------------------------------
# the kernel told its rows' lengths
# ---------------------------------------------------------------------------
CHUNK, LENGTH = 64, 256
# one batch: no position, one, a chunk less one, a chunk, a chunk and one,
# all but one, all
ROWS = (0, 1, CHUNK - 1, CHUNK, CHUNK + 1, LENGTH - 1, LENGTH)


@pytest.fixture(scope="module")
def told():
    """The kernel with and without its rows' lengths on one right-padded
    batch, and with NaNs planted wherever it was told not to look."""
    ops = inputs(B=len(ROWS), S=LENGTH, H=4)
    lengths = jnp.asarray(ROWS, jnp.int32)
    run = jax.jit(lambda *a, n=None: kernel.kda_chunked(*a, CHUNK, False, n))
    q, k, v, g, beta, s0 = ops
    # a NaN in every operand the kernel reads by chunk, from the first
    # chunk edge at or past a row's end on
    edge = -(-lengths // CHUNK) * CHUNK
    past = (jnp.arange(LENGTH) >= edge[:, None])[..., None, None]
    planted = tuple(jnp.where(past, jnp.nan, a) for a in (q, k, v, g)) + (
        jnp.where(past[..., 0], jnp.nan, beta),)
    return {"lengths": lengths, "s0": s0,
            "none": run(*ops), "whole": run(
                *ops, n=jnp.full(len(ROWS), LENGTH, jnp.int32)),
            "told": run(*ops, n=lengths),
            "planted": run(*planted, s0, n=lengths),
            # what a row's state is after its first `c` chunks
            "after": {c: run(*(a[:, :c * CHUNK] for a in ops[:5]), s0)[1]
                      for c in range(1, LENGTH // CHUNK + 1)}}


@pytest.mark.parametrize("row", range(len(ROWS)), ids=[
    f"{n}_positions" for n in ROWS])
def test_the_kernel_told_its_rows_lengths(told, row):
    n = ROWS[row]
    chunks = -(-n // CHUNK)
    (o, s), (plain_o, _) = told["told"], told["none"]
    # the row's own outputs (and its last chunk's, which runs whole) are
    # the kernel's without lengths, to the bit; everything after is 0
    np.testing.assert_array_equal(o[row, :chunks * CHUNK],
                                  plain_o[row, :chunks * CHUNK])
    assert not np.asarray(o[row, chunks * CHUNK:]).any()
    # the state after the last chunk that ran; s0 for a row of none
    want = told["after"][chunks][row] if chunks else told["s0"][row]
    np.testing.assert_array_equal(s[row], want)
    # the chunks past the row's end are not read: NaNs there reach nothing
    for got, clean in zip(told["planted"], told["told"]):
        np.testing.assert_array_equal(got[row], clean[row])
    # no lengths and every row whole are one result
    for a, b in zip(told["none"], told["whole"]):
        np.testing.assert_array_equal(a[row], b[row])


def test_the_dispatcher_hands_the_lengths_on():
    """``kda`` with lengths: the kernel's result by ``flash``, ragged last
    chunk and all; by ``reference`` the recurrence with the same zeros, so
    that the two can be held to each other over every position."""
    q, k, v, g, beta, s0 = inputs(B=3, S=200)
    lengths = jnp.asarray([0, 70, 200], jnp.int32)
    o, s = kda.kda(q, k, v, g, beta, s0, 64, impl="flash", lengths=lengths)
    ref_o, _ = kda.kda(q, k, v, g, beta, s0, 64, impl="reference",
                       lengths=lengths)
    want_o, want_s = kda.reference_kda(q, k, v, g, beta, s0)
    np.testing.assert_allclose(o, ref_o, **TIGHT)
    np.testing.assert_allclose(ref_o[1, :128], want_o[1, :128], **TIGHT)
    assert not np.asarray(ref_o[0]).any() and not np.asarray(
        ref_o[1, 128:]).any()
    np.testing.assert_array_equal(ref_o[2], want_o[2])
    np.testing.assert_array_equal(s[0], s0[0])
    np.testing.assert_allclose(s[2], want_s[2], **TIGHT)
    with pytest.raises(ValueError, match=r"lengths\(2,\)"):
        kernel.kda_chunked(q[:, :128], k[:, :128], v[:, :128], g[:, :128],
                           beta[:, :128], s0, 64, False, lengths[:2])


def test_the_backward_raises_by_name():
    q, k, v, g, beta, s0 = inputs(S=64, B=1, H=2)

    def loss(v, impl):
        return jnp.sum(kda.kda(q, k, v, g, beta, s0, 64, impl=impl)[0])

    with pytest.raises(NotImplementedError, match="kda_chunk.py.*forward "
                       "only.*impl='reference'"):
        jax.grad(lambda v: loss(v, "flash"))(v)
    # the recurrence has one
    grad = jax.grad(lambda v: loss(v, "reference"))(v)
    assert grad.shape == v.shape and bool(jnp.isfinite(grad).all())
    assert float(jnp.abs(grad).max()) > 0
