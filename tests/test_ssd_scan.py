"""The chunked state-space scan (``ops/pallas/ssd_scan.py``), interpreted,
against the recurrence itself (``ops.ssm.reference_ssd_scan``): chunks
SHORTER than the lengths, so that the state crosses edges, lengths that are
not whole chunks, so that a ragged last chunk bites, an entering state,
right-padded rows, told their lengths (PR 59: no chunk past a row's end
is run, and the state is the one after the row's last position) and not,
and the controls that say the tolerance can tell a fault: the state
dropped at the chunks' edges, ``D x`` left out.

Both sides compute in float32 here and differ by the order of sums alone
(the kernel's sums run by chunk): some 1e-5 of outputs of order 10.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssm
from ray_tpu.ops.pallas import ssd_scan as kernel

TIGHT = dict(rtol=1e-4, atol=1e-4)


def inputs(B=2, S=300, H=4, P=64, N=128, dtype=jnp.float32, seed=0):
    """Step sizes and decays as a Mamba-2 layer's: ``dt`` from 0.003 to
    0.7, ``A`` from -1 to -16, so that a head's memory runs from a few
    positions to many chunks."""
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, S, H)) * 1.5 - 3)
    a = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.77))
    b = (jax.random.normal(k[3], (B, S, N)) * 0.3).astype(dtype)
    c = (jax.random.normal(k[4], (B, S, N)) * 0.3).astype(dtype)
    d = 1.0 + 0.2 * jax.random.normal(k[5], (H,))
    h0 = jax.random.normal(k[6], (B, H, P, N))
    return x, dt, a, b, c, d, h0


# lengths of whole chunks and not, one chunk and several, a single position
@pytest.mark.parametrize("S, chunk", [(300, 128), (384, 128), (512, 256),
                                      (130, 128), (1, 128), (700, 256)])
def test_the_kernel_is_the_recurrence(S, chunk):
    x, dt, a, b, c, d, h0 = inputs(S=S)
    want_y, want_h = ssm.reference_ssd_scan(x, dt, a, b, c, d, h0)
    y, h = ssm.ssd_scan(x, dt, a, b, c, d, chunk=chunk, h0=h0, impl="flash")
    assert y.shape == x.shape and y.dtype == x.dtype
    assert h.shape == h0.shape and h.dtype == jnp.float32
    np.testing.assert_allclose(y, want_y, **TIGHT)
    np.testing.assert_allclose(h, want_h, **TIGHT)


def test_a_sequence_starts_from_zeros_without_a_state():
    x, dt, a, b, c, d, _ = inputs(S=300)
    want = ssm.reference_ssd_scan(x, dt, a, b, c, d)
    got = ssm.ssd_scan(x, dt, a, b, c, d, chunk=128, impl="flash")
    zeros = ssm.ssd_scan(x, dt, a, b, c, d, chunk=128, impl="flash",
                         h0=jnp.zeros((2, 4, 64, 128)))
    for g, z, w in zip(got, zeros, want):
        np.testing.assert_allclose(g, w, **TIGHT)
        np.testing.assert_array_equal(g, z)


def test_the_state_crosses_the_chunks_edges():
    """The control: each chunk scanned from zeros, as a kernel that lost
    its state between grid steps would. The tolerance tells it by three
    orders of magnitude."""
    x, dt, a, b, c, d, _ = inputs(S=384)
    want, _ = ssm.reference_ssd_scan(x, dt, a, b, c, d)
    cut = jnp.concatenate([
        ssm.ssd_scan(*(v[:, s:s + 128] for v in (x, dt)), a,
                     *(v[:, s:s + 128] for v in (b, c)), d, chunk=128,
                     impl="flash")[0] for s in (0, 128, 256)], axis=1)
    np.testing.assert_allclose(cut[:, :128], want[:, :128], **TIGHT)
    assert float(jnp.abs(cut[:, 128:] - want[:, 128:]).max()) > 1e3 * 1e-4


def test_the_skip_is_in_it():
    """The control: ``D x`` left out moves every output by ``x``'s size."""
    x, dt, a, b, c, d, h0 = inputs()
    want, _ = ssm.reference_ssd_scan(x, dt, a, b, c, d, h0)
    without, _ = ssm.ssd_scan(x, dt, a, b, c, 0 * d, chunk=128, h0=h0,
                              impl="flash")
    assert float(jnp.abs(without - want).max()) > 1e3 * 1e-4
    np.testing.assert_allclose(without + d[:, None] * x, want, **TIGHT)


# rows of no position, one, a chunk and one, 300 and the whole length
LENGTHS = (0, 1, 129, 300, 384)


def padded_rows():
    """Five rows of 384 with ``LENGTHS`` positions of their own, and the
    same rows with other numbers where their padding lies."""
    x, dt, a, b, c, d, h0 = inputs(B=5, S=384)
    own = jnp.arange(384)[None, :] < jnp.asarray(LENGTHS)[:, None]
    other = [jnp.where(own.reshape(own.shape + (1,) * (v.ndim - 2)), v,
                       jnp.roll(v, 1, axis=0)[:, ::-1])
             for v in (x, dt, b, c)]
    return (x, dt, b, c), other, (a, d, h0)


@pytest.mark.parametrize("told", [False, True], ids=["not_told", "told"])
def test_a_rows_padding_reaches_none_of_its_tokens(told):
    """Rows padded on the right, as a serving step pads them: whatever lies
    after a row's own positions, its outputs are the recurrence's over its
    own positions alone. Told the rows' lengths, the state handed back is
    the recurrence's over those positions too, whatever the padding holds,
    and a chunk past a row's end is zeros."""
    rows, other, (a, d, h0) = padded_rows()
    lengths = jnp.asarray(LENGTHS, jnp.int32) if told else None

    def scan(x, dt, b, c):
        return ssm.ssd_scan(x, dt, a, b, c, d, chunk=128, h0=h0,
                            impl="flash", lengths=lengths)

    got, h = scan(*rows)
    moved, h_moved = scan(*other)
    for row, n in enumerate(LENGTHS):
        x, dt, b, c = (v[row:row + 1, :n] for v in rows)
        alone, h_alone = ssm.reference_ssd_scan(x, dt, a, b, c, d,
                                                h0[row:row + 1])
        np.testing.assert_allclose(got[row, :n], alone[0], **TIGHT)
        np.testing.assert_array_equal(moved[row, :n], got[row, :n])
        if told:
            np.testing.assert_allclose(h[row], h_alone[0], **TIGHT)
            np.testing.assert_array_equal(h_moved[row], h[row])
            assert not np.asarray(got[row, -(-n // 128) * 128:]).any()
    if told:  # inside the chunk that holds a row's end the padding is run
        assert float(jnp.abs(got[3, 300:]).max()) > 0.1
    else:
        assert float(jnp.abs(moved[3, 300:] - got[3, 300:]).max()) > 0.1
        assert float(jnp.abs(h_moved[3] - h[3]).max()) > 0.1


@pytest.mark.parametrize("impl", ["flash", "reference"])
def test_a_row_of_no_position_hands_its_state_back(impl):
    """To the bit, by either impl: no position of it takes a step."""
    rows, _, (a, d, h0) = padded_rows()
    x, dt, b, c = rows
    y, h = ssm.ssd_scan(x, dt, a, b, c, d, chunk=128, h0=h0, impl=impl,
                        lengths=jnp.asarray(LENGTHS, jnp.int32))
    np.testing.assert_array_equal(h[0], h0[0])
    assert not np.asarray(y[0]).any()
    assert float(jnp.abs(h[1] - h0[1]).max()) > 1e-3      # one position does


def test_told_the_lengths_the_two_impls_agree_at_every_position():
    """The padding inside the chunk that holds a row's end reads the
    standing state, the chunks past it are zeros, and the state is the one
    after the row's last position: the recurrence's ``y`` and state under
    the same lengths."""
    rows, _, (a, d, h0) = padded_rows()
    x, dt, b, c = rows
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    want = ssm.ssd_scan(x, dt, a, b, c, d, chunk=128, h0=h0,
                        impl="reference", lengths=lengths)
    got = ssm.ssd_scan(x, dt, a, b, c, d, chunk=128, h0=h0, impl="flash",
                       lengths=lengths)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, **TIGHT)


def test_whole_rows_told_are_the_rows_not_told():
    """To the bit: a batch with no padding pays the test a grid step and
    nothing else."""
    x, dt, a, b, c, d, h0 = inputs(S=384)
    not_told = kernel.ssd_scan_chunked(x, dt, a, b, c, d, h0, 128)
    told = kernel.ssd_scan_chunked(x, dt, a, b, c, d, h0, 128,
                                   jnp.full((2,), 384, jnp.int32))
    for t, n in zip(told, not_told):
        np.testing.assert_array_equal(t, n)


def test_the_kernel_takes_eight_positional_arguments_and_the_lengths():
    """The benchmark's scan check calls it with eight; the lengths trail
    them and default to none, which is the call that knew of none: one
    operand fewer, no scalar prefetched."""
    import inspect

    params = list(inspect.signature(kernel.ssd_scan_chunked).parameters
                  .values())
    assert [p.name for p in params] == ["x", "dt", "a", "b", "c", "d", "h0",
                                        "chunk", "lengths"]
    assert params[8].default is None
    x, dt, a, b, c, d, h0 = inputs(S=128, B=1)

    def call(*more):
        jaxpr = jax.make_jaxpr(lambda *v: kernel.ssd_scan_chunked(
            *v, 128, *more))(x, dt, a, b, c, d, h0)
        (eqn,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        return eqn

    plain, told = call(), call(jnp.ones((1,), jnp.int32))
    assert len(plain.invars) == 8 and len(told.invars) == 9
    assert plain.params["grid_mapping"].num_index_operands == 0
    assert told.params["grid_mapping"].num_index_operands == 1
    with pytest.raises(ValueError, match=r"lengths\(2,\)"):
        kernel.ssd_scan_chunked(x, dt, a, b, c, d, h0, 128,
                                jnp.ones((2,), jnp.int32))


def test_bf16_operands_keep_a_float32_state():
    """The serving path's types: bf16 in and out, the decays, their sums
    and the state float32; against the recurrence in float32 over the same
    bf16 numbers the kernel lies a bf16 rounding of its outputs away."""
    x, dt, a, b, c, d, h0 = inputs(S=640, dtype=jnp.bfloat16)
    want_y, want_h = ssm.reference_ssd_scan(
        x.astype(jnp.float32), dt, a, b.astype(jnp.float32),
        c.astype(jnp.float32), d, h0)
    y, h = ssm.ssd_scan(x, dt, a, b, c, d, chunk=256, h0=h0, impl="flash")
    assert y.dtype == jnp.bfloat16 and h.dtype == jnp.float32
    scale = float(jnp.abs(want_y).max())
    assert float(jnp.abs(y.astype(jnp.float32) - want_y).max()) < 0.02 * scale
    assert float(jnp.abs(h - want_h).max()) < 0.02 * float(
        jnp.abs(want_h).max())


def test_the_kernels_backward_raises_by_name_and_the_reference_has_one():
    x, dt, a, b, c, d, _ = inputs(S=128, B=1)

    def total(x, impl):
        return ssm.ssd_scan(x, dt, a, b, c, d, chunk=128,
                            impl=impl)[0].sum()

    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(total)(x, "flash")
    g = jax.grad(total)(x, "reference")
    assert g.shape == x.shape and float(jnp.abs(g).max()) > 0
    # told the rows' lengths the kernel is called bare (a serving step's
    # call: `ops/ssm.py`), and jax raises for it
    with pytest.raises(NotImplementedError):
        jax.grad(lambda x: ssm.ssd_scan(
            x, dt, a, b, c, d, chunk=128, impl="flash",
            lengths=jnp.asarray([100], jnp.int32))[0].sum())(x)


def test_the_dispatcher():
    x, dt, a, b, c, d, h0 = inputs(S=40)
    auto = ssm.ssd_scan(x, dt, a, b, c, d, h0=h0)       # the CPU: reference
    plain = ssm.reference_ssd_scan(x, dt, a, b, c, d, h0)
    for got, want in zip(auto, plain):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError, match="unknown ssd_scan impl 'chunked'"):
        ssm.ssd_scan(x, dt, a, b, c, d, impl="chunked")
    with pytest.raises(ValueError, match="not whole chunks"):
        kernel.ssd_scan_chunked(x, dt, a, b, c, d, h0, 128)
    with pytest.raises(ValueError, match="whole lane tiles"):
        kernel.ssd_scan_chunked(x, dt, a, b, c, d, h0, 40)


def test_how_many_heads_a_grid_step_takes():
    assert kernel.ssd_heads_a_step(64, 64) == 8          # granite-4.0-h
    assert kernel.ssd_heads_a_step(4, 32) == 4           # the rehearsal's
    assert kernel.ssd_heads_a_step(4, 64) == 4
    assert kernel.ssd_heads_a_step(3, 40) == 3           # the whole array
    assert kernel.ssd_heads_a_step(24, 128) == 8
    assert kernel.ssd_heads_a_step(16, 8) == 16          # 8 x 8: half a tile


def test_the_kernel_wears_its_own_name():
    """The trace tells the scan from the flash forward by this scope,
    innermost round the Pallas call."""
    x, dt, a, b, c, d, h0 = inputs(S=128, B=1)
    jaxpr = jax.make_jaxpr(lambda *v: kernel.ssd_scan_chunked(*v, 128))(
        x, dt, a, b, c, d, h0)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert str(calls[0].source_info.name_stack) == \
        kernel.SSD_SCAN_TRACE_NAME == "ssd_scan"
    # the running sums and the transposes round it are not under it
    assert all(kernel.SSD_SCAN_TRACE_NAME not in str(e.source_info.name_stack)
               for e in jaxpr.eqns if e.primitive.name != "pallas_call")
