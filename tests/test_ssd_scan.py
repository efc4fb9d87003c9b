"""The chunked state-space scan (``ops/pallas/ssd_scan.py``), interpreted,
against the recurrence itself (``ops.ssm.reference_ssd_scan``): chunks
SHORTER than the lengths, so that the state crosses edges, lengths that are
not whole chunks, so that a ragged last chunk bites, an entering state,
right-padded rows, and the controls that say the tolerance can tell a
fault: the state dropped at the chunks' edges, ``D x`` left out.

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


def test_a_rows_padding_reaches_none_of_its_tokens():
    """Rows padded on the right, as a serving step pads them: whatever lies
    after a row's own positions, its outputs are the recurrence's over its
    own positions alone."""
    x, dt, a, b, c, d, _ = inputs(S=384)
    lengths = (300, 77)
    got, _ = ssm.ssd_scan(x, dt, a, b, c, d, chunk=128, impl="flash")
    other = [v.at[0, 300:].set(v[1, :84]).at[1, 77:].set(v[0, :307])
             for v in (x, dt, b, c)]
    moved, _ = ssm.ssd_scan(other[0], other[1], a, other[2], other[3], d,
                            chunk=128, impl="flash")
    for row, n in enumerate(lengths):
        alone, _ = ssm.reference_ssd_scan(
            *(v[row:row + 1, :n] for v in (x, dt)), a,
            *(v[row:row + 1, :n] for v in (b, c)), d)
        np.testing.assert_allclose(got[row, :n], alone[0], **TIGHT)
        np.testing.assert_array_equal(moved[row, :n], got[row, :n])
    assert float(jnp.abs(moved[0, 300:] - got[0, 300:]).max()) > 0.1


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
