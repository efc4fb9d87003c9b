"""The two-width flash forward told its rows' lengths (PR 54):
``ops/pallas/flash_attention.py::_flash_fwd_shared_rope(..., lengths)``
computes no block past a right-padded row's end. Here, interpreted, over
its three forms (plain, under a window, under a choice of keys) and square
and oblong tiles: a row's own outputs are, to the bit, those of the kernel
that knows no lengths, everything past a row's last live query block is
zeros, and nothing past a row's last live blocks is read; lowering it with
the lengths traces no more than without them, and no index map holds a
nested ``jit``; and a serving step of a model with the three latent
operators, told the lengths off its mask, is the step that was not."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, init_llama, llama_next_token
from ray_tpu.ops.pallas import flash_attention as fa

S, HEADS, OWN, ROPE, VALUE, WINDOW = 512, 2, 128, 64, 128, 130
FORMS = ("plain", "window", "selected")
# the row beside a whole one: every position its own, a short one, one
# that ends on a block's edge (of 128 and of 256), one token, none
ROWS = {"whole": S, "short": 200, "edge": 256, "one": 1, "empty": 0}


@pytest.fixture
def tiles(request, monkeypatch):
    """Tiles smaller than ``flash_tiles`` gives a length the interpreter
    can afford, so that a row has blocks to skip."""
    monkeypatch.setattr(fa, "flash_tiles", lambda *a, **kw: request.param)
    fa._shared_rope_steps.cache_clear()
    yield request.param
    fa._shared_rope_steps.cache_clear()


def operands(form):
    ks = jax.random.split(jax.random.key(7), 6)
    q, k = (jax.random.normal(key, (2, HEADS, S, OWN)) for key in ks[:2])
    q_rope = jax.random.normal(ks[2], (2, HEADS, S, ROPE))
    k_rope = jax.random.normal(ks[3], (2, S, ROPE))
    v = jax.random.normal(ks[4], (2, HEADS, S, VALUE))
    kwargs = {"scale": 0.07, "causal": True}
    if form == "window":
        kwargs["window"] = WINDOW
    if form == "selected":  # a query keeps its own key and half the others
        kwargs["keep"] = ((jax.random.uniform(ks[5], (2, S, S)) < 0.5)
                          | jnp.eye(S, dtype=bool)).astype(jnp.int8)
    return [q, q_rope, k, k_rope, v], kwargs


def past(a, axis, end, value):
    """``a`` with ``value`` from ``end`` on along ``axis``, in row 0."""
    at = [slice(None)] * a.ndim
    at[0], at[axis] = 0, slice(end, None)
    return a.at[tuple(at)].set(value)


@pytest.mark.parametrize("tiles", [(128, 128), (256, 128), (128, 256)],
                         indirect=True, ids=lambda t: "%dx%d" % t)
@pytest.mark.parametrize("row", sorted(ROWS))
@pytest.mark.parametrize("form", FORMS)
def test_a_rows_own_outputs_are_the_kernels_that_knew_no_lengths(
        form, row, tiles):
    (q, q_rope, k, k_rope, v), kwargs = operands(form)
    n = ROWS[row]
    lengths = jnp.asarray([n, S], jnp.int32)
    want = np.asarray(fa._flash_fwd_shared_rope(q, q_rope, k, k_rope, v,
                                                **kwargs))
    got = np.asarray(fa._flash_fwd_shared_rope(
        q, q_rope, k, k_rope, v, lengths=lengths, **kwargs))
    # the row's own positions, and the whole row beside it, to the bit
    np.testing.assert_array_equal(got[0, :, :n], want[0, :, :n])
    np.testing.assert_array_equal(got[1], want[1])
    # zeros past the row's last live query block
    block_q, block_k = tiles
    end_q, end_k = -(-n // block_q) * block_q, -(-n // block_k) * block_k
    assert not got[0, :, end_q:].any()
    assert np.isfinite(got).all()
    if n == S:  # no lengths is every row whole
        np.testing.assert_array_equal(got, want)
    # nothing past the row's last live blocks is read: NaNs there (a
    # choice of keys is int8, so a kept pair where none was) reach no
    # output of the row's own and leave every output finite
    nan = jnp.nan
    if "keep" in kwargs:
        kwargs["keep"] = past(past(kwargs["keep"], 1, end_q, 1), 2, end_k, 1)
    got = np.asarray(fa._flash_fwd_shared_rope(
        past(q, 2, end_q, nan), past(q_rope, 2, end_q, nan),
        past(k, 2, end_k, nan), past(k_rope, 1, end_k, nan),
        past(v, 2, end_k, nan), lengths=lengths, **kwargs))
    np.testing.assert_array_equal(got[0, :, :n], want[0, :, :n])
    np.testing.assert_array_equal(got[1], want[1])
    assert np.isfinite(got).all() and not got[0, :, end_q:].any()


def test_lengths_are_a_prefills():
    (q, q_rope, k, k_rope, v), kwargs = operands("plain")
    with pytest.raises(ValueError, match="a prefill's"):
        fa._flash_fwd_shared_rope(q[:, :, :128], q_rope[:, :, :128], k,
                                  k_rope, v, lengths=jnp.zeros(2, jnp.int32),
                                  **kwargs)
    with pytest.raises(ValueError, match="for 2 rows"):
        fa._flash_fwd_shared_rope(q, q_rope, k, k_rope, v,
                                  lengths=jnp.zeros(3, jnp.int32), **kwargs)


# ---------------------------------------------------------------- lowering
def lowered(form, told, traced):
    """Lower the kernel at one shape FOR THE TPU (Mosaic's lowering runs
    in Python and needs neither a chip nor its library); -> how many
    jaxprs were traced meanwhile (``jax.monitoring``: a ``jit``-wrapped
    function, which every ``jnp`` call on a tracer is, traces one)."""
    arrays, kwargs = operands(form)
    keep = kwargs.pop("keep", None)
    shapes = [jax.ShapeDtypeStruct(a.shape, jnp.bfloat16) for a in arrays]
    shapes.append(None if keep is None
                  else jax.ShapeDtypeStruct(keep.shape, keep.dtype))
    shapes.append(jax.ShapeDtypeStruct((2,), jnp.int32) if told else None)
    before = len(traced)
    jax.jit(lambda q, qr, k, kr, v, keep, n: fa._flash_fwd_shared_rope(
        q, qr, k, kr, v, keep=keep, lengths=n, **kwargs)
    ).trace(*shapes).lower(lowering_platforms=("tpu",))
    return len(traced) - before


@pytest.fixture
def traced(monkeypatch):
    from jax._src import monitoring

    names = []

    def listener(name, _seconds, **_):
        if name.endswith("jaxpr_trace_duration"):
            names.append(name)

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    jax.monitoring.register_event_duration_secs_listener(listener)
    yield names
    monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize("form", FORMS)
def test_lowering_with_the_lengths_traces_no_more_than_without(form, traced):
    """The CPU's stand-in for a bucket's warm start by parts (PERF.md
    section 6, PR 54): what a lowering traces is paid at every start, from
    whatever compile cache. Two lowerings each, after one that fills
    ``jnp``'s own caches: 60 (plain) and 108 (window) either way, where the
    parent's window form traced 220 (its maps' ``//``)."""
    lowered(form, True, traced), lowered(form, False, traced)
    told = lowered(form, True, traced) + lowered(form, True, traced)
    untold = lowered(form, False, traced) + lowered(form, False, traced)
    assert 0 < told <= untold
    if form == "window":
        assert told < 220


@pytest.mark.parametrize("told", [False, True], ids=["untold", "told"])
@pytest.mark.parametrize("form", FORMS)
def test_no_index_map_holds_a_nested_jit(form, told):
    """A ``jnp`` call on a tracer comes into a jaxpr as a ``jit`` (``pjit``)
    equation (the parent's window maps held two each, ``//``'s): the maps
    hold ``lax`` primitives and reads of the prefetched scalars alone."""
    arrays, kwargs = operands(form)
    lengths = jnp.asarray([200, S], jnp.int32) if told else None
    jaxpr = jax.make_jaxpr(lambda *a: fa._flash_fwd_shared_rope(
        *a, lengths=lengths, **kwargs))(*arrays)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    maps = calls[0].params["grid_mapping"].block_mappings
    assert len(maps) == (7 if form == "selected" else 6)
    names = {e.primitive.name for m in maps
             for e in m.index_map_jaxpr.jaxpr.eqns}
    assert names <= {"get", "min", "max", "select_n", "gt", "mul", "add",
                     "sub", "div"}, names


# -------------------------------------------------------- a serving step's
def dots_shaped():
    """Three layers, one of each latent operator (plain, under a window of
    65, under an indexer that keeps 40 keys), 2 heads of 32 + 16 against
    values of 32, 4 experts of which 2 a token, in float32 through the
    kernels."""
    return LlamaConfig(
        vocab_size=256, hidden=64, mlp_hidden=32, num_layers=3, num_heads=2,
        num_kv_heads=2, head_dim=32, max_seq_len=256, rms_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="flash",
        num_experts=4, experts_per_token=2, norm_topk_prob=True,
        layer_types=("latent_attention", "window_latent_attention",
                     "indexed_latent_attention"),
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, sliding_window=65,
        swa_num_heads=2, swa_q_lora_rank=16, swa_kv_lora_rank=16,
        swa_qk_nope_head_dim=32, swa_qk_rope_head_dim=16, swa_v_head_dim=32,
        index_heads=2, index_head_dim=16, index_topk=40)


@pytest.mark.parametrize("tiles", [(128, 128)], indirect=True,
                         ids=lambda t: "%dx%d" % t)
def test_a_step_told_its_rows_lengths_is_the_step_that_was_not(
        tiles, monkeypatch):
    """Rows of no token, one, a block and one, and the whole bucket, padded
    on the right as ``_step`` pads them."""
    cfg = dots_shaped()
    params = init_llama(cfg, jax.random.key(3))
    lengths = np.asarray([0, 1, 129, 256])
    live = np.arange(256)[None, :] < lengths[:, None]
    tokens = np.where(live, np.asarray(jax.random.randint(
        jax.random.key(4), live.shape, 1, cfg.vocab_size)), 0)
    last = np.maximum(lengths - 1, 0).astype(np.int32)
    handed = []
    sound = fa._flash_fwd_shared_rope

    def step(withheld):
        def kernel(*args, lengths, **kwargs):
            handed.append(lengths)
            return sound(*args, lengths=None if withheld else lengths,
                         **kwargs)

        monkeypatch.setattr(fa, "_flash_fwd_shared_rope", kernel)
        ids, hidden, load = jax.jit(lambda p, t, i, on: llama_next_token(
            p, t, i, cfg, live=on))(params, tokens, last, live)
        return np.asarray(ids), np.asarray(hidden), int(load["index_kept"].sum())

    ids, hidden, kept = step(withheld=False)
    # a kernel a layer, each handed the rows' lengths
    assert len(handed) == 3 and all(
        n is not None and n.shape == (4,) and n.dtype == jnp.int32
        for n in handed)
    want_ids, want_hidden, want_kept = step(withheld=True)
    np.testing.assert_array_equal(ids[lengths > 0], want_ids[lengths > 0])
    np.testing.assert_array_equal(hidden[live], want_hidden[live])
    assert kept == want_kept and np.isfinite(hidden).all()
    # and the lengths did something: the padding's hidden states moved
    assert not np.array_equal(hidden[~live], want_hidden[~live])
    # without a mask no length is made: every position is wanted
    del handed[:]
    monkeypatch.setattr(fa, "_flash_fwd_shared_rope",
                        lambda *a, lengths, **kw: (
                            handed.append(lengths),
                            sound(*a, lengths=lengths, **kw))[1])
    llama_next_token(params, jnp.asarray(tokens), jnp.asarray(last), cfg)
    assert handed == [None] * 3
