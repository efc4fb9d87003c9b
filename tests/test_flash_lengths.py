"""The flash forwards told their rows' lengths (the two-width one in PR 54,
the equal-width one in PR 56):
``ops/pallas/flash_attention.py::_flash_fwd_shared_rope(..., lengths)`` and
``_flash_fwd(..., lengths)`` compute no block past a right-padded row's end.
Here, interpreted, over the two-width forward's three forms (plain, under a
window, under a choice of keys) and the equal-width forward's two (``full``
and ``sliding``, four query heads on two key heads), at square and oblong
tiles: a row's own outputs are, to the bit, those of the kernel that knows
no lengths, everything past a row's last live query block is zeros, and
nothing past a row's last live blocks is read; lowering it with the lengths
traces no more than without them, and no index map holds a nested ``jit``;
without the lengths the equal-width forward is its parent's program, and
with them it has no gradient; and a serving step of a model with the three
latent operators, of a dense one and of one with ``sliding`` layers and
experts, told the lengths off its mask, is the step that was not."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, init_llama, llama_next_token
from ray_tpu.ops.pallas import flash_attention as fa

S, HEADS, OWN, ROPE, VALUE, WINDOW = 512, 2, 128, 64, 128, 130
EQUAL = ("full", "sliding")      # `_flash_fwd`'s: no window, and under one
FORMS = ("plain", "window", "selected") + EQUAL
# the row beside a whole one: every position its own, a short one, one
# that ends on a block's edge (of 128 and of 256), one token, none
ROWS = {"whole": S, "short": 200, "edge": 256, "one": 1, "empty": 0}


@pytest.fixture
def tiles(request, monkeypatch):
    """Tiles smaller than ``flash_tiles`` gives a length the interpreter
    can afford, so that a row has blocks to skip."""
    monkeypatch.setattr(fa, "flash_tiles", lambda *a, **kw: request.param)
    # under the window: the walk's key blocks at these tiles (the window's
    # one step a query block, PR 61, is `step`'s tests)
    monkeypatch.setattr(fa, "window_step", lambda *a, **kw: None)
    return request.param


@pytest.fixture
def step(monkeypatch):
    """The window's one step a query block (``window_step``) at a block
    the interpreter can afford: two query blocks of 256, each over its own
    keys and the 256 before them."""
    monkeypatch.setattr(fa, "window_step", lambda *a, **kw: (256, 256))
    return 256, 256


def operands(form):
    """``(arrays, the axis of each that its positions lie on, kwargs)``."""
    ks = jax.random.split(jax.random.key(7), 6)
    if form in EQUAL:  # a group of two query heads a key head
        q = jax.random.normal(ks[0], (2, 2 * HEADS, S, OWN))
        k, v = (jax.random.normal(key, (2, HEADS, S, OWN)) for key in ks[1:3])
        return [q, k, v], (2, 2, 2), {
            "causal": True, **({"window": WINDOW} if form == "sliding"
                               else {})}
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
    return [q, q_rope, k, k_rope, v], (2, 2, 2, 1, 2), kwargs


def forward(form, arrays, **kwargs):
    """The form's kernel -> its outputs: ``o``, and beside it the
    equal-width forward's ``lse`` where it makes one (told no lengths)."""
    if form in EQUAL:
        return tuple(x for x in fa._flash_fwd(*arrays, **kwargs)
                     if x is not None)
    return (fa._flash_fwd_shared_rope(*arrays, **kwargs),)


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
    rows_own_outputs_are_the_untold_kernels(form, row, tiles)


# the window's one step keeps them too; the keys it reads end with the
# query blocks (a block's own and the tail before it)
@pytest.mark.parametrize("row", sorted(ROWS))
def test_the_windows_one_step_keeps_a_rows_own_outputs(row, step):
    call, = [e for e in jax.make_jaxpr(lambda *a: fa._flash_fwd(
        *a, causal=True, window=WINDOW))(*operands("sliding")[0]).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (2, 2 * HEADS, S // 256)
    rows_own_outputs_are_the_untold_kernels("sliding", row, step)


def rows_own_outputs_are_the_untold_kernels(form, row, tiles):
    arrays, axes, kwargs = operands(form)
    n = ROWS[row]
    lengths = jnp.asarray([n, S], jnp.int32)
    want = np.asarray(forward(form, arrays, **kwargs)[0])
    got, *lse = map(np.asarray, forward(form, arrays, lengths=lengths,
                                        **kwargs))
    # the row's own positions, and the whole row beside it, to the bit
    np.testing.assert_array_equal(got[0, :, :n], want[0, :, :n])
    np.testing.assert_array_equal(got[1], want[1])
    # zeros past the row's last live query block
    block_q, block_k = tiles
    end_q, end_k = -(-n // block_q) * block_q, -(-n // block_k) * block_k
    assert not got[0, :, end_q:].any()
    assert np.isfinite(got).all() and all(np.isfinite(x).all() for x in lse)
    if n == S:  # no lengths is every row whole
        np.testing.assert_array_equal(got, want)
    # nothing past the row's last live blocks is read: NaNs there (a
    # choice of keys is int8, so a kept pair where none was) reach no
    # output of the row's own and leave every output finite
    if "keep" in kwargs:
        kwargs["keep"] = past(past(kwargs["keep"], 1, end_q, 1), 2, end_k, 1)
    # the queries (and their rotary part) end with the query blocks, the
    # keys and values with the key blocks
    ends = (end_q, end_k, end_k) if form in EQUAL else (
        end_q, end_q, end_k, end_k, end_k)
    got, *lse = map(np.asarray, forward(
        form, [past(a, axis, end, jnp.nan)
               for a, axis, end in zip(arrays, axes, ends)],
        lengths=lengths, **kwargs))
    np.testing.assert_array_equal(got[0, :, :n], want[0, :, :n])
    np.testing.assert_array_equal(got[1], want[1])
    assert np.isfinite(got).all() and not got[0, :, end_q:].any()
    assert all(np.isfinite(x).all() for x in lse)


@pytest.mark.parametrize("form", ["plain", "full"])
def test_lengths_are_a_prefills(form):
    arrays, _, kwargs = operands(form)
    queries = 1 if form in EQUAL else 2           # q, and its rotary part
    short = [a[:, :, :128] if i < queries else a
             for i, a in enumerate(arrays)]
    with pytest.raises(ValueError, match="a prefill's"):
        forward(form, short, lengths=jnp.zeros(2, jnp.int32), **kwargs)
    with pytest.raises(ValueError, match="for 2 rows"):
        forward(form, arrays, lengths=jnp.zeros(3, jnp.int32), **kwargs)
    if form in EQUAL:
        with pytest.raises(ValueError, match="a prefill's"):
            forward(form, arrays, lengths=jnp.zeros(2, jnp.int32),
                    causal=False)


# ---------------------------------------------------------------- lowering
def lowered(form, told, traced):
    """Lower the kernel at one shape FOR THE TPU (Mosaic's lowering runs
    in Python and needs neither a chip nor its library); -> how many
    jaxprs were traced meanwhile (``jax.monitoring``: a ``jit``-wrapped
    function, which every ``jnp`` call on a tracer is, traces one)."""
    arrays, _, kwargs = operands(form)
    keep = kwargs.pop("keep", None)
    shapes = [[jax.ShapeDtypeStruct(a.shape, jnp.bfloat16) for a in arrays]]
    shapes.append(None if keep is None
                  else jax.ShapeDtypeStruct(keep.shape, keep.dtype))
    shapes.append(jax.ShapeDtypeStruct((2,), jnp.int32) if told else None)
    fewer = lambda keep: {} if keep is None else {"keep": keep}  # noqa: E731
    before = len(traced)
    jax.jit(lambda arrays, keep, n: forward(
        form, arrays, lengths=n, **fewer(keep), **kwargs)
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


def test_lowering_the_windows_one_step_told_traces_no_more(traced, step):
    lowered("sliding", True, traced), lowered("sliding", False, traced)
    told = lowered("sliding", True, traced) + lowered("sliding", True, traced)
    untold = (lowered("sliding", False, traced)
              + lowered("sliding", False, traced))
    assert 0 < told <= untold


def block_maps(form, told):
    """The kernel's one ``pallas_call`` equation -> its block mappings."""
    arrays, _, kwargs = operands(form)
    lengths = jnp.asarray([200, S], jnp.int32) if told else None
    jaxpr = jax.make_jaxpr(lambda *a: forward(
        form, a, lengths=lengths, **kwargs))(*arrays)
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return calls[0].params["grid_mapping"].block_mappings


@pytest.mark.parametrize("told", [False, True], ids=["untold", "told"])
@pytest.mark.parametrize("form", FORMS)
def test_no_index_map_holds_a_nested_jit(form, told):
    """A ``jnp`` call on a tracer comes into a jaxpr as a ``jit`` (``pjit``)
    equation (the parent's window maps held two each, ``//``'s): the maps
    hold ``lax`` primitives and reads of the prefetched scalars alone. The
    equal-width forward that is NOT told keeps the one its key head's ``//``
    is, for its text's sake (training's program is the parent's to the
    character); told, it holds none."""
    maps = block_maps(form, told)
    # told, the equal-width forward has `o` alone to write
    assert len(maps) == {"selected": 7}.get(
        form, 5 - told if form in EQUAL else 6)
    names = {e.primitive.name for m in maps
             for e in m.index_map_jaxpr.jaxpr.eqns}
    allowed = {"get", "min", "max", "select_n", "gt", "mul", "add", "sub",
               "div"}
    if form in EQUAL and not told:
        assert names - allowed <= {"jit", "pjit"}
    else:
        assert names <= allowed, names


@pytest.mark.parametrize("told", [False, True], ids=["untold", "told"])
def test_the_windows_one_step_maps_hold_lax_alone(told, step):
    """Five operands (the queries, and the keys and the values twice: the
    tail's blocks and the query blocks' own) and ``o``; told or not, no map
    holds a nested ``jit``."""
    maps = block_maps("sliding", told)
    assert len(maps) == 6
    names = {e.primitive.name for m in maps
             for e in m.index_map_jaxpr.jaxpr.eqns}
    assert names <= {"get", "min", "max", "mul", "sub", "div"}, names


def named_blocks(mapping, blocks, grid):
    """Every block index an index map names over ``grid``, the prefetched
    ``blocks`` (``_live_blocks``) read as values (the map's jaxpr with its
    state discharged) -> ``{(b, h, iq, ik): index}`` (no ``ik`` on the
    window's one step's grid of three)."""
    import itertools

    from jax._src.state import discharge

    jaxpr, consts = discharge.discharge_state(
        mapping.index_map_jaxpr.jaxpr, mapping.index_map_jaxpr.consts)
    told = (blocks,) * (len(jaxpr.invars) - len(grid))  # none if not told
    return {step: tuple(int(i) for i in jax.core.eval_jaxpr(
        jaxpr, consts, *step, *told)[:4])
        for step in itertools.product(*map(range, grid))}


@pytest.mark.parametrize("tiles", [(128, 128)], indirect=True,
                         ids=lambda t: "%dx%d" % t)
@pytest.mark.parametrize("form", EQUAL)
def test_a_dead_step_names_blocks_that_are_there_already(form, tiles):
    """What the maps name with a row empty, one short and one whole (a
    copy is made when a step names another block than the step before
    it): a live step names what the call that is not told names; a step
    past a short row's end holds its head's last live blocks; an empty
    row's steps hold ONE block of each operand, whatever the head."""
    arrays, _, kwargs = operands(form)
    arrays = [jnp.concatenate([a, a[:1]]) for a in arrays]     # three rows
    lengths = jnp.asarray([0, 200, S], jnp.int32)
    call, = [e for e in jax.make_jaxpr(lambda *a: fa._flash_fwd(
        *a, lengths=lengths, **kwargs))(*arrays).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    untold, = [e for e in jax.make_jaxpr(lambda *a: fa._flash_fwd(
        *a, **kwargs))(*arrays).jaxpr.eqns if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert mapping.grid == untold.params["grid_mapping"].grid
    blocks = np.asarray(fa._live_blocks(lengths, *tiles))
    for told, plain in zip(mapping.block_mappings[:3],
                           untold.params["grid_mapping"].block_mappings):
        got = named_blocks(told, blocks, mapping.grid)
        want = named_blocks(plain, blocks, mapping.grid)
        assert len({i for (b, *_), i in got.items() if b == 0}) == 1
        for (b, h, iq, ik), index in got.items():
            if b == 2:
                assert index == want[b, h, iq, ik]
            if b == 1:  # 200 positions: two live blocks of 128
                assert index[:2] == want[b, h, iq, ik][:2]
                assert index[2] <= 1
                if iq > 1:
                    assert index == got[b, h, 1, mapping.grid[3] - 1]
    # `o` is written where it belongs, every block of it
    out = named_blocks(mapping.block_mappings[3], blocks, mapping.grid)
    assert all(index == (b, h, iq, 0) for (b, h, iq, _), index in out.items())


def test_the_windows_one_step_names_blocks_that_are_there_already(step):
    """The same three rows through the window's one step (a grid of (row,
    head, query block)): a live step names what the call that is not told
    names, the tail the block of keys that ends where the query block
    begins (the row's first its own first keys, masked); a step past a
    short row's end holds its head's last live blocks; an empty row's
    steps hold ONE block of each operand, whatever the head."""
    arrays, _, kwargs = operands("sliding")
    arrays = [jnp.concatenate([a, a[:1]]) for a in arrays]     # three rows
    lengths = jnp.asarray([0, 200, S], jnp.int32)
    call, = [e for e in jax.make_jaxpr(lambda *a: fa._flash_fwd(
        *a, lengths=lengths, **kwargs))(*arrays).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    untold, = [e for e in jax.make_jaxpr(lambda *a: fa._flash_fwd(
        *a, **kwargs))(*arrays).jaxpr.eqns if e.primitive.name == "pallas_call"]
    mapping = call.params["grid_mapping"]
    assert mapping.grid == untold.params["grid_mapping"].grid == (
        3, 2 * HEADS, S // 256)
    blocks = np.asarray(fa._live_blocks(lengths, *step))

    def named(m):
        return named_blocks(m, blocks, mapping.grid)

    for operand, (told, plain) in enumerate(zip(
            mapping.block_mappings[:5],
            untold.params["grid_mapping"].block_mappings)):
        got, want = named(told), named(plain)
        assert len({i for (b, *_), i in got.items() if b == 0}) == 1
        for (b, h, iq), index in got.items():
            if operand in (1, 2):   # the tail: the block before, or 0
                assert want[b, h, iq][2] == max(iq - 1, 0)
            if b == 2:
                assert index == want[b, h, iq]
            if b == 1:  # 200 positions: one live block of 256
                assert index == want[b, h, 0]
    out = named(mapping.block_mappings[5])
    assert all(index == (b, h, iq, 0) for (b, h, iq), index in out.items())


# what `_flash_fwd` without lengths traced to at the parent (`3fc52f6`), at
# `operands`' shapes with the rule's own tiles (one block of 512): the
# sha256 of the jaxpr's text with addresses blanked
PARENTS = {"full": "b45b906a0122e09e", "sliding": "f775d532687f5662"}


@pytest.mark.parametrize("form", EQUAL)
def test_without_the_lengths_the_call_is_the_parents(form):
    import hashlib
    import re

    arrays, _, kwargs = operands(form)
    text = lambda **kw: re.sub(r"0x[0-9a-f]+", "0x", str(  # noqa: E731
        jax.make_jaxpr(lambda *a: fa._flash_fwd(*a, **kwargs, **kw))(
            *arrays)))
    assert text() == text(lengths=None)
    assert hashlib.sha256(text().encode()).hexdigest()[:16] == PARENTS[form]
    # no operand, no test, no `min`: three operands, nothing prefetched,
    # where the call that is told has the rows' live blocks in front
    def call(**kw):
        eqn, = [e for e in jax.make_jaxpr(lambda *a: fa._flash_fwd(
            *a, **kwargs, **kw))(*arrays).jaxpr.eqns
            if e.primitive.name == "pallas_call"]
        return len(eqn.invars), eqn.params["grid_mapping"].num_index_operands

    assert call() == (3, 0)
    assert call(lengths=jnp.asarray([200, S], jnp.int32)) == (4, 1)
    # the logsumexp is the backward's: told the lengths, none is made
    o, lse = fa._flash_fwd(*arrays, **kwargs)
    assert lse.shape == o.shape[:3] + (128,)
    assert fa._flash_fwd(*arrays, lengths=jnp.asarray([200, S], jnp.int32),
                         **kwargs)[1] is None


@pytest.mark.parametrize("form", EQUAL)
def test_a_gradient_through_a_call_with_lengths_raises(form):
    q, k, v = (jnp.swapaxes(a, 1, 2) for a in operands(form)[0])
    lengths = jnp.asarray([200, S], jnp.int32)
    function, told = ((fa.flash_attention, True) if form == "full"
                      else (fa.flash_attention_window, WINDOW))
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: function(q, k, v, told, lengths).sum())(q)
    if form == "full":  # without them it has the one it had
        assert jax.grad(lambda q: function(q, k, v, told).sum())(q).shape \
            == q.shape


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


def dense_shaped():
    """Two layers of grouped-query attention, 4 heads on 2 of 32, and the
    dense feed-forward."""
    return LlamaConfig(
        vocab_size=256, hidden=64, mlp_hidden=96, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim=32, max_seq_len=256, rms_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32, attn_impl="flash")


def mellum_shaped():
    """A period of Mellum2's pattern cut to three layers: two under a
    window of 65 and a full one, 4 experts of which 2 a token."""
    return dataclasses.replace(
        dense_shaped(), mlp_hidden=32, num_layers=3, num_experts=4,
        experts_per_token=2, norm_topk_prob=True, sliding_window=65,
        layer_types=("sliding_attention", "sliding_attention",
                     "full_attention"))


# a model -> (its configuration, the kernel's wrapper its layers call)
STEPS = {"latent": (dots_shaped, "_flash_fwd_shared_rope"),
         "dense": (dense_shaped, "_flash_fwd"),
         "sliding": (mellum_shaped, "_flash_fwd")}


@pytest.mark.parametrize("tiles", [(128, 128)], indirect=True,
                         ids=lambda t: "%dx%d" % t)
@pytest.mark.parametrize("model", sorted(STEPS))
def test_a_step_told_its_rows_lengths_is_the_step_that_was_not(
        model, tiles, monkeypatch):
    """Rows of no token, one, a block and one, and the whole bucket, padded
    on the right as ``_step`` pads them."""
    make, wrapper = STEPS[model]
    cfg = make()
    params = init_llama(cfg, jax.random.key(3))
    lengths = np.asarray([0, 1, 129, 256])
    live = np.arange(256)[None, :] < lengths[:, None]
    tokens = np.where(live, np.asarray(jax.random.randint(
        jax.random.key(4), live.shape, 1, cfg.vocab_size)), 0)
    last = np.maximum(lengths - 1, 0).astype(np.int32)
    handed = []
    sound = getattr(fa, wrapper)

    def step(withheld):
        def kernel(*args, lengths, **kwargs):
            handed.append(lengths)
            return sound(*args, lengths=None if withheld else lengths,
                         **kwargs)

        monkeypatch.setattr(fa, wrapper, kernel)
        ids, hidden, load = jax.jit(lambda p, t, i, on: llama_next_token(
            p, t, i, cfg, live=on))(params, tokens, last, live)
        kept = int(load["index_kept"].sum()) if model == "latent" else 0
        return np.asarray(ids), np.asarray(hidden), kept

    ids, hidden, kept = step(withheld=False)
    # a kernel a run of like layers, each handed the rows' lengths
    assert len(handed) == {"dense": 1, "sliding": 2}.get(model, 3) and all(
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
    monkeypatch.setattr(fa, wrapper,
                        lambda *a, lengths, **kw: (
                            handed.append(lengths),
                            sound(*a, lengths=lengths, **kw))[1])
    llama_next_token(params, jnp.asarray(tokens), jnp.asarray(last), cfg)
    assert handed and handed == [None] * len(handed)
