"""The fused KDA decode update (``ops/pallas/kda_step.py``) against the
XLA form it replaces on the TPU (``ops/delta_rule.py::kda_step``), in
interpret mode on the CPU: every head's matrix, both read-outs, dead
slots bit for bit; which of the two a shape and a platform take
(``delta_rule.kda_update``), what the cache says of it, and what
importing the serving plane costs a process that traces no such step.

Nothing here times anything: ``tests/test_tpu_compile.py`` compiles the
cell's decode program for a described v5e, the chip measures it.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from kungfu_tpu.models import solar_open2 as arch
from kungfu_tpu.ops import delta_rule
from kungfu_tpu.ops.pallas import kda_step as ks
from kungfu_tpu.serve.recurrent import HybridCaches

F32 = jnp.float32
#: a shape that tiles: four slots, 16 heads of 128 x 128
B, H, K, V = 4, 16, 128, 128

#: name -> the slots of six a step is live for
LIVE = {"all": (1, 1, 1, 1, 1, 1), "none": (0, 0, 0, 0, 0, 0),
        "mixed": (1, 0, 0, 1, 0, 0), "first_alone": (1, 0, 0, 0, 0, 0),
        "last_alone": (0, 0, 0, 0, 0, 1),
        "dead_before_the_first_live": (0, 0, 1, 1, 0, 1),
        "a_dead_one_between_two_live": (0, 1, 0, 1, 1, 0),
        "a_random_half": tuple(int(x) for x in np.random.default_rng(
            46).permutation([1, 1, 1, 0, 0, 0]))}
SLOTS = 6


def draw(seed, b=B, h=H, k=K, v=V):
    """(state ``[1, b, h, k, v]``, q, k, v, g, beta) as a KDA layer hands
    them over: unit keys, queries over ``sqrt(k)``, decays' logarithms
    down to -3 (a channel that forgets 95 % a token), steps in (0, 2)."""
    r = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (jax.random.normal(r[0], (1, b, h, k, v), F32),
            unit(jax.random.normal(r[1], (b, h, k), F32)) / np.sqrt(k),
            unit(jax.random.normal(r[2], (b, h, k), F32)),
            jax.random.normal(r[3], (b, h, v), F32),
            -3.0 * jax.random.uniform(r[4], (b, h, k), F32),
            2.0 * jax.nn.sigmoid(jax.random.normal(r[5], (b, h), F32)))


def bits(x):
    return np.asarray(x).view(np.uint32)


def interpreted(*args, **kw):
    """``kda_step`` in the TPU interpreter, waited for.  The interpreter's
    callbacks run JAX operations of their own, and the CPU's dispatch is
    asynchronous: a test that dispatched its next operation while they
    ran has waited for them, and they for it, until the run was cut."""
    return jax.block_until_ready(ks.kda_step(*args, interpret=True, **kw))


@pytest.fixture
def on_tpu(monkeypatch):
    """What the code can see says TPU, and every Pallas kernel runs in
    the interpreter: ``kda_update`` takes its kernel branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture(scope="module")
def whole_walk():
    """heads -> (state, the vectors, the new state and ``o``) of the
    kernel with every slot live: what a live slot's share of any other
    step has to equal bit for bit."""
    state, *x = draw(37, SLOTS)
    return {heads: (state, x) + tuple(interpreted(
        state, *x, jnp.ones(SLOTS, bool), heads=heads))
        for heads in (8, 16)}


@pytest.mark.parametrize("heads", [8, 16])
@pytest.mark.parametrize("name", list(LIVE))
def test_kernel_equals_xlas_kda_step(name, heads, whole_walk):
    """One load of a head's matrix gives both read-outs and the update:
    a live slot's new state and ``o`` are ``kda_step``'s to float32's
    rounding and the whole walk's to the bit, whatever else is live and
    at either head block; a slot that is not live has the very bits it
    had, and zeros for ``o``."""
    state, x, all_new, all_o = whole_walk[heads]
    live = jnp.asarray(LIVE[name], bool)
    new, o = interpreted(state, *x, live, heads=heads)
    want, want_o = delta_rule.kda_step(state[0], *x, live)
    assert new.shape == state.shape and new.dtype == F32
    assert o.shape == (SLOTS, H, V) and o.dtype == F32
    np.testing.assert_allclose(new[0], want, rtol=1e-5, atol=1e-6)
    alive, dead = (np.flatnonzero(np.asarray(live) == x) for x in (1, 0))
    np.testing.assert_allclose(np.asarray(o)[alive],
                               np.asarray(want_o)[alive], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_array_equal(bits(new)[0, alive], bits(all_new)[0, alive])
    np.testing.assert_array_equal(bits(o)[alive], bits(all_o)[alive])
    np.testing.assert_array_equal(bits(new)[0, dead], bits(state)[0, dead])
    assert not np.asarray(o)[dead].any()
    for slot in alive:                              # ... a live one moved
        assert np.abs(np.asarray(new - state)[0, slot]).max() > 0.1


@pytest.mark.parametrize("heads", [8, 16])
@pytest.mark.parametrize("name", list(LIVE))
def test_a_dead_slots_state_is_neither_read_nor_written(name, heads,
                                                        whole_walk):
    """NaNs planted all through every dead slot's state reach no ``o``,
    and those states come out bit-equal, NaNs and all; the live slots'
    are the whole walk's to the bit.  The interpreter keeps one buffer a
    block, NaNs where nothing has written, copies a block in when the
    block named changes and writes one back when the next step names
    another: a dead step that named another block than the step before
    would write that step's bytes over a state (a); dead slots before
    the first live one that named a block nobody fills would write NaNs
    (b); and so would a step for nothing at all (c: ``none``)."""
    state, x, all_new, all_o = whole_walk[heads]
    live = np.asarray(LIVE[name], bool)
    state = jnp.where(live[None, :, None, None, None], state, jnp.nan)
    new, o = interpreted(state, *x, jnp.asarray(live), heads=heads)
    assert not np.isnan(np.asarray(o)).any()
    assert not np.asarray(o)[~live].any()
    np.testing.assert_array_equal(bits(new)[0, ~live], bits(state)[0, ~live])
    assert np.isnan(np.asarray(new)[0, ~live]).all()
    np.testing.assert_array_equal(bits(new)[0, live], bits(all_new)[0, live])
    np.testing.assert_array_equal(bits(o)[live], bits(all_o)[live])


@pytest.mark.parametrize("blocks", [1, 2, 4])
@pytest.mark.parametrize("name", list(LIVE))
def test_a_dead_step_names_the_block_the_step_before_held(name, blocks):
    """The plan, step by step as the pipeline reads it: a live step
    names its own block; a dead step the block of the step before it;
    the first step of all, where it is dead, the first live slot's first
    block (which is then named without a break until that slot's step
    fills it) or, with nothing live, the one block every step names."""
    live = np.asarray(LIVE[name], bool)
    at, lo, hi = (np.asarray(x) for x in ks.plan(jnp.asarray(live), blocks))
    steps = [(b, j) for b in range(SLOTS) for j in range(blocks)]
    named = [(int(at[b]), int(np.clip(j, lo[b], hi[b]))) for b, j in steps]
    for step, (b, j) in enumerate(steps):
        if live[b]:
            assert named[step] == (b, j)
        elif step:
            assert named[step] == named[step - 1]
    first = int(np.argmax(live)) if live.any() else 0
    assert named[0] == (first, 0)
    assert int(ks.slots_walked(jnp.asarray(live))) == live.sum()
    # bools and the int32 the kernel prefetches plan alike
    for a, b in zip(ks.plan(jnp.asarray(live, jnp.int32), blocks),
                    (at, lo, hi)):
        np.testing.assert_array_equal(np.asarray(a), b)


def _own_blocks(live, blocks):
    """A plan that breaks the rule above: a dead step names its own
    slot's blocks, as a live one does."""
    slots = jnp.arange(live.shape[0], dtype=jnp.int32)
    return slots, jnp.zeros_like(slots), jnp.full_like(slots, blocks - 1)


@pytest.mark.parametrize("name", ["a_dead_one_between_two_live",
                                  "dead_before_the_first_live"])
def test_the_interpreter_shows_what_a_wrong_plan_does(name):
    """The hazard is the interpreter's too: under a plan whose dead steps
    name their own blocks, a dead slot's state comes out holding what the
    buffer held -- the new state of the live slot before it (a), or what
    the first step copied through (b).  So the cases above fail where
    the plan names a wrong block."""
    state, *x = draw(43, SLOTS)
    live = jnp.asarray(LIVE[name], bool)
    new, _ = interpreted(state, *x, live, heads=8,
                         walk=_own_blocks(live, 2))
    dead = np.flatnonzero(~np.asarray(live))
    assert (bits(new)[0, dead] != bits(state)[0, dead]).any()


@pytest.mark.parametrize("shape", [(2, 8, 256, 128), (2, 8, 128, 256),
                                   (1, 32, 128, 128)],
                         ids=["keys_256", "values_256", "32_heads_a_step"])
def test_kernel_at_other_widths_and_the_widest_block(shape):
    """Keys and values of two lane tiles, and 32 heads a grid step (no
    padding rows in the transpose)."""
    b, h, k, v = shape
    state, *x = draw(38, b, h, k, v)
    live = jnp.asarray([True, False][:b])
    heads = 32 if h == 32 else None
    new, o = interpreted(state, *x, live, heads=heads)
    want, want_o = delta_rule.kda_step(state[0], *x, live)
    np.testing.assert_allclose(new[0], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o[0], want_o[0], rtol=1e-5, atol=1e-6)
    assert not np.asarray(o)[1:].any()


@pytest.mark.parametrize("heads", [8, 16])
def test_a_run_of_steps_leaves_a_dead_slots_state_bit_equal(heads):
    """Seven steps, another set of live slots each (the first slot dead,
    the last, a dead one between two live, none, all): after every step
    a slot the step was not live for holds the bits it held before it
    and has zeros for ``o``, the others ``kda_step``'s; slot 2 is live
    for one step and ends as that step left it."""
    state, *_ = draw(39)
    began = np.asarray(state).copy()
    mine = plain = state
    masks = [(1, 1, 0, 1), (0, 1, 0, 1), (1, 0, 0, 0), (0, 0, 0, 0),
             (1, 1, 1, 1), (0, 0, 0, 1), (1, 0, 0, 1)]
    for t, mask in enumerate(masks):
        _, *x = draw(100 + t)
        live = jnp.asarray(mask, bool)
        before = np.asarray(mine).copy()
        mine, o = interpreted(mine, *x, live, heads=heads)
        want, want_o = delta_rule.kda_step(plain[0], *x, live)
        plain = want[None]
        dead = np.flatnonzero(~np.asarray(live))
        np.testing.assert_array_equal(
            np.asarray(mine)[0, dead].view(np.uint32),
            before[0, dead].view(np.uint32))
        np.testing.assert_allclose(mine, plain, rtol=1e-5, atol=1e-5)
        alive = np.flatnonzero(np.asarray(live))
        np.testing.assert_allclose(np.asarray(o)[alive],
                                   np.asarray(want_o)[alive], rtol=1e-5,
                                   atol=1e-5)
        assert not np.asarray(o)[dead].any()
        if t == 4:
            after_its_step = np.asarray(mine)[0, 2].copy()
    np.testing.assert_array_equal(np.asarray(mine)[0, 2].view(np.uint32),
                                  after_its_step.view(np.uint32))
    assert np.abs(after_its_step - began[0, 2]).max() > 0.1
    assert np.abs(np.asarray(mine)[0, 0] - began[0, 0]).max() > 0.1


#: (backend, heads, head size, state dtype) -> the head block, or None
CHOICES = [
    ("tpu", 64, 128, "float32", 16, "the cell's state"),
    ("tpu", 8, 128, "float32", 8, "one block of eight heads"),
    ("tpu", 32, 256, "float32", 8, "16 heads of 256 x 256 do not fit"),
    ("cpu", 64, 128, "float32", None, "off the TPU"),
    ("tpu", 4, 8, "float32", None, "the rehearsal preset's 4 heads of 8"),
    ("tpu", 4, 128, "float32", None, "heads off the sublane tile"),
    ("tpu", 64, 64, "float32", None, "matrices under a lane tile"),
    ("tpu", 64, 128, "bfloat16", None, "a state that is not float32"),
]


@pytest.mark.parametrize("backend,heads,size,dtype,block,why", CHOICES,
                         ids=[c[-1].replace(" ", "_") for c in CHOICES])
def test_the_chooser_follows_the_platform_and_the_shapes(
        monkeypatch, backend, heads, size, dtype, block, why):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert delta_rule.kda_update_heads(heads, size, size, dtype) == block, why


def test_the_cells_state_takes_16_heads_a_grid_step():
    """64 heads of 128 x 128: 16 a grid step, 4.3 MiB by the kernel's own
    count (1 MiB of state in and out, twice over); 32 would fit too and
    are not taken (no faster on the chip, twice the VMEM: the module's
    docstring)."""
    assert ks.head_block(64, 128, 128, F32) == 16
    assert ks._vmem_bytes(16, 128, 128) < 4.5 * 2 ** 20
    assert ks._vmem_bytes(32, 128, 128) <= ks.VMEM_BUDGET_BYTES \
        < ks._vmem_bytes(64, 128, 128)


@pytest.mark.parametrize("case,why", [
    ((16, 128, 128, "float32", 4), "a head block off the sublane tile"),
    ((16, 128, 128, "float32", 32), "a head block that does not divide"),
    ((16, 64, 128, "float32", None), "keys under a lane tile"),
    ((4, 128, 128, "float32", None), "four heads"),
    ((16, 128, 128, "bfloat16", None), "a bfloat16 state"),
])
def test_a_shape_that_does_not_tile_is_refused(case, why):
    h, k, v, dtype, heads = case
    state, *x = draw(40, 1, h, k, v)
    with pytest.raises(ValueError, match="does not tile"):
        ks.kda_step(state.astype(dtype), *x, jnp.ones((1,), bool),
                    heads=heads, interpret=True)


def test_kda_update_takes_the_kernel_on_a_tpu_and_xlas_form_off_it(
        on_tpu, monkeypatch):
    """``delta_rule.kda_update`` where the platform says TPU and the
    shapes tile: one call of the kernel at the chooser's head block,
    whose state and live slots' ``o`` are the XLA form's, handed the
    plan ``kda_moves`` made for the step or making its own; where it
    says CPU, and at the rehearsal's heads of 8, no call, no plan, and
    every slot counted as moved."""
    state, *x = draw(41)
    live = jnp.asarray((1, 0, 0, 1), bool)
    calls = []
    plain = ks.kda_step
    monkeypatch.setattr(ks, "kda_step", lambda *a, **k: calls.append(
        (k["heads"], k["walk"])) or plain(*a, **k))
    walk, moved = delta_rule.kda_moves(H, K, V, F32, live)
    for mine, theirs in zip(walk, ks.plan(live, 1)):
        np.testing.assert_array_equal(np.asarray(mine), np.asarray(theirs))
    assert int(moved) == 2
    new, o = jax.block_until_ready(
        delta_rule.kda_update(state, *x, live, walk))
    own, own_o = jax.block_until_ready(
        delta_rule.kda_update(state, *x, live))
    assert calls == [(16, walk), (16, None)]
    np.testing.assert_array_equal(bits(new), bits(own))
    np.testing.assert_array_equal(bits(o), bits(own_o))
    small = draw(42, B, 4, 8, 8)
    assert delta_rule.kda_moves(4, 8, 8, F32, live) == (None, B)
    tiny, tiny_o = delta_rule.kda_update(*small, live)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert delta_rule.kda_moves(H, K, V, F32, live) == (None, B)
    xla, xla_o = delta_rule.kda_update(state, *x, live)
    assert len(calls) == 2                  # XLA's form, both times
    assert xla.shape == new.shape == state.shape
    np.testing.assert_allclose(new, xla, rtol=1e-5, atol=1e-6)
    alive = np.flatnonzero(np.asarray(live))
    np.testing.assert_allclose(np.asarray(o)[alive], np.asarray(xla_o)[alive],
                               rtol=1e-5, atol=1e-6)
    want, want_o = delta_rule.kda_step(small[0][0], *small[1:], live)
    np.testing.assert_array_equal(np.asarray(tiny[0]), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(tiny_o), np.asarray(want_o))


#: a period of four layers (one softmax layer, three KDA layers) whose
#: decode step tiles at 8 KDA heads of 128 and does not at 4 heads of 8
SMALL = dict(vocab_size=64, d_model=64, n_layers=4, gqa_layers=(0,),
             n_heads=4, n_kv_heads=2, head_dim=8, gate_rank=8, d_expert=32,
             n_experts=4, experts_held=(0, 4), top_k=2, max_seq=32)
TILES, TINY = dict(kda_heads=8, kda_head_dim=128), dict(kda_heads=4,
                                                        kda_head_dim=8)


def _small(kda):
    """(the model, the cache of four slots that serves it)."""
    model = arch.SolarOpen2(arch.SolarOpen2Config(**SMALL, **kda))
    return model, HybridCaches(model, 4, 32)


def _shapes(model, caches):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    k, v = jax.eval_shape(caches.new_slabs)
    slots = jax.ShapeDtypeStruct((caches.batch,), jnp.int32)
    return params, k, v, slots, slots, jax.ShapeDtypeStruct(
        (caches.batch,), bool)


@pytest.mark.parametrize("backend,kda,kernel", [
    ("tpu", TILES, 1), ("tpu", TINY, 0), ("cpu", TILES, 0),
    ("cpu", TINY, 0)], ids=["tpu_tiles", "tpu_tiny", "cpu_tiles",
                            "cpu_tiny"])
def test_the_cache_says_which_form_its_decode_step_took(
        monkeypatch, backend, kda, kernel):
    """``kda_step_kernel`` on ``kf:serve.decode_read`` is the choice
    ``kda_update`` made when the step was traced: the kernel on a TPU at
    a shape that tiles, one a KDA layer; XLA's form on the CPU, and on a
    TPU at the tiny models' 4 heads of 8.  ``state_slots_read`` is what
    the step itself says it moved -- the kernel's count of the live
    slots, every slot through XLA's form -- and ``state_bytes_read``
    those slots' matrices and every slot's tails."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model, caches = _small(kda)
    text = str(jax.make_jaxpr(caches.decode)(*_shapes(model, caches)))
    # (the jitted call is printed once and named where it is called)
    assert text.count("name=_call") == kernel * len(
        model.cfg.recurrent_layers) == kernel * 3
    assert ("pallas_call" in text) == bool(kernel)
    params = model.init(jax.random.PRNGKey(5))
    k, v = caches.new_slabs()
    live = jnp.asarray([True, False, True, True])
    with pltpu.force_tpu_interpret_mode():
        *_, out = jax.block_until_ready(caches.decode(
            params, k, v, jnp.asarray([5, 9, 11, 2]),
            jnp.asarray([3, 17, 8, 30]), live))
    tokens, says = caches.read(out, np.asarray([4, 9, 31]))
    assert says["kda_step_kernel"] == kernel
    assert says["state_slots_live"] == 3
    moved = says["state_slots_read"]
    assert moved == (3 if kernel else caches.batch) and "state_slots_moved" \
        not in says
    cfg = model.cfg
    matrices = cfg.kda_heads * cfg.kda_head_dim ** 2 * 4
    tails = caches.batch * (cfg.conv_kernel - 1) * 3 * cfg.kda_width \
        * cfg.compute_dtype.itemsize
    assert says["state_bytes_read"] == 3 * (moved * matrices + tails)
    assert tokens.shape == (caches.batch,)


def test_a_decode_step_through_the_kernel_decodes_what_xlas_form_decodes(
        on_tpu, monkeypatch):
    """One whole decode step of a small model at a tiling shape, the
    kernel interpreted, against the same step through XLA's form: the
    same live tokens and routing, states apart by float32's rounding, a dead
    slot's state and tail untouched by either."""
    model, caches = _small(TILES)
    params = model.init(jax.random.PRNGKey(3))
    k, v = caches.new_slabs()
    rs = jax.random.split(jax.random.PRNGKey(4), 8)
    fill = lambda r, a, scale: (scale * jax.random.normal(
        r, a.shape, F32)).astype(a.dtype)
    k = (fill(rs[0], k[0], 1.0), tuple(
        fill(r, s, 0.3) for r, s in zip(rs[1:4], k[1])))
    v = (fill(rs[4], v[0], 1.0), tuple(
        fill(r, t, 1.0) for r, t in zip(rs[5:8], v[1])))
    ids = jnp.asarray([5, 9, 11, 2], jnp.int32)
    pos = jnp.asarray([3, 17, 8, 31], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    kernel = jax.block_until_ready(
        caches.decode(params, k, v, ids, pos, live))
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    xla = caches.decode(params, k, v, ids, pos, live)
    # the live slots' tokens and the routing are the same (the dead
    # slot's token is nobody's: its ``o`` is zeros from the kernel); of
    # the four slots the kernel moved the three live ones and XLA's form
    # all (``state_slots_moved``, behind ``state_slots_live``)
    said = np.asarray([0, 1, 3, 4, 5, 6, 7, 9])
    np.testing.assert_array_equal(np.asarray(kernel[2])[said],
                                  np.asarray(xla[2])[said])
    assert [int(out[2][8]) for out in (kernel, xla)] == [3, 4]
    for got, want, was in zip(kernel[0][1], xla[0][1], k[1]):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        np.testing.assert_array_equal(np.asarray(got)[0, 2],
                                      np.asarray(was)[0, 2])
        assert np.abs(np.asarray(got)[0, 0] - np.asarray(was)[0, 0]
                      ).max() > 1e-3


FOOTPRINT = textwrap.dedent("""
    import sys
    import kungfu_tpu.models, kungfu_tpu.serve.engine
    import kungfu_tpu.serve.recurrent, kungfu_tpu.serve.caches
    import kungfu_tpu.serve.windowed
    heavy = ("jax.experimental.pallas", "kungfu_tpu.ops.pallas")
    before = [m for m in heavy if m in sys.modules]
    import ast, jax, jax.numpy as jnp
    from kungfu_tpu.models import cohere2_moe, solar_open2, transformer
    jax.default_backend = lambda: sys.argv[1]
    make = {"hybrid": lambda **z: solar_open2.SolarOpen2(
                solar_open2.SolarOpen2Config(**z)),
            "windowed": lambda **z: cohere2_moe.Cohere2Moe(
                cohere2_moe.Cohere2MoeConfig(**z)),
            "dense": lambda **z: transformer.Transformer(
                transformer.TransformerConfig(**z))}[sys.argv[3]]
    model = make(**ast.literal_eval(sys.argv[2]))
    caches = model.serve_caches(4, model.cfg.max_seq)
    built = [m for m in heavy if m in sys.modules]
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    k, v = jax.eval_shape(caches.new_slabs)
    slots = jax.ShapeDtypeStruct((4,), jnp.int32)
    jax.eval_shape(caches.decode, params, k, v, slots, slots,
                   jax.ShapeDtypeStruct((4,), bool))
    after = [m for m in heavy if m in sys.modules]
    print("FOOTPRINT", type(caches).__name__, before, built, after,
          [m for m in ("kda_step", "decode_attention")
           if "kungfu_tpu.ops.pallas." + m in sys.modules])
""")

LOADED = "['jax.experimental.pallas', 'kungfu_tpu.ops.pallas']"
#: the other two families at shapes the grouped-head kernel WOULD tile
#: (heads of 128 in bfloat16, eight query heads a group, positions in
#: lane tiles): on a TPU their decode steps still trace no kernel
WINDOWED = dict(vocab_size=64, d_model=64, n_layers=4, n_heads=16,
                n_kv_heads=2, head_dim=128, d_expert=32, n_experts=4,
                experts_held=(0, 4), top_k=2, n_shared=1, window=128,
                max_seq=256)
DENSE = dict(vocab_size=64, d_model=256, n_layers=1, n_heads=2, d_ff=64,
             max_seq=256, dropout=0.0, causal=True, pos="learned",
             dtype="bfloat16")


@pytest.mark.parametrize("backend,family,sizes,after,kernels", [
    ("tpu", "hybrid", {**SMALL, **TILES}, LOADED,
     "['kda_step', 'decode_attention']"),
    ("cpu", "hybrid", {**SMALL, **TILES}, "[]", "[]"),
    ("tpu", "windowed", WINDOWED, "[]", "[]"),
    ("tpu", "dense", DENSE, "[]", "[]")],
    ids=["tpu", "cpu", "tpu_windowed", "tpu_dense"])
def test_only_a_traced_kda_decode_step_imports_the_kernels(
        backend, family, sizes, after, kernels):
    """Importing the models and the serving plane, and building a
    ``HybridCaches``, loads neither Pallas nor ``kungfu_tpu.ops.pallas``
    (about a second of every serving cell's set-up, were it paid at
    import: PERF.md, PR 35).  Tracing a decode step of the hybrid cache
    where the platform says TPU does (its two choosers ask the kernels'
    modules what tiles); where it says CPU that does not either.  A
    ``WindowedCaches`` or a ``DenseCaches`` loads none whatever the
    platform and the shapes, its traced decode step included."""
    done = subprocess.run(
        [sys.executable, "-c", FOOTPRINT, backend, repr(sizes), family],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = [l for l in done.stdout.splitlines() if l.startswith("FOOTPRINT")]
    cache = {"hybrid": "HybridCaches", "windowed": "WindowedCaches",
             "dense": "DenseCaches"}[family]
    assert line == [f"FOOTPRINT {cache} [] [] {after} {kernels}"], \
        (done.stdout, done.stderr[-2000:])


def test_the_package_does_not_reexport_the_kernel():
    """``import kungfu_tpu.ops.pallas`` (the train cells' flash kernels)
    stays what it was: the KDA kernel is its own module's."""
    import kungfu_tpu.ops.pallas as package

    assert "kda_step" not in package.__all__
    # (the name is there as the submodule this file imported, no more)
    assert package.kda_step is ks and not callable(package.kda_step)
