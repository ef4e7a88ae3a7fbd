"""The fused latent decode attention (``ops/pallas/latent_attention.py``)
against the two products it replaces (``models/pangu_moe.py::
absorbed_attention``'s XLA form) and against the expanded order, in
interpret mode on the CPU; that it walks only the tiles a slot's visible
rows reach, to the same bits as the whole walk; which of the two a shape
and a platform take, what the cache says of it and of the rows it read,
and what importing the serving plane costs a process that traces no
latent decode step.

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

from kungfu_tpu.models import pangu_moe as arch
from kungfu_tpu.ops.pallas import latent_attention as la
from kungfu_tpu.serve.latent import LatentCaches

BF16, F32 = jnp.bfloat16, jnp.float32
#: a shape that tiles: four slots, 16 heads, 512 positions of 128 + 64
L, B, H, S, R, ROPE, NOPE, V = 2, 4, 16, 512, 128, 64, 32, 32
TILE = 128                      # four tiles a slot
SCALE = (NOPE + ROPE) ** -0.5

#: name -> the rows each slot may see (``n``: ``pos + 1`` of a live slot,
#: 0 of one the step is not for), at four tiles of 128
CONTEXTS = {
    "ends_on_a_tiles_edge": (TILE, 2 * TILE, 3 * TILE, S),
    "one_row_into_a_tile": (TILE + 1, 2 * TILE + 1, 3 * TILE + 1, 2),
    "one_row": (1, 1, 1, 1),
    "every_row": (S, S, S, S),
    "differ_across_slots": (1, 131, 318, S),
    "a_dead_slot_between_two_live": (201, 0, 48, 0),
    "a_dead_slot_first": (0, TILE + 1, 0, S),
    "nothing_live": (0, 0, 0, 0),
}


@pytest.fixture(scope="module")
def rows():
    """(attention parameters, queries, slab) in bfloat16; slot 1 of the
    slab holds zeros, as a slot nobody was admitted to does."""
    ks = jax.random.split(jax.random.PRNGKey(35), 6)
    draw = lambda k, *dims: jax.random.normal(k, dims, F32).astype(BF16)
    ap = {"w_uk": draw(ks[0], H, NOPE, R) * 0.2,
          "w_uv": draw(ks[1], H, R, V) * 0.2}
    live = jnp.asarray([1, 0, 1, 1], BF16)[None, :, None, None, None]
    return (ap, draw(ks[2], B, H, NOPE), draw(ks[3], B, H, ROPE),
            draw(ks[4], L, B, 1, S, R) * live,
            draw(ks[5], L, B, 1, S, ROPE) * live)


@pytest.fixture
def on_tpu(monkeypatch):
    """What the code can see says TPU, and every Pallas kernel runs in
    the interpreter: ``absorbed_attention`` takes its kernel branch."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        yield


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("chunk", [TILE, 64], ids=["whole_tile", "chunks"])
@pytest.mark.parametrize("name", list(CONTEXTS))
def test_kernel_equals_xlas_two_products(rows, name, chunk):
    """Scores, softmax and weighted sum in one kernel, tile by tile with
    the softmax carried across them, give what the two einsums with the
    ``[B, H, S]`` scores between them give (``absorbed_products``),
    within bfloat16's rounding of an output of order one -- wherever a
    context ends."""
    ap, q_nope, q_rope, c, k_r = rows
    n = jnp.asarray(CONTEXTS[name], jnp.int32)
    q_lat = jnp.einsum("bhn,hnc->bhc", q_nope, ap["w_uk"])
    got = la.latent_attn(q_lat, q_rope, c, k_r, 1, n, SCALE, tile=TILE,
                         chunk=chunk, interpret=True)
    want = arch.absorbed_products(q_lat, q_rope, c, k_r, 1, n, SCALE)
    assert got.shape == (B, H, R) and got.dtype == BF16
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_allclose(_f32(got), _f32(want), atol=2e-2, rtol=2e-2)
    # a slot that sees one row attends to it and nothing else; one that
    # sees none gets zeros, from either form, and not 0/0
    for slot in np.flatnonzero(np.asarray(n) == 1):
        np.testing.assert_allclose(_f32(got[slot]), np.broadcast_to(
            _f32(c[1, slot, 0, 0]), (H, R)), atol=1e-6)
    dead = np.asarray(n) == 0
    assert not _f32(got)[dead].any() and not _f32(want)[dead].any()


@pytest.fixture(scope="module")
def dense_rows(rows):
    """``rows``' queries over a slab with no slot of zeros: every slot
    holds rows whether the step is for it or not."""
    ap, q_nope, q_rope, c, k_r = rows
    ks = jax.random.split(jax.random.PRNGKey(45), 2)
    draw = lambda k, like: jax.random.normal(k, like.shape, F32).astype(BF16)
    return (jnp.einsum("bhn,hnc->bhc", q_nope, ap["w_uk"]), q_rope,
            draw(ks[0], c), draw(ks[1], k_r))


@pytest.mark.parametrize("name", list(CONTEXTS))
def test_skipping_a_tile_changes_no_bit_of_a_live_slots_output(dense_rows,
                                                               name):
    """A tile wholly past ``n[b]`` added ``exp(-1e30 - m) = 0`` to the
    running sum and kept the accumulator: walking four tiles of 128 and
    skipping those, the kernel gives every slot that sees a row the very
    bits of the WHOLE walk -- one tile of all 512 positions, every chunk
    of 64 computed under the mask, in the same order."""
    q_lat, q_rope, c, k_r = dense_rows
    n = jnp.asarray(CONTEXTS[name], jnp.int32)
    skipped, whole = (la.latent_attn(
        q_lat, q_rope, c, k_r, 1, n, SCALE, tile=tile, chunk=64,
        interpret=True) for tile in (TILE, S))
    np.testing.assert_array_equal(_f32(skipped), _f32(whole))
    assert _f32(skipped)[np.asarray(n) > 0].any(axis=(1, 2)).all()


@pytest.mark.parametrize("name", list(CONTEXTS))
def test_a_tile_no_slot_sees_is_not_read(dense_rows, name):
    """NaNs in every tile wholly past ``n[b]``, in every row of a slot
    that sees none, and in the layer the call is not for: the output is
    what it was without them.  (Under the mask alone a NaN row would
    reach it through ``0 x NaN`` in the weighted sum.)"""
    q_lat, q_rope, c, k_r = dense_rows
    n = np.asarray(CONTEXTS[name])
    # the first row no walked tile holds, a slot
    past = (-(-n // TILE) * TILE)[None, :, None, None, None]
    unseen = jnp.arange(S)[None, None, None, :, None] >= past
    unseen = unseen | (jnp.arange(L) != 1)[:, None, None, None, None]
    assert unseen[1].sum() == B * S - la.rows_walked(jnp.asarray(n), TILE)
    run = lambda c, k_r: la.latent_attn(
        q_lat, q_rope, c, k_r, 1, jnp.asarray(n, jnp.int32), SCALE,
        tile=TILE, chunk=64, interpret=True)
    got = run(jnp.where(unseen, jnp.nan, c), jnp.where(unseen, jnp.nan, k_r))
    assert np.isfinite(_f32(got)).all()
    np.testing.assert_array_equal(_f32(got), _f32(run(c, k_r)))


@pytest.mark.parametrize("tile", [TILE, 2 * TILE])
@pytest.mark.parametrize("name", list(CONTEXTS))
def test_the_rows_walked_are_the_live_tiles_whole(name, tile):
    """What a step that calls the kernel states as read: every tile a
    visible row falls in, whole -- under a tile more than the live rows
    a live slot, and nothing for a slot that sees none."""
    n = np.asarray(CONTEXTS[name])
    walked = int(la.rows_walked(jnp.asarray(n, jnp.int32), tile))
    assert walked == sum(-(-int(x) // tile) * tile for x in n)
    assert 0 <= walked - n.sum() < tile * max((n > 0).sum(), 1)


@pytest.mark.parametrize("name", list(CONTEXTS))
def test_absorbed_attention_takes_the_kernel_and_equals_both_orders(
        rows, on_tpu, monkeypatch, name):
    """``absorbed_attention`` where the platform says TPU and the shapes
    tile: the kernel's output, ``W_uv`` applied, equals the XLA form's
    and, like it, the expanded order's over the same rows (keys and
    values of every row formed first)."""
    ap, q_nope, q_rope, c, k_r = rows
    n = jnp.asarray(CONTEXTS[name], jnp.int32)
    assert arch.absorbed_tile(H, S, R, ROPE, BF16) == 512
    calls = []
    plain = la.latent_attn
    monkeypatch.setattr(la, "latent_attn", lambda *a, **k: calls.append(
        k["tile"]) or plain(*a, **{**k, "tile": TILE}))
    got = arch.absorbed_attention(ap, q_nope, q_rope, c, k_r, 1, n, SCALE)
    assert calls == [512]
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert arch.absorbed_tile(H, S, R, ROPE, BF16) is None
    xla = arch.absorbed_attention(ap, q_nope, q_rope, c, k_r, 1, n, SCALE)
    assert calls == [512]                       # the einsums, this time
    np.testing.assert_allclose(_f32(got), _f32(xla), atol=3e-2, rtol=3e-2)
    wide = jax.tree_util.tree_map(lambda a: a.astype(F32),
                                  (ap, q_nope, q_rope, c[1][:, 0],
                                   k_r[1][:, 0]))
    k_nope, v = arch.expand(wide[0], wide[3])
    for i in range(B):
        if not n[i]:            # no row: zeros, which W_uv leaves zeros
            assert not _f32(got[i]).any() and not _f32(xla[i]).any()
            continue
        want = arch.expanded_attention(
            wide[1][i][None], wide[2][i][None], k_nope[i], wide[4][i], v[i],
            n[i][None] - 1, SCALE)[0]
        np.testing.assert_allclose(_f32(got[i]), _f32(want), atol=6e-2,
                                   rtol=3e-2)


@pytest.mark.parametrize("shape,why", [
    ((H, S, 16, ROPE), "latents off the lane tile"),
    ((H, S + 64, R, ROPE), "positions no tile divides"),
    ((6, S, R, ROPE), "heads off the sublane tile"),
    ((H, S, R, 8), "a rotary part under a bfloat16 tile"),
])
def test_a_shape_that_does_not_tile_has_no_key_tile(shape, why):
    h, s, r, rope = shape
    assert la.key_tile(s, h, r, rope, BF16) is None, why
    with pytest.raises(ValueError, match="does not tile"):
        la.latent_attn(jnp.zeros((1, h, r), BF16),
                       jnp.zeros((1, h, rope), BF16),
                       jnp.zeros((1, 1, 1, s, r), BF16),
                       jnp.zeros((1, 1, 1, s, rope), BF16), 0,
                       jnp.zeros((1,), jnp.int32), 1.0, interpret=True)


def test_the_cells_slab_takes_the_largest_tile_that_fits():
    """32 slots of 16,384 positions under 128 heads of 512 + 64: 4,096
    keys a grid step (12.4 MiB by the kernel's own count, inside the 16
    MiB the compiler gives it); twice that would not fit."""
    assert la.key_tile(16384, 128, 512, 64, BF16) == 4096
    assert la._vmem_bytes(4096, 128, 512, 64, 2) <= la.VMEM_BUDGET_BYTES \
        < la._vmem_bytes(8192, 128, 512, 64, 2)
    assert la.key_tile(S, H, R, ROPE, BF16) == 512


#: a two-layer model in bfloat16 whose decode step tiles at latents of
#: 128 and does not at 16
SMALL = dict(vocab_size=64, d_model=64, n_layers=2, n_dense=1, n_heads=8,
             qk_nope_dim=16, qk_rope_dim=16, v_head_dim=16, q_lora_rank=32,
             d_ff=64, d_expert=32, n_experts=4, experts_held=(0, 4), top_k=2,
             max_seq=384)


def _small(kv_rank, seq=256):
    """(the model, the cache of four slots of ``seq`` positions that
    serves it: 256 is one key tile a slot, 384 three of 128)."""
    model = arch.PanguMoe(arch.PanguMoeConfig(**SMALL, kv_lora_rank=kv_rank))
    return model, LatentCaches(model, 4, seq)


def _decode_jaxpr(model, caches):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    c, k_r = (jax.ShapeDtypeStruct(s, BF16) for s in caches.shapes())
    slots = jax.ShapeDtypeStruct((caches.batch,), jnp.int32)
    return str(jax.make_jaxpr(caches.decode)(
        params, c, k_r, slots, slots,
        jax.ShapeDtypeStruct((caches.batch,), bool)))


@pytest.mark.parametrize("backend,kv_rank,kernel", [
    ("tpu", 128, 1), ("tpu", 16, 0), ("cpu", 128, 0), ("cpu", 16, 0)])
def test_the_cache_says_which_form_its_decode_step_took(
        monkeypatch, backend, kv_rank, kernel):
    """``latent_attn_kernel`` on ``kf:serve.decode_read`` is the choice
    ``absorbed_attention`` made when the step was traced: the kernel on
    a TPU at a shape that tiles, one a layer; XLA's two products on the
    CPU, and on a TPU at the tiny models' latents of 16."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model, caches = _small(kv_rank)
    text = _decode_jaxpr(model, caches)
    # (the jitted call is printed once and named where it is called)
    assert text.count("name=_call") == kernel * model.cfg.n_layers
    assert ("pallas_call" in text) == bool(kernel)
    out = np.arange(caches.batch + 5, dtype=np.int32)
    tokens, says = caches.read(out, np.asarray([3, 5]))
    assert says["latent_attn_kernel"] == kernel
    # the rows read are the step's own count, the last thing it says
    assert says["latent_rows_read"] == caches.batch + 4
    assert says["latent_rows_live"] == caches.batch + 3
    assert "latent_rows_walked" not in says
    assert tokens.tolist() == list(range(caches.batch))


@pytest.mark.parametrize("backend,read", [("tpu", 128 + 128 + 384),
                                          ("cpu", 4 * 384)])
def test_the_cache_states_the_rows_its_step_read(on_tpu, monkeypatch,
                                                 backend, read):
    """``latent_rows_read`` on ``kf:serve.decode_read`` is counted IN the
    step: through the kernel the tiles it walked a layer -- one of three
    for the contexts of 1 and of 128 rows, all three for the one of 384,
    none for the slot that is not live -- and ``batch x seq`` through
    XLA's two products, which read every row under their mask; the live
    rows beside it are the same count either way."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model, caches = _small(128, seq=384)
    assert caches.attn_tile() == (128 if backend == "tpu" else None)
    params = model.init(jax.random.PRNGKey(3))
    out = caches.decode(
        params, *caches.new_slabs(), jnp.asarray([5, 9, 11, 2], jnp.int32),
        jnp.asarray([0, 127, 200, 383], jnp.int32),
        jnp.asarray([True, True, False, True]))[2]
    assert out.shape == caches.new_out().shape
    _, says = caches.read(out, np.asarray([1, 128, 384]))
    assert says["latent_rows_live"] == 1 + 128 + 384
    assert says["latent_rows_read"] == read
    assert says["latent_attn_kernel"] == (backend == "tpu")


def test_a_decode_step_through_the_kernel_decodes_what_xlas_form_decodes(
        on_tpu, monkeypatch):
    """One whole decode step of a small model at a tiling shape, the
    kernel interpreted, against the same step through the einsums: the
    same rows written, and logits apart by bfloat16's rounding."""
    model, caches = _small(128)
    params = model.init(jax.random.PRNGKey(3))
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    c, k_r = (jax.random.normal(k, s, F32).astype(BF16)
              for k, s in zip(ks, caches.shapes()))
    ids = jnp.asarray([5, 9, 11, 2], jnp.int32)
    pos = jnp.asarray([0, 127, 128, 255], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    rows = []
    plain = model.logits
    model.logits = lambda p, h: rows.append(plain(p, h)) or rows[-1]
    kernel = caches.decode(params, c, k_r, ids, pos, live)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    xla = caches.decode(params, c, k_r, ids, pos, live)
    # layer 0's new rows precede any attention and are equal; layer 1's
    # follow layer 0's output
    np.testing.assert_array_equal(_f32(kernel[0][0]), _f32(xla[0][0]))
    for a, b in zip(kernel[:2], xla[:2]):
        np.testing.assert_allclose(_f32(a), _f32(b), atol=4e-2, rtol=4e-2)
    got, want = (_f32(r) for r in rows)
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=0.05 * np.abs(want).max())


FOOTPRINT = textwrap.dedent("""
    import sys
    import kungfu_tpu.models, kungfu_tpu.serve.engine, kungfu_tpu.serve.latent
    import kungfu_tpu.serve.windowed, kungfu_tpu.serve.caches
    heavy = ("jax.experimental.pallas", "kungfu_tpu.ops.pallas")
    before = [m for m in heavy if m in sys.modules]
    import jax, jax.numpy as jnp
    from kungfu_tpu.models import pangu_moe as arch
    from kungfu_tpu.serve.latent import LatentCaches
    jax.default_backend = lambda: sys.argv[1]
    import ast
    model = arch.PanguMoe(arch.PanguMoeConfig(
        **ast.literal_eval(sys.argv[2]), kv_lora_rank=128))
    caches = LatentCaches(model, 4, 256)
    built = [m for m in heavy if m in sys.modules]
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    c, k_r = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in caches.shapes())
    slots = jax.ShapeDtypeStruct((4,), jnp.int32)
    jax.eval_shape(caches.decode, params, c, k_r, slots, slots,
                   jax.ShapeDtypeStruct((4,), bool))
    after = [m for m in heavy if m in sys.modules]
    print("FOOTPRINT", before, built, after,
          "kungfu_tpu.ops.pallas.attention" in sys.modules)
""")


@pytest.mark.parametrize("backend,after", [
    ("tpu", "['jax.experimental.pallas', 'kungfu_tpu.ops.pallas']"),
    ("cpu", "[]")])
def test_only_a_traced_latent_decode_step_imports_the_kernels(backend,
                                                              after):
    """Importing the models and the serving plane, and building a latent
    cache, loads neither Pallas nor ``kungfu_tpu.ops.pallas`` (0.9-1.0 s
    of every serving cell's set-up, were it paid at import: PERF.md, PR
    35).  Tracing a latent decode step where the platform says TPU does;
    where it says CPU that does not either."""
    done = subprocess.run(
        [sys.executable, "-c", FOOTPRINT, backend, repr(SMALL)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    line = [l for l in done.stdout.splitlines() if l.startswith("FOOTPRINT")]
    assert line == [f"FOOTPRINT [] [] {after} {backend == 'tpu'}"], \
        (done.stdout, done.stderr[-2000:])


def test_the_package_does_not_reexport_the_kernel():
    """``import kungfu_tpu.ops.pallas`` (the train cells' flash kernels)
    stays what it was: the latent kernel is its own module's."""
    import kungfu_tpu.ops.pallas as package

    assert "latent_attn" not in package.__all__
    assert not hasattr(package, "latent_attn")
