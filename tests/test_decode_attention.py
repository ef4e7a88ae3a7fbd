"""The decode step's attention over a grouped-head K/V slab as one kernel
that walks only the live tiles (``ops/pallas/decode_attention.py``)
against the XLA form it replaces on the TPU
(``models/cohere2_moe.py::attention`` under the same mask), in interpret
mode on the CPU: every count of visible rows around a tile's edge mixed
in one batch, a slot with none, the rows the walk reads, the blocks a
dead slot's grid steps hold, and which of the two forms a platform and a
shape take (``serve/recurrent.py::HybridCaches.attn_tile``).  Then the
same kernel over a slot's TWO visible runs, as ``serve/pooled.py`` calls
it: the open window's exact rows and the closed windows' chunk rows
under ONE softmax, against ``evabyte.eva_attention`` under
``PooledCaches.visible_rows``, with one query head a key/value head.

Nothing here times anything: ``tests/test_tpu_compile.py`` compiles the
cell's decode program for a described v5e, the chip measures it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import cohere2_moe, evabyte, solar_open2
from kungfu_tpu.ops.pallas import decode_attention as da
from kungfu_tpu.serve.pooled import PooledCaches

BF16 = jnp.bfloat16
#: two layers of six slots, two key/value heads of 128 over 512 positions,
#: walked 128 keys a grid step
L, B, G, D, S, TILE = 2, 6, 2, 128, 512, 128
#: visible rows a slot: none, one, and either side of a tile's edge
EDGES = (0, 1, TILE - 1, TILE, TILE + 1, S)
#: name -> the order the slots hold them in (a slot with no row first,
#: last, and twice between live ones)
ORDERS = {"dead_first": EDGES, "dead_last": EDGES[::-1],
          "dead_between": (S, 0, TILE + 1, 0, 1, TILE)}
#: both products round to bfloat16 in XLA's form (its logits too, which
#: the kernel keeps in float32): outputs of size 1 agree to 2-4e-3
TOL = 2e-2


def draw(seed, j, slots=B):
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(r[0], (slots, G, j, D), BF16),
            jax.random.normal(r[1], (L, slots, G, S, D), BF16),
            jax.random.normal(r[2], (L, slots, G, S, D), BF16))


def xla(q, k, v, li, n):
    """``cohere2_moe.attention`` for one query row a slot that sees its
    slab's first ``n`` rows."""
    see = (jnp.arange(k.shape[3]) < n[:, None])[:, None, None, None]
    return cohere2_moe.attention(q[:, None], k[li], v[li], see)[:, 0]


def f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("j", [8, 16])
@pytest.mark.parametrize("order", list(ORDERS))
def test_kernel_equals_xlas_attention_under_the_mask(order, j):
    """Every slot's output is ``cohere2_moe.attention``'s over the rows
    it may see, whichever layer is asked for and wherever the slots
    without a row lie; such a slot gets zeros, and finite ones."""
    q, k, v = draw(51, j)
    n = jnp.asarray(ORDERS[order], jnp.int32)
    for li in range(L):
        got = da.decode_attn(q, k, v, li, n, tile=TILE, interpret=True)
        assert got.shape == q.shape and got.dtype == BF16
        want = xla(q, k, v, li, n)
        live = np.asarray(n) > 0
        np.testing.assert_allclose(f32(got)[live], f32(want)[live],
                                   atol=TOL, rtol=TOL)
        assert np.isfinite(f32(got)).all()
        assert not f32(got)[~live].any()
    # (the layers differ: the index map did pick one)
    assert np.abs(f32(xla(q, k, v, 0, n)) - f32(xla(q, k, v, 1, n))
                  ).max() > 0.1


@pytest.mark.parametrize("tile", [128, 256, 512])
def test_one_row_is_that_rows_value_at_any_tile(tile):
    """A slot that sees one row returns that row of V for every query
    head of its group, whatever the tile."""
    q, k, v = draw(52, 8, slots=2)
    n = jnp.asarray([1, 1], jnp.int32)
    got = da.decode_attn(q, k, v, 1, n, tile=tile, interpret=True)
    want = np.broadcast_to(f32(v)[1, :, :, None, 0], got.shape)
    np.testing.assert_array_equal(f32(got), want)


@pytest.mark.parametrize("order", list(ORDERS))
@pytest.mark.parametrize("tile", [128, 256])
def test_the_rows_walked_are_the_live_tiles_whole(order, tile):
    n = np.asarray(ORDERS[order])
    want = sum(-(-int(x) // tile) * tile for x in n)
    assert int(da.rows_walked(jnp.asarray(n, jnp.int32), tile)) == want
    assert want >= n.sum() and want - n.sum() < tile * (n > 0).sum()


@pytest.mark.parametrize("order", list(ORDERS))
def test_a_grid_step_without_rows_holds_the_block_before_it(order):
    """The blocks the index map asks for, over the whole grid: a live
    slot walks its own tiles ``0 .. ceil(n / tile) - 1`` and stays on
    the last; a slot with no row stays on the block the step before it
    held, so the pipeline copies nothing for it; and no tile past a
    slot's rows is ever asked for."""
    n = np.asarray(ORDERS[order])
    at, lo, hi = (np.asarray(x) for x in da._walk(
        jnp.asarray(n, jnp.int32), TILE))
    blocks = [(int(at[b]), int(np.clip(t, lo[b], hi[b])))
              for b in range(B) for t in range(S // TILE)]
    copies = 1 + sum(a != b for a, b in zip(blocks, blocks[1:]))
    live_tiles = sum(-(-int(x) // TILE) for x in n)
    # (with a dead slot first, tile 0 of slot 0 is held before anything)
    assert copies == live_tiles + (n[0] == 0)
    for b in range(B):
        mine = blocks[b * S // TILE:(b + 1) * S // TILE]
        if n[b]:
            last = -(-int(n[b]) // TILE) - 1
            assert mine == [(b, min(t, last)) for t in range(S // TILE)]
        else:
            assert len(set(mine)) == 1
            assert mine[0] == (blocks[b * S // TILE - 1] if b else (0, 0))


#: (backend, positions, key/value heads, query heads, head size, dtype)
#: -> the key tile, or None
CHOICES = [
    ("tpu", 4096, 8, 64, 128, "bfloat16", 512, "the reasoning cell's slab"),
    ("tpu", 8192, 8, 128, 128, "bfloat16", 512, "the mixedlen cell's slab"),
    ("tpu", 384, 2, 16, 128, "bfloat16", 128, "a slab of three lane tiles"),
    ("cpu", 4096, 8, 64, 128, "bfloat16", None, "off the TPU"),
    ("tpu", 1024, 20, 20, 64, "bfloat16", None, "the dense slab's heads of 64"),
    ("tpu", 32, 2, 4, 8, "bfloat16", None, "the rehearsal preset's heads of 8"),
    ("tpu", 4096, 8, 64, 128, "float32", None, "a slab that is not bfloat16"),
    ("tpu", 4096, 8, 32, 128, "bfloat16", 512,
     "four query heads a group, padded to eight rows"),
    ("tpu", 4000, 8, 64, 128, "bfloat16", None, "positions off the lane tile"),
]


@pytest.mark.parametrize("backend,s,g,heads,d,dtype,tile,why", CHOICES,
                         ids=[c[-1].replace(" ", "_").replace(",", "")
                              for c in CHOICES])
def test_the_picker_follows_the_platform_and_the_shapes(
        monkeypatch, backend, s, g, heads, d, dtype, tile, why):
    """The choice is the cache's, made when it is first asked and the
    same from then on: the step that is traced later and the span that
    says which form ran cannot disagree."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    caches = solar_open2.SolarOpen2(solar_open2.SolarOpen2Config(
        n_layers=1, gqa_layers=(0,), n_heads=heads, n_kv_heads=g,
        head_dim=d, dtype=dtype)).serve_caches(4, s)
    assert caches.attn_tile == tile, why
    assert caches.kv_attn_kernel == int(tile is not None)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert caches.attn_tile == tile


def test_the_cells_slab_takes_512_keys_a_grid_step():
    """8 key/value heads of 128: 512 keys of K and of V are 2 MiB, twice
    over and with the scores 4.9 MiB by the kernel's own count; 1,024
    would fit too and are not taken (slower on the chip: the module's
    docstring), 2,048 would not."""
    assert da.key_tile(4096, 8, 8, 128, BF16) == 512
    assert da._vmem_bytes(512, 8, 8, 128, 2) < 5 * 2 ** 20
    assert da._vmem_bytes(1024, 8, 8, 128, 2) <= da.VMEM_BUDGET_BYTES \
        < da._vmem_bytes(2048, 8, 8, 128, 2)


@pytest.mark.parametrize("case,why", [
    ((S, 128, 8, "bfloat16", 96, (0,)), "a tile off the lane tile"),
    ((S, 128, 8, "bfloat16", 384, (0,)), "a tile that does not divide"),
    ((S, 128, 8, "bfloat16", 2048, (0,)), "a tile past the slab"),
    ((S, 64, 8, "bfloat16", TILE, (0,)), "heads of 64"),
    ((S, 128, 8, "float32", TILE, (0,)), "a float32 slab"),
    ((S, 128, 1, "bfloat16", 256, (0, 384)), "a run that starts off a tile"),
    ((S, 128, 1, "bfloat16", TILE, (0, 320)), "a run no tile starts at"),
])
def test_a_shape_that_does_not_tile_is_refused(case, why):
    s, d, j, dtype, tile, starts = case
    q = jnp.zeros((1, G, j, d), dtype)
    k = jnp.zeros((1, 1, G, s, d), dtype)
    with pytest.raises(ValueError, match="does not tile"):
        da.decode_attn(q, k, k, 0, jnp.ones((len(starts), 1), jnp.int32),
                       tile=tile, starts=starts, interpret=True)


@pytest.mark.parametrize("starts", [(128,), (128, 256), (0, 256, 128),
                                    (0, 128, 128)])
def test_runs_start_at_row_0_and_in_order(starts):
    q = jnp.zeros((1, G, 8, D), BF16)
    k = jnp.zeros((1, 1, G, S, D), BF16)
    with pytest.raises(ValueError, match="in order"):
        da.decode_attn(q, k, k, 0, jnp.ones((len(starts), 1), jnp.int32),
                       tile=TILE, starts=starts, interpret=True)


def test_four_query_heads_a_group_are_padded_to_eight_rows():
    """Any number of query heads a group is served: the products are
    made with whole sublane tiles of query rows, the padding's outputs
    dropped."""
    q, k, v = draw(53, 8)
    n = jnp.asarray(EDGES, jnp.int32)
    whole = da.decode_attn(q, k, v, 1, n, tile=TILE, interpret=True)
    part = da.decode_attn(q[:, :, :4], k, v, 1, n, tile=TILE, interpret=True)
    assert part.shape == (B, G, 4, D)
    np.testing.assert_array_equal(f32(part), f32(whole)[:, :, :4])


# -- two runs a slot: the open window's exact rows, the closed windows'
# -- chunk rows (serve/pooled.py) ----------------------------------------------
#: windows of 256 positions in chunks of 2, slots of 1,024: a slot and
#: layer keeps 256 exact rows and 512 chunk rows, of which a closed
#: window brings 128 into sight
EVA_W, EVA_C, EVA_SEQ = 256, 2, 1024
EVA_STARTS = (0, EVA_W)
#: name -> (position, live) a slot
EVA_SLOTS = {
    "under one window: no chunk run": (100, True),
    "the last row of a window: every exact row, no chunk row yet": (255, True),
    "a slot the step is not for": (300, False),
    "the first row of the next: one exact row, 128 chunk rows": (256, True),
    "the slot's last position: every chunk row a context ever sees":
        (EVA_SEQ - 1, True),
    "a tile's edge in both runs": (3 * EVA_W - 129, True),
}
#: (key/value heads, the order of the slots): one query head each
EVA_CASES = {
    "dead_between": (2, (0, 1, 2, 3, 4, 5)),
    "dead_first": (2, (2, 4, 0, 5, 1, 3)),
    "dead_last": (2, (3, 5, 4, 1, 0, 2)),
    "32x1_heads": (32, (0, 1, 2, 3, 4, 5)),
}


def pooled(heads, slots=len(EVA_SLOTS)):
    return PooledCaches(evabyte.EvaByte(evabyte.EvaByteConfig(
        d_model=heads * D, n_layers=L, n_heads=heads, head_dim=D, d_ff=64,
        chunk_size=EVA_C, window_size=EVA_W, max_seq=EVA_SEQ)),
        slots, EVA_SEQ)


def eva_slots(order):
    """(positions, live) of :data:`EVA_SLOTS` in ``order``."""
    slots = list(EVA_SLOTS.values())
    return tuple(jnp.asarray([slots[i][x] for i in order]) for x in (0, 1))


def eva_draw(seed, caches):
    r = jax.random.split(jax.random.PRNGKey(seed), 3)
    _, b, h, _, d = caches.shape
    return (jax.random.normal(r[0], (b, 1, h, d), BF16),
            jax.random.normal(r[1], caches.shape, BF16),
            jax.random.normal(r[2], caches.shape, BF16))


@pytest.mark.parametrize("case", list(EVA_CASES))
def test_two_runs_equal_eva_attention_under_visible_rows(case):
    """ONE softmax over a slot's exact rows and chunk rows, walked as
    two runs of the slab's tiles, is ``eva_attention`` under
    ``PooledCaches.visible_rows`` at the same positions -- whichever
    layer, wherever the slot the step is not for lies (zeros back) --
    and the counts the cache hands the kernel are that mask's."""
    heads, order = EVA_CASES[case]
    caches = pooled(heads)
    assert caches.shape == (L, 6, heads, EVA_W + EVA_SEQ // EVA_C, D)
    pos, live = eva_slots(order)
    n = caches.visible_runs(pos, live)
    see = caches.visible_rows(pos)
    exact, chunk = np.asarray(see)[:, :EVA_W], np.asarray(see)[:, EVA_W:]
    for run, rows in zip(np.asarray(n), (exact, chunk)):
        # (the mask's rows are each run's FIRST ones)
        assert [r[:c].all() and not r[c:].any() for r, c in zip(
            rows[np.asarray(live)], run[np.asarray(live)])] == [True] * 5
        assert not run[~np.asarray(live)].any()
    q, k, v = eva_draw(54, caches)
    for li in range(L):
        got = da.decode_attn(q[:, 0, :, None], k, v, li, n, tile=TILE,
                             starts=EVA_STARTS, interpret=True)
        assert got.shape == (6, heads, 1, D) and got.dtype == BF16
        want = evabyte.eva_attention(q, k[li], v[li], see[:, None, None])
        alive = np.asarray(live)
        np.testing.assert_allclose(f32(got)[alive, :, 0],
                                   f32(want)[alive, 0], atol=TOL, rtol=TOL)
        assert np.isfinite(f32(got)).all() and not f32(got)[~alive].any()


def test_both_runs_whole_is_the_softmax_over_every_row():
    """Counts no position reaches (every exact row AND every chunk
    row): the walk is every tile of the slab, the output the plain
    softmax over all of it."""
    caches = pooled(2, slots=2)
    q, k, v = eva_draw(55, caches)
    n = jnp.asarray([[EVA_W] * 2, [EVA_SEQ // EVA_C] * 2], jnp.int32)
    got = da.decode_attn(q[:, 0, :, None], k, v, 1, n, tile=TILE,
                         starts=EVA_STARTS, interpret=True)
    want = evabyte.eva_attention(q, k[1], v[1], jnp.ones((), bool))
    np.testing.assert_allclose(f32(got)[:, :, 0], f32(want)[:, 0],
                               atol=TOL, rtol=TOL)
    assert int(da.rows_walked(n, TILE)) == 2 * caches.shape[3]


def test_an_exact_run_of_one_row_and_no_chunk_row_is_that_rows_value():
    caches = pooled(2, slots=2)
    q, k, v = eva_draw(56, caches)
    n = caches.visible_runs(jnp.asarray([0, 0]), jnp.asarray([True, True]))
    got = da.decode_attn(q[:, 0, :, None], k, v, 0, n, tile=TILE,
                         starts=EVA_STARTS, interpret=True)
    np.testing.assert_array_equal(f32(got)[:, :, 0], f32(v)[0, :, :, 0])


@pytest.mark.parametrize("tile", [128, 256])
def test_the_rows_walked_of_two_runs_are_each_runs_live_tiles(tile):
    """Against a count by hand: an exact run of 101 rows and no chunk
    row is one tile; 1 and 128 a tile each; 256 and 384 are 2 + 3 tiles
    of 128 or 1 + 2 of 256; the slot the step is not for, none."""
    caches = pooled(2)
    pos, live = eva_slots(range(6))
    n = caches.visible_runs(pos, live)
    assert np.asarray(n).tolist() == [[101, 256, 0, 1, 256, 128],
                                      [0, 0, 0, 128, 384, 256]]
    by_hand = {128: ([128, 256, 0, 128, 256, 128], [0, 0, 0, 128, 384, 256]),
               256: ([256, 256, 0, 256, 256, 256], [0, 0, 0, 256, 512, 256])}
    exact, chunk = (int(da.rows_walked(x, tile)) for x in n)
    assert (exact, chunk) == tuple(sum(x) for x in by_hand[tile])
    assert int(da.rows_walked(n, tile)) == exact + chunk


@pytest.mark.parametrize("case", ["dead_between", "dead_first", "dead_last"])
def test_a_slots_grid_steps_hold_its_first_runs_live_tiles_then_its_seconds(
        case):
    """The blocks the index map asks for, over the whole grid: a live
    slot holds its exact run's live tiles, then its chunk run's from row
    ``W`` on, then stays on the last; a slot with no row stays on the
    block the step before it held; so the pipeline copies each live tile
    once and nothing else, and no tile past a run's rows is asked
    for."""
    _, order = EVA_CASES[case]
    caches = pooled(2)
    pos, live = eva_slots(order)
    n = np.asarray(caches.visible_runs(pos, live))
    at, *steps = (np.asarray(x) for x in da._walk(
        jnp.asarray(n), TILE, EVA_STARTS))
    assert len(steps) == 4 and all(x.dtype == np.int32 for x in steps)
    n_t = caches.shape[3] // TILE
    blocks = [(int(at[b]), int(da._tile_at(b, t, *steps)))
              for b in range(6) for t in range(n_t)]
    first = EVA_W // TILE
    for b in range(6):
        mine = blocks[b * n_t:(b + 1) * n_t]
        tiles = [t for t in range(-(-n[0, b] // TILE))] + [
            first + t for t in range(-(-n[1, b] // TILE))]
        if tiles:
            assert mine == [(b, x) for x in tiles] + [(b, tiles[-1])] * (
                n_t - len(tiles))
        else:
            assert len(set(mine)) == 1
            assert mine[0] == (blocks[b * n_t - 1] if b else (0, 0))
    copies = 1 + sum(a != b for a, b in zip(blocks, blocks[1:]))
    assert copies == int((-(-n // TILE)).sum()) + (not n[:, 0].any())


def test_one_run_walks_as_before_for_the_hybrid_caches_shapes():
    """``HybridCaches``' call -- one run from row 0, 8 x 8 heads of 128
    over 4,096 positions, 128 slots -- is handed the tile, the grid and
    the walk it was handed before the kernel took runs: ``key_tile`` 512
    whether asked with the run or without, three vectors from ``_walk``
    and they PR 39's, the index map ``clip(t, lo, hi)``, five vectors of
    scalars ahead of the operands."""
    slots, s = 128, 4096
    assert da.key_tile(s, 8, 8, 128, BF16) == 512 \
        == da.key_tile(s, 8, 8, 128, BF16, starts=(0,))
    rng = np.random.default_rng(7)
    n = np.where(rng.random(slots) < 0.43, rng.integers(1, s + 1, slots), 0)
    n[:3], n[-2:] = (0, s, 512), (513, 0)
    walk = [np.asarray(x) for x in da._walk(jnp.asarray(n, jnp.int32), 512)]
    assert len(walk) == 3
    # PR 39's lines
    live = np.where(n > 0, np.arange(slots), -1)
    at = np.maximum(np.maximum.accumulate(live), 0)
    hi = np.maximum(-(-n[at] // 512) - 1, 0)
    for got, want in zip(walk, (at, np.where(n > 0, 0, hi), hi)):
        assert got.dtype == np.int32 and got.tolist() == want.tolist()
    _, lo, hi = walk
    for b in (0, 1, 2, 77, 126, 127):
        assert [int(da._tile_at(b, t, lo, hi)) for t in range(8)] \
            == np.clip(np.arange(8), lo[b], hi[b]).tolist()
    shapes = [jax.ShapeDtypeStruct(x, BF16) for x in (
        (slots, 8, 8, 128), (1, slots, 8, s, 128), (1, slots, 8, s, 128))]
    traced = jax.make_jaxpr(lambda q, k, v: da.decode_attn(
        q, k, v, 0, jnp.asarray(n, jnp.int32), tile=512))(*shapes)
    assert " pad" not in str(traced)
    (call,), = [[e for e in inner.eqns if e.primitive.name == "pallas_call"]
                for e in traced.eqns if e.primitive.name == "jit"
                for inner in [e.params["jaxpr"].jaxpr]]
    grid = call.params["grid_mapping"]
    assert grid.grid == (slots, 8) and grid.num_index_operands == 5
    assert [x.aval.shape for x in call.invars] == [(1,)] + [(slots,)] * 4 \
        + [x.shape for x in shapes]
