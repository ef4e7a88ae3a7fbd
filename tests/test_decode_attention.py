"""The decode step's attention over a grouped-head K/V slab as one kernel
that walks only the live tiles (``ops/pallas/decode_attention.py``)
against the XLA form it replaces on the TPU
(``models/cohere2_moe.py::attention`` under the same mask), in interpret
mode on the CPU: every count of visible rows around a tile's edge mixed
in one batch, a slot with none, the rows the walk reads, the blocks a
dead slot's grid steps hold, and which of the two forms a platform and a
shape take (``serve/recurrent.py::HybridCaches.attn_tile``).

Nothing here times anything: ``tests/test_tpu_compile.py`` compiles the
cell's decode program for a described v5e, the chip measures it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kungfu_tpu.models import cohere2_moe, solar_open2
from kungfu_tpu.ops.pallas import decode_attention as da

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
    ("tpu", 4096, 8, 32, 128, "bfloat16", None, "four query heads a group"),
    ("tpu", 4000, 8, 64, 128, "bfloat16", None, "positions off the lane tile"),
]


@pytest.mark.parametrize("backend,s,g,heads,d,dtype,tile,why", CHOICES,
                         ids=[c[-1].replace(" ", "_") for c in CHOICES])
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
    ((S, 128, 8, "bfloat16", 96), "a tile off the lane tile"),
    ((S, 128, 8, "bfloat16", 384), "a tile that does not divide"),
    ((S, 128, 8, "bfloat16", 2048), "a tile past the slab"),
    ((S, 64, 8, "bfloat16", TILE), "heads of 64"),
    ((S, 128, 4, "bfloat16", TILE), "four query heads a group"),
    ((S, 128, 8, "float32", TILE), "a float32 slab"),
])
def test_a_shape_that_does_not_tile_is_refused(case, why):
    s, d, j, dtype, tile = case
    q = jnp.zeros((1, G, j, d), dtype)
    k = jnp.zeros((1, 1, G, s, d), dtype)
    with pytest.raises(ValueError, match="does not tile"):
        da.decode_attn(q, k, k, 0, jnp.ones((1,), jnp.int32), tile=tile,
                       interpret=True)
