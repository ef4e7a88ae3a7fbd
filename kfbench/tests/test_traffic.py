"""The generator: every seed offers the same work, in another order."""

import numpy as np

from kfbench.lib import files, traffic as gen

BIG = 2 ** 31 + 12345  # the driver's seeds pass 32 signed bits


def test_open_loop_offers_one_multiset_whatever_the_seed():
    tr = files.load_traffic("chat-open")
    runs = [gen.open_schedule(tr, 40.0, seed) for seed in (1, 2, BIG)]
    sets = [sorted((p, o, w) for _, p, o, w in run) for run in runs]
    assert sets[0] == sets[1] == sets[2]
    assert [r[:3] for r in runs[0]] != [r[:3] for r in runs[1]]
    n = round(tr["rate_rps"] * 40.0)
    assert sum(w for *_, w in runs[0]) == n
    for due, p, o, w in runs[0]:
        assert p + o <= tr["max_total"] and o >= 1
        assert (0 <= due < 40.0) if w else (-tr["preroll_s"] <= due < 0)
    assert runs[2] == gen.open_schedule(tr, 40.0, BIG)


def test_packed_batches_are_a_function_of_seed_and_step():
    tr = files.load_traffic("train-packed-1k")
    x1, y1 = gen.packed_batch(tr, 50257, BIG, 7, 4)
    x2, y2 = gen.packed_batch(tr, 50257, BIG, 7, 4)
    x3, _ = gen.packed_batch(tr, 50257, BIG, 8, 4)
    assert x1.shape == y1.shape == (4, 1024) and x1.dtype == np.int32
    assert (x1 == x2).all() and (y1 == y2).all() and (x1 != x3).any()
    assert (x1[:, 1:] == y1[:, :-1]).all()
    rows = [tuple(r) for r in x1]
    assert len(set(rows)) == len(rows)  # rows that all differ
    sep = tr["documents"]["separator_id"]
    assert 2 <= (x1 == sep).sum() <= 64  # documents of a few hundred tokens
