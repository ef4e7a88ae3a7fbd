"""The generator: an open loop's schedule is its file's, the same for
every seed; a train cell's rows are a function of seed and step."""

import numpy as np

from conftest import BIG, one_schedule_whatever_the_seed
from kfbench.lib import files, traffic as gen


def test_open_loop_offers_one_multiset_whatever_the_seed():
    tr, schedule = one_schedule_whatever_the_seed("chat-open")
    assert schedule == gen.open_schedule(tr, 40.0)  # a function of the file
    assert len(gen.open_schedule(tr, 20.0)) < len(schedule)


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
