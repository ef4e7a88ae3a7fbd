"""The one generator of traffic.  A traffic mix is a data file of
parameters; everything here is driven by them and by ``--seed``.

The file fixes the work offered and, for an open loop, its schedule:
which request is due at which instant, drawn once from the file's
``schedule_seed``.  Two runs of a cell offer the same requests at the
same instants.  ``--seed`` draws what must differ between runs and what
the comparison needs -- the weights, every request's token ids, the
sample held against the reference -- and none of it changes the amount
of work or when it arrives: a step whose time follows the contexts that
are live together reads the same in every run.
"""

from __future__ import annotations

import numpy as np

from kfbench.lib.stats import dist_quantile

# --seed may exceed 32 signed bits; numpy takes any non-negative integer


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


# -- training: packed documents ---------------------------------------------

def packed_batch(traffic: dict, vocab: int, seed: int, step: int,
                 rows: int):
    """(ids, targets), both [rows, seq_len] int32: documents of the
    stated length distribution, random ids, joined by the separator and
    cut into full rows.  A function of (seed, step) alone, so that the
    reference can be fed the very rows the program saw."""
    g = rng(seed, 1, step)
    doc = traffic["documents"]
    need = rows * (traffic["seq_len"] + 1)
    toks = g.integers(0, vocab, need, dtype=np.int32)
    at = 0
    while at < need:  # one separator closes each document
        at += dist_quantile(doc, float(g.uniform(1e-6, 1 - 1e-6)))
        if at < need:
            toks[at] = doc["separator_id"] % vocab
        at += 1
    toks = toks.reshape(rows, traffic["seq_len"] + 1)
    return toks[:, :-1], toks[:, 1:]


# -- serving: requests --------------------------------------------------------

def length_pairs(traffic: dict, n: int):
    """n (prompt, output) lengths: the quantiles (i + 1/2) / n of the
    two stated distributions, paired by a permutation fixed in the file
    (``pairing_seed``), with prompt + output kept within ``max_total``.
    The multiset depends on n and the file alone."""
    qs = [(i + 0.5) / n for i in range(n)]
    prompts = [dist_quantile(traffic["prompt"], u) for u in qs]
    outputs = [dist_quantile(traffic["output"], u) for u in qs]
    perm = np.random.default_rng(traffic["pairing_seed"]).permutation(n)
    pairs = []
    for i in range(n):
        p, o = prompts[i], outputs[int(perm[i])]
        pairs.append((p, min(o, traffic["max_total"] - p)))
    return pairs


def open_schedule(traffic: dict, seconds: float):
    """Open loop: ``(due_s, prompt_len, output_len, in_window)`` sorted
    by due time, relative to the window's start.  The pre-roll (negative
    due times) and the window each offer a number and a multiset of
    requests fixed by the file, in an order and at due times drawn from
    the file's ``schedule_seed`` -- one draw of a Poisson process given
    its count, that is sorted uniform draws.  ``--seed`` has no part in
    it (``tools/sweep_fresh.py`` states a seed a window: a knee has to
    hold over draws)."""
    out = []
    for stream, t0, span in ((2, -traffic["preroll_s"], traffic["preroll_s"]),
                             (3, 0.0, seconds)):
        n = max(1, round(traffic["rate_rps"] * span))
        g = rng(traffic["schedule_seed"], stream)
        pairs = length_pairs(traffic, n)
        order = g.permutation(n)
        dues = np.sort(g.uniform(t0, t0 + span, n))
        out += [(float(dues[i]), *pairs[int(order[i])], t0 >= 0.0)
                for i in range(n)]
    return out


def prompt_ids(vocab: int, seed: int, index: int, n: int):
    """Random ids from ``--seed``: no two requests share a prefix, and
    no two seeds a prompt."""
    return rng(seed, 5, index).integers(0, vocab, n).tolist()
