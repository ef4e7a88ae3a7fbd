"""What the tests of the engine's one step of lookahead share
(``tests/test_serve.py`` for the dense caches, ``tests/
test_cohere2_moe.py`` for the window rings): a recorder of the engine's
spans, the committed pages of a pool as bytes, ONE schedule of mixed
requests, so that two engines driven through it take the same slots at
the same calls, the comparison of what such a run committed, every
``kf:serve.decode_read`` of a run beside the contexts of the tokens it
handed out (``tests/test_serve_kv_rows.py``), and the steer under which
a decode step takes its attention kernel, interpreted
(``tests/test_solar_open2.py``, ``tests/test_evabyte.py``)."""

from __future__ import annotations

from kungfu_tpu.monitor import timeline


class Span:
    def __init__(self, log, name, attrs):
        self.name, self.attrs = name, dict(attrs)
        log.append(self)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        self.attrs.update(attrs)


def record_spans(monkeypatch) -> list:
    """Every ``timeline.span`` opened from here on, in order."""
    log: list = []
    monkeypatch.setattr(timeline, "span",
                        lambda kind, name, **attrs: Span(log, name, attrs))
    return log


def says_tpu(monkeypatch):
    """What the code can see says TPU, so a decode step takes its
    kernel branch, and ``ops/pallas/decode_attention.py``'s kernel (and
    ``row_write.py``'s, where a cache writes its rows by it) runs in
    Pallas's plain interpreter (JAX operations in the calling program:
    nothing that calls back into Python from a step the engine has
    dispatched ahead)."""
    import functools

    import jax

    from kungfu_tpu.ops.pallas import decode_attention, row_write

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(decode_attention, "decode_attn", functools.partial(
        decode_attention.decode_attn, interpret=True))
    monkeypatch.setattr(row_write, "write_rows", functools.partial(
        row_write.write_rows, interpret=True))


def decode_reads(eng, spans, asked: dict) -> list:
    """Drive ``eng`` through ``asked`` (rid -> (prompt, max_new)), all
    submitted before the first step, until it is idle; ``spans`` is
    :func:`record_spans`'s log.  Returns, for every
    ``kf:serve.decode_read``, (its attrs, the contexts of the rows it
    handed out): a decode step's token is its request's ``n``-th, made
    from a row that attended over the prompt and the ``n - 1`` tokens
    before it, its own position among them -- a plain count from the
    requests' own lengths, which knows nothing of slots or flights.  A
    row the engine drops (its request ended on ``eos_id`` in the step
    before) hands out no token and is in no count."""
    for rid, (prompt, max_new) in asked.items():
        eng.submit(rid, prompt, max_new)
    out = []
    while eng.pending_count or eng.active_count or eng._flight is not None:
        seen = len(spans)
        events = eng.step()
        reads = [s for s in spans[seen:] if s.name == "decode_read"]
        assert len(reads) <= 1
        for read in reads:
            out.append((read.attrs, [
                len(asked[e["rid"]][0]) + e["n"] - 1 for e in events
                if e["kind"] == "token" and e["n"] > 1]))
    return out


def committed(pool) -> dict:
    """chain key -> (the tokens it covers, K bytes, V bytes, whole) of
    every committed page."""
    return {key: (tuple(pool._pages[pid].prefix.tolist()),
                  pool._pages[pid].k.tobytes(), pool._pages[pid].v.tobytes(),
                  pool._pages[pid].whole)
            for key, pid in pool._by_key.items()}


def mixed_run(eng, asked: dict):
    """Drive ``eng`` (three slots) through the mixed set ``asked``
    (rid -> (prompt, max_new)): ``by_n`` and ``stops`` and ``dropped``
    fill the slots; ``dropped`` is cancelled while a step that computes
    its row is in flight; ``late`` is admitted into the slot that frees,
    while the others decode; once ``stops`` has ended, ``next`` takes
    the slot it left.  Returns (events, rid -> slot)."""
    events, slots = [], {}

    def step():
        events.extend(eng.step())
        for slot, r in eng._active.items():
            slots.setdefault(r.rid, slot)

    def done(rid):
        return any(e["kind"] == "done" and e["rid"] == rid for e in events)

    for rid in ("by_n", "stops", "dropped"):
        eng.submit(rid, *asked[rid])
    for _ in range(5):
        step()
    assert eng.active_count == 3
    assert "dropped" in {r.rid for r in eng._flight.rows.values()}
    assert eng.cancel("dropped")            # its step is on the device
    eng.submit("late", *asked["late"])
    step()                                  # retires it, admits ``late``
    assert slots["late"] == slots["dropped"]
    while not done("stops"):
        step()
    assert not done("by_n") or not done("late")   # others still decode
    eng.submit("next", *asked["next"])
    step()
    assert slots["next"] == slots["stops"]
    events.extend(eng.drain())
    assert eng._flight is None and not eng.active_count
    return events, slots


def check_committed(fresh_engine, asked: dict, want: dict, events, slots,
                    pool, against: str) -> None:
    """``pool``, after ``mixed_run`` over ``asked`` with an ``eos_id``
    (``want``: rid -> the tokens each request stopped at), holds byte
    for byte and under the same chains what engines without one hold
    that never compute a row in vain: ``same_schedule`` one fresh engine
    through the same run at the lengths the requests stopped at (what
    differs is the discarded row alone), ``alone`` one fresh engine a
    request, which has no step in flight behind a request that is
    finishing."""
    stopped = {rid: (asked[rid][0], len(want[rid])) for rid in asked}
    stopped["dropped"] = asked["dropped"]
    have = committed(pool)
    assert have
    if against == "same_schedule":
        plain = fresh_engine()
        ev, sl = mixed_run(plain, stopped)
        assert tokens_of(ev) == tokens_of(events) and sl == slots
        assert committed(plain.pool) == have
        return
    alone = {}
    for rid in set(asked) - {"dropped"}:
        one = fresh_engine()
        one.submit(rid, *stopped[rid])
        one.drain()
        alone.update(committed(one.pool))
    assert alone == have


def tokens_of(events) -> dict:
    return {e["rid"]: e["tokens"] for e in events if e["kind"] == "done"}


def until_eos(tokens, eos):
    """``tokens`` up to and with the first ``eos``."""
    return tokens[:tokens.index(eos) + 1] if eos in tokens else tokens


def pick_eos(reference: dict, asked: dict, rid: str = "stops",
             earliest: int = 3):
    """A token of ``rid``'s reference continuation, not before index
    ``earliest`` nor in its last two, that no other request of ``asked``
    makes within its budget and ``rid`` not earlier: as ``eos_id`` it
    ends ``rid`` early and nothing else."""
    own = reference[rid][:asked[rid][1]]
    others = {t for r, (_, n) in asked.items() if r != rid
              for t in reference[r][:n]}
    for i in range(earliest, len(own) - 2):
        if own[i] not in others and own[i] not in own[:i]:
            return own[i]
    raise AssertionError("no token ends the request early and alone")
